"""Program-level pass framework.

Reference analogue: paddle/fluid/framework/ir/ — ir::Graph (graph.h:63),
Pass/PassRegistry (pass.h:32), GraphPatternDetector, and the fusion pass
suite chained by BuildStrategy (details/build_strategy.cc:27).

TPU redesign: most reference passes exist to pre-fuse kernels (fc_fuse,
conv_bn, fuse_elewise_add_act) — XLA's fusion subsumes them, so the fusion
passes here are *structural parity* rewrites kept for program inspection and
op-count parity, while graph_viz / is_test / memory passes carry real
behavior. The pass substrate works on the Program in place (the Program IS
the graph: ops + var def/use edges), mirroring ir::Pass::ApplyImpl.
"""

from .framework import Program

__all__ = ["Pass", "register_pass", "get_pass", "apply_passes",
           "registered_passes"]

_PASS_REGISTRY = {}


class Pass:
    """reference ir/pass.h:32. Subclasses implement apply_impl(program)."""

    name = None

    def __init__(self, **attrs):
        self.attrs = dict(attrs)

    def set(self, key, value):
        self.attrs[key] = value
        return self

    def get(self, key, default=None):
        return self.attrs.get(key, default)

    def apply(self, program):
        out = self.apply_impl(program)
        program._bump_version()
        return out if out is not None else program

    def apply_impl(self, program):
        raise NotImplementedError


def register_pass(cls):
    assert cls.name, "pass needs a name"
    _PASS_REGISTRY[cls.name] = cls
    return cls


def get_pass(name, **attrs):
    return _PASS_REGISTRY[name](**attrs)


def registered_passes():
    return sorted(_PASS_REGISTRY)


def apply_passes(program, names, **attrs):
    for n in names:
        program = get_pass(n, **attrs).apply(program)
    return program


def use_count(block, var_name, _seen=None):
    """Number of ops in `block` consuming var_name (the reference's
    intermediate-node single-consumer rule; shared by the adjacency
    passes and GraphPatternDetector). Reads hidden inside nested
    sub-blocks (conditional_block/while declare outputs={} at the parent
    level) count too — a fusion must not delete an op whose output a
    sub-block still reads."""
    _seen = _seen if _seen is not None else set()
    n_uses = 0
    for o in block.ops:
        n_uses += sum(1 for ns in o.inputs.values() for n in ns
                      if n == var_name)
        sub = o.attrs.get("sub_block")
        if sub is not None and id(sub) not in _seen:
            _seen.add(id(sub))
            n_uses += use_count(sub, var_name, _seen)
    return n_uses


# ---------------------------------------------------------------------------
# concrete passes
# ---------------------------------------------------------------------------

@register_pass
class GraphVizPass(Pass):
    """ir/graph_viz_pass.cc: dump the op/var graph as graphviz dot."""

    name = "graph_viz_pass"

    def apply_impl(self, program):
        from .debugger import draw_block_graphviz
        path = self.get("graph_viz_path", "./program.dot")
        draw_block_graphviz(program.global_block(), path=path)
        return program


@register_pass
class IsTestPass(Pass):
    """ir/is_test_pass.cc: flip is_test on inference-sensitive ops."""

    name = "is_test_pass"

    _OPS = ("dropout", "batch_norm", "lrn", "layer_norm")

    def apply_impl(self, program):
        for blk in program.blocks:
            for op in blk.ops:
                if op.type in self._OPS:
                    op.attrs["is_test"] = True
        return program


@register_pass
class FuseElewiseAddActPass(Pass):
    """ir/fuse_elewise_add_act_pass.cc: elementwise_add + activation ->
    fused_elemwise_activation. XLA fuses these anyway; the rewrite keeps
    op-count/structure parity and exercises the pattern machinery."""

    name = "fuse_elewise_add_act_pass"

    _ACTS = ("relu", "sigmoid", "tanh", "gelu")

    def apply_impl(self, program):
        blk = program.global_block()
        i = 0
        while i < len(blk.ops) - 1:
            add_op = blk.ops[i]
            act_op = blk.ops[i + 1]
            if (add_op.type == "elementwise_add" and
                    act_op.type in self._ACTS and
                    act_op.inputs.get("X", [None])[0] ==
                    add_op.outputs["Out"][0] and
                    self._single_use(blk, add_op.outputs["Out"][0])):
                fused = blk.ops[i]
                fused.type = "fused_elemwise_activation"
                # the activation's own attrs (e.g. gelu's 'approximate')
                # must survive the fusion or the fused lowering reads
                # defaults the unfused path would not have used
                for k, v in act_op.attrs.items():
                    fused.attrs.setdefault(k, v)
                fused.attrs["functor_list"] = [
                    "elementwise_add", act_op.type]
                fused.attrs["axis"] = add_op.attrs.get("axis", -1)
                fused.outputs = {"Out": list(act_op.outputs["Out"])}
                del blk.ops[i + 1]
            i += 1
        return program

    @staticmethod
    def _single_use(blk, name):
        return use_count(blk, name) == 1


@register_pass
class FCFusePass(Pass):
    """ir/fc_fuse_pass.cc: mul + elementwise_add(bias) -> fc op."""

    name = "fc_fuse_pass"

    def apply_impl(self, program):
        blk = program.global_block()
        i = 0
        while i < len(blk.ops) - 1:
            mul_op = blk.ops[i]
            add_op = blk.ops[i + 1]
            if (mul_op.type == "mul" and
                    add_op.type == "elementwise_add" and
                    add_op.inputs.get("X", [None])[0] ==
                    mul_op.outputs["Out"][0] and
                    FuseElewiseAddActPass._single_use(
                        blk, mul_op.outputs["Out"][0])):
                fused = blk.ops[i]
                fused.type = "fc"
                fused.inputs = {"Input": list(mul_op.inputs["X"]),
                                "W": list(mul_op.inputs["Y"]),
                                "Bias": list(add_op.inputs["Y"])}
                fused.attrs = {"in_num_col_dims":
                               mul_op.attrs.get("x_num_col_dims", 1)}
                fused.outputs = {"Out": list(add_op.outputs["Out"])}
                del blk.ops[i + 1]
            i += 1
        return program


@register_pass
class MultiBatchMergePass(Pass):
    """ir/multi_batch_merge_pass.cc (+ test_dist_mnist_batch_merge):
    gradient accumulation — run N micro-batches, apply ONE optimizer
    update from the averaged accumulated gradient.

    The reference rewrote the SSA graph to repeat the fwd/bwd subgraph N
    times per iteration; the TPU-idiomatic encoding keeps one jitted step
    and gates the optimizer ops instead (ops/optimizer_ops._merge_gated):
    this pass creates a persistable accumulation buffer per gradient,
    wires it into each optimizer op, and annotates `merge_n` so the gated
    lowering accumulates on micro-steps and applies+resets every Nth
    step. LR-decay counter increments are gated to count applied updates.

    Usage: get_pass("multi_batch_merge_pass", n=4).apply(main_program)
    """

    name = "multi_batch_merge_pass"

    def apply_impl(self, program):
        from ..ops.optimizer_ops import MERGEABLE_OPT_OPS
        from .layers.learning_rate_scheduler import LR_COUNTER_NAME
        n = int(self.get("n", 1))
        if n <= 1:
            return program
        blk = program.global_block()
        # adam/adamax advance their beta-pow accumulators with separate
        # in-place `scale` ops (optimizer.py _finish_update, mirroring the
        # reference) — those must gate with the optimizer update
        pow_names = set()
        for op in blk.ops:
            if op.type in MERGEABLE_OPT_OPS:
                for slot in ("Beta1Pow", "Beta2Pow"):
                    for nm in op.inputs.get(slot, []):
                        if nm:
                            pow_names.add(nm)
        for op in blk.ops:
            if op.type in MERGEABLE_OPT_OPS:
                gname = op.inputs.get("Grad", [None])[0]
                if not gname:
                    continue
                gvar = blk._find_var_recursive(gname)
                acc_name = gname + "@MERGE_ACC"
                if blk._find_var_recursive(acc_name) is None:
                    blk.create_var(
                        name=acc_name,
                        dtype=gvar.dtype if gvar is not None else "float32",
                        shape=gvar.shape if gvar is not None else None,
                        persistable=True, stop_gradient=True)
                op.inputs["GradAcc"] = [acc_name]
                op.outputs["GradAccOut"] = [acc_name]
                op.attrs["merge_n"] = n
            elif op.type == "increment":
                xn = op.inputs.get("X", [None])[0]
                if xn == LR_COUNTER_NAME:
                    op.attrs["merge_n"] = n
            elif op.type == "scale":
                xn = op.inputs.get("X", [None])[0]
                on = op.outputs.get("Out", [None])[0]
                if xn and xn == on and xn in pow_names:
                    op.attrs["merge_n"] = n
        return program


# ---------------------------------------------------------------------------
# GraphPatternDetector (reference ir/graph_pattern_detector.h: PDPattern of
# PDNodes + subgraph matcher that fusion passes build on). Program-level
# equivalent: declarative op-chain patterns where dataflow is expressed by
# shared symbols bound to concrete variable names during matching.
# ---------------------------------------------------------------------------

class GraphPatternDetector:
    """Declarative subgraph patterns over a Block.

    Usage:
        d = GraphPatternDetector()
        d.add_op("mul", types=["mul"], outputs={"Out": "mm"})
        d.add_op("add", types=["elementwise_add"], inputs={"X": "mm"},
                 single_use={"mm"})
        for m in d.detect(block):   # m: name -> Operator
            ...rewrite...

    Symbols (like "mm") bind to concrete var names; a symbol appearing in
    one node's outputs and another's inputs is a dataflow edge. `single_use`
    marks symbols that must have exactly one consumer in the block (the
    reference's intermediate-node constraint, so fusion never drops a value
    some other op still reads).
    """

    def __init__(self):
        self._nodes = []   # (name, types, in_links, out_links, single_use)

    def add_op(self, name, types, inputs=None, outputs=None,
               single_use=()):
        self._nodes.append((name, tuple(types), dict(inputs or {}),
                            dict(outputs or {}), frozenset(single_use)))
        return self

    @staticmethod
    def _uses(block, var_name):
        return use_count(block, var_name)

    def detect(self, block):
        """Yield non-overlapping matches as {node_name: Operator}."""
        matches = []
        used_ops = set()

        def bind(node_idx, binding, chosen, anchor=None):
            if node_idx == len(self._nodes):
                matches.append(dict(chosen))
                used_ops.update(id(op) for op in chosen.values())
                return True
            name, types, ins, outs, single = self._nodes[node_idx]
            for op in ([anchor] if anchor is not None else block.ops):
                if op.type not in types or id(op) in used_ops or \
                        any(op is c for c in chosen.values()):
                    continue
                b2 = dict(binding)
                ok = True
                for slot, sym in ins.items():
                    actual = op.inputs.get(slot, [None])[0]
                    if actual is None or \
                            (sym in b2 and b2[sym] != actual):
                        ok = False
                        break
                    b2[sym] = actual
                if not ok:
                    continue
                for slot, sym in outs.items():
                    actual = op.outputs.get(slot, [None])[0]
                    if actual is None or \
                            (sym in b2 and b2[sym] != actual):
                        ok = False
                        break
                    b2[sym] = actual
                if not ok:
                    continue
                if any(self._uses(block, b2[s]) != 1 for s in single
                       if s in b2):
                    continue
                chosen[name] = op
                if bind(node_idx + 1, b2, chosen):
                    return True
                del chosen[name]
            return False

        # greedily find all non-overlapping matches: each op is tried as
        # the first pattern node's anchor exactly once (no full-search
        # restart per accepted match)
        for op in list(block.ops):
            if id(op) not in used_ops:
                bind(0, {}, {}, anchor=op)
        return matches


@register_pass
class FCLstmFusePass(Pass):
    """ir/fc_lstm_fuse_pass.cc: fc (projection to 4H gates) feeding an
    lstm collapses into one fusion_lstm op (the reference's CPU-fused
    kernel; here the rewrite keeps op-structure parity and drops an IR
    level — XLA fuses either form). Built on GraphPatternDetector."""

    name = "fc_lstm_fuse_pass"

    def _rewrite(self, blk, lstm_op, x, wx, bias_x, dead_ops, xx_name):
        inputs = {"X": [x], "WeightX": [wx],
                  "WeightH": list(lstm_op.inputs["Weight"]),
                  "Bias": list(lstm_op.inputs["Bias"])}
        if bias_x:
            inputs["BiasX"] = [bias_x]
        for h0slot in ("H0", "C0"):
            if lstm_op.inputs.get(h0slot):
                inputs[h0slot] = list(lstm_op.inputs[h0slot])
        lstm_op.type = "fusion_lstm"
        lstm_op.inputs = inputs
        lstm_op.outputs = {"Hidden": list(lstm_op.outputs["Hidden"]),
                           "Cell": list(lstm_op.outputs["Cell"]),
                           "XX": [xx_name]}
        for op in dead_ops:
            blk.ops.remove(op)

    @staticmethod
    def _is_bias_var(blk, name):
        """The folded add's Y must be a real fc bias — a vector of 4H
        gate values (reference fc_lstm_fuse matches the fc pattern's bias
        node, never a residual add)."""
        v = blk._find_var_recursive(name)
        if v is None or v.shape is None:
            return False
        dims = [d for d in v.shape if d not in (1,)]
        return len(dims) <= 1

    def apply_impl(self, program):
        blk = program.global_block()
        # the fc projection appears as an `fc` op, or un-fused as
        # mul(+elementwise_add) — match all three shapes (the reference's
        # pattern is built over the fc-fuse result)
        d = GraphPatternDetector()
        d.add_op("mul", types=["mul"], outputs={"Out": "mm"})
        d.add_op("add", types=["elementwise_add"], inputs={"X": "mm"},
                 outputs={"Out": "proj"}, single_use={"mm"})
        d.add_op("lstm", types=["lstm"], inputs={"Input": "proj"},
                 single_use={"proj"})
        for m in d.detect(blk):
            bias_name = m["add"].inputs["Y"][0]
            if not self._is_bias_var(blk, bias_name):
                continue        # residual add, not an fc bias — skip
            self._rewrite(blk, m["lstm"], m["mul"].inputs["X"][0],
                          m["mul"].inputs["Y"][0],
                          bias_name,
                          [m["mul"], m["add"]],
                          m["add"].outputs["Out"][0])
        d = GraphPatternDetector()
        d.add_op("fc", types=["fc"], outputs={"Out": "proj"})
        d.add_op("lstm", types=["lstm"], inputs={"Input": "proj"},
                 single_use={"proj"})
        for m in d.detect(blk):
            fc_op = m["fc"]
            self._rewrite(blk, m["lstm"], fc_op.inputs["Input"][0],
                          fc_op.inputs["W"][0],
                          fc_op.inputs.get("Bias", [None])[0],
                          [fc_op], fc_op.outputs["Out"][0])
        d = GraphPatternDetector()
        d.add_op("mul", types=["mul"], outputs={"Out": "proj"})
        d.add_op("lstm", types=["lstm"], inputs={"Input": "proj"},
                 single_use={"proj"})
        for m in d.detect(blk):
            mul_op = m["mul"]
            self._rewrite(blk, m["lstm"], mul_op.inputs["X"][0],
                          mul_op.inputs["Y"][0], None,
                          [mul_op], mul_op.outputs["Out"][0])
        return program


@register_pass
class FuseBottleneckPass(Pass):
    """Collapse a BN-folded ResNet bottleneck (conv1x1+bias+relu ->
    conv3x3+bias+relu -> conv1x1+bias -> add(shortcut) -> relu, NHWC) into
    one `fused_bottleneck` op backed by the VMEM-resident Pallas kernel
    (ops/pallas_kernels.py).

    Reference analogue: the conv+bn+act fusion family
    (paddle/fluid/framework/ir/conv_bn_fuse_pass.cc, conv_elementwise_add_
    act_fuse_pass.cc) — the reference fuses per-conv epilogues; on TPU the
    win is fusing ACROSS the block so intermediate activations never leave
    VMEM (ROOFLINE.md "cross-layer fused conv pipelines"). Runs after
    InferenceTranspiler's BN fold, which produces exactly this op chain.
    NHWC only: the kernel keeps channels in the lane dimension; NCHW
    programs are left to XLA untouched.
    """

    name = "fuse_bottleneck_pass"

    @staticmethod
    def _norm2(v, default):
        if v is None:
            return (default, default)
        if isinstance(v, (list, tuple)):
            return (int(v[0]), int(v[1] if len(v) > 1 else v[0]))
        return (int(v), int(v))

    def _conv_geom(self, blk, op, ksize, stride=None, padding=0):
        """conv2d op is a plain kxk NHWC conv with the given geometry."""
        if op.attrs.get("data_format", "NCHW") != "NHWC":
            return None
        if op.inputs.get("Bias"):
            # the fused kernel has no slot for an inline conv bias (the
            # B0/B1/B2 inputs come from the BN-fold elementwise_adds);
            # rewriting would silently drop it and change numerics
            return None
        if int(op.attrs.get("groups", 1) or 1) != 1:
            return None
        if self._norm2(op.attrs.get("dilations"), 1) != (1, 1):
            return None
        if self._norm2(op.attrs.get("paddings"), 0) != (padding, padding):
            return None
        st = self._norm2(op.attrs.get("strides"), 1)
        if st[0] != st[1] or (stride is not None and st != (stride, stride)):
            return None
        w = blk._find_var_recursive(op.inputs["Filter"][0])
        if w is None or w.shape is None or tuple(w.shape[2:]) != (ksize,
                                                                  ksize):
            return None
        return st[0]

    @staticmethod
    def _is_channel_bias(blk, op, channels):
        """elementwise_add whose Y is a persistable per-channel vector of
        the conv's output width, broadcast over the trailing (NHWC
        channel) axis — the exact shape the BN fold emits. A vector
        riding a different axis (or length) is some other computation."""
        if op.attrs.get("axis", -1) not in (-1, 3):
            return False
        v = blk._find_var_recursive(op.inputs["Y"][0])
        if v is None or v.shape is None:
            return False
        dims = [d for d in v.shape if d != 1]
        return (len(dims) <= 1 and getattr(v, "persistable", False)
                and (not dims or dims[0] == channels))

    def _filter_shape(self, blk, op):
        w = blk._find_var_recursive(op.inputs["Filter"][0])
        return None if w is None else tuple(w.shape or ())

    def _detector(self, branch, swapped):
        d = GraphPatternDetector()
        d.add_op("conv0", types=["conv2d"], inputs={"Input": "xin"},
                 outputs={"Output": "c0"})
        d.add_op("add0", types=["elementwise_add"], inputs={"X": "c0"},
                 outputs={"Out": "a0"}, single_use={"c0"})
        d.add_op("relu0", types=["relu"], inputs={"X": "a0"},
                 outputs={"Out": "r0"}, single_use={"a0"})
        d.add_op("conv1", types=["conv2d"], inputs={"Input": "r0"},
                 outputs={"Output": "c1"}, single_use={"r0"})
        d.add_op("add1", types=["elementwise_add"], inputs={"X": "c1"},
                 outputs={"Out": "a1"}, single_use={"c1"})
        d.add_op("relu1", types=["relu"], inputs={"X": "a1"},
                 outputs={"Out": "r1"}, single_use={"a1"})
        d.add_op("conv2", types=["conv2d"], inputs={"Input": "r1"},
                 outputs={"Output": "c2"}, single_use={"r1"})
        d.add_op("add2", types=["elementwise_add"], inputs={"X": "c2"},
                 outputs={"Out": "a2"}, single_use={"c2"})
        if branch:
            d.add_op("convs", types=["conv2d"], inputs={"Input": "xin"},
                     outputs={"Output": "cs"})
            d.add_op("adds", types=["elementwise_add"], inputs={"X": "cs"},
                     outputs={"Out": "short"}, single_use={"cs"})
            res_in = {"X": "short", "Y": "a2"} if not swapped else \
                     {"X": "a2", "Y": "short"}
            single = {"a2", "short"}
        else:
            res_in = {"X": "xin", "Y": "a2"} if not swapped else \
                     {"X": "a2", "Y": "xin"}
            single = {"a2"}
        d.add_op("add_res", types=["elementwise_add"], inputs=res_in,
                 outputs={"Out": "res"}, single_use=single)
        d.add_op("relu_f", types=["relu"], inputs={"X": "res"},
                 outputs={"Out": "out"}, single_use={"res"})
        return d

    def _try_rewrite(self, blk, m, branch):
        s = self._conv_geom(blk, m["conv1"], 3, padding=1)
        if s is None or s not in (1, 2):
            return False
        if self._conv_geom(blk, m["conv0"], 1, stride=1) is None:
            return False
        if self._conv_geom(blk, m["conv2"], 1, stride=1) is None:
            return False
        if branch and self._conv_geom(blk, m["convs"], 1, stride=s) is None:
            return False
        # the kernel needs a consistent OIHW filter chain with a SQUARE
        # 3x3 (C->F->F->C4): a width-changing middle conv is a valid
        # graph but not this kernel's shape — leave it to XLA
        f0 = self._filter_shape(blk, m["conv0"])   # [F, C, 1, 1]
        f1 = self._filter_shape(blk, m["conv1"])   # [F, F, 3, 3]
        f2 = self._filter_shape(blk, m["conv2"])   # [C4, F, 1, 1]
        if not (f0 and f1 and f2):
            return False
        F, C = f0[0], f0[1]
        if f1[:2] != (F, F) or f2[1] != F:
            return False
        # measured-geometry gate: the Pallas kernel wins only for
        # narrow bottlenecks (round-5 chip sweep, ROOFLINE.md;
        # tune_bottleneck: F=64 +12% vs XLA, F=128 parity-plus,
        # F=256/512 LOSE). Fusing the losing geometries made the whole
        # inference graph slower, so wide blocks stay with XLA.
        from paddle_tpu.flags import FLAGS
        if F > FLAGS.fuse_bottleneck_max_width:
            return False
        C4 = f2[0]
        if branch:
            fs = self._filter_shape(blk, m["convs"])
            if not fs or fs[:2] != (C4, C):
                return False
        elif C != C4 or s != 1:
            return False
        widths = {"add0": F, "add1": F, "add2": C4, "adds": C4}
        for a in ("add0", "add1", "add2") + (("adds",) if branch else ()):
            if not self._is_channel_bias(blk, m[a], widths[a]):
                return False
        inputs = {"X": list(m["conv0"].inputs["Input"]),
                  "W0": list(m["conv0"].inputs["Filter"]),
                  "B0": list(m["add0"].inputs["Y"]),
                  "W1": list(m["conv1"].inputs["Filter"]),
                  "B1": list(m["add1"].inputs["Y"]),
                  "W2": list(m["conv2"].inputs["Filter"]),
                  "B2": list(m["add2"].inputs["Y"])}
        if branch:
            inputs["Ws"] = list(m["convs"].inputs["Filter"])
            inputs["Bs"] = list(m["adds"].inputs["Y"])
        from .framework import Operator
        fused = Operator(blk, "fused_bottleneck", inputs=inputs,
                         outputs={"Out": list(m["relu_f"].outputs["Out"])},
                         attrs={"stride": s, "data_format": "NHWC"})
        first = min(blk.ops.index(op) for op in m.values())
        for op in m.values():
            blk.ops.remove(op)
        blk.ops.insert(first, fused)
        return True

    def apply_impl(self, program):
        blk = program.global_block()
        n = 0
        # projection-shortcut blocks first (their identity-pattern prefix
        # would otherwise shadow), then identity; both add orderings
        for branch in (True, False):
            for swapped in (False, True):
                for m in self._detector(branch, swapped).detect(blk):
                    n += self._try_rewrite(blk, m, branch)
        if n:
            program._fused_bottlenecks = n
        return program
