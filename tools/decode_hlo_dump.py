"""What a PR that must not move the decode cells holds itself to: the texts of
the decode phases of ONE tree, to be compared with another tree's by `cmp`.

    python tools/decode_hlo_dump.py <tree> <out dir>      # once a tree
    diff -rq <out parent> <out change>

For a tiny artifact of each of the six served stacks (GPT-2-, OLMoE-, LFM2-,
openPangu-, Falcon-H1- and K-EXAONE-shaped: the tests' own, so every kind of
slot state occurs; fp32 caches and, where the stack takes one, int8) it writes
the jaxpr, the StableHLO and the CPU's optimized HLO of `step` (the window),
`step_logits` and `prefill`; and, compiled for a DESCRIBED v5e with the Mosaic
kernels forced (no chip: the `on-chip-measurement` guide, section 2), the TPU's
optimized HLO of `step` and `prefill` at the six decode configurations'
published widths and slot counts, two layers deep - the Mosaic kernel's
payload is in that text.  Beside each phase's texts goes its compile-cache
fingerprint (`GenerativePredictor._fingerprint`, as JSON): two trees whose
fingerprints are equal find each other's stored executables.  Source
locations are dropped (they name the tree), those inside a Mosaic payload too:
the payload is written as its MLIR text.
"""
import base64
import json
import os
import re
import sys
import tempfile

import ml_dtypes

import jax  # noqa: E402
import numpy as np  # noqa: E402

OLMOE = dict(norm="rmsnorm", norm_eps=1e-5, position="rope",
             rope_theta=10000.0, qk_norm=True, ffn="moe_swiglu")
CELLS = {"gpt2_small": (dict(vocab_size=50257, d_model=768, n_heads=12,
                             n_layers=2, max_seq_len=1024, eos_id=0), 32),
         "olmoe_1b_7b": (dict(vocab_size=50304, d_model=2048, n_heads=16,
                              n_layers=2, max_seq_len=4096, eos_id=0,
                              n_experts=64, experts_per_token=8,
                              expert_width=1024, **OLMOE), 8)}


def cell_of(root, config, layer_types):
    """(meta, slots) of `benchmark/configs/<config>.json` at its published
    widths and slot count, cut to the two layers `layer_types` (one dense
    FFN, one of the stack's own), so that every kind of slot state the
    configuration holds occurs."""
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    meta = dict(cfg["model"], n_layers=2, layer_types=layer_types)
    return meta, cfg["deployment"]["decode_slots"]


def cells(root):
    """{configuration: (meta, slots)}: the six decode configurations the
    described-v5e texts are written for."""
    return dict(
        CELLS,
        lfm2_24b_a2b=cell_of(root, "lfm2_24b_a2b", ["conv", "attention"]),
        openpangu_ultra_moe_718b=cell_of(root, "openpangu_ultra_moe_718b",
                                         ["mla", "mla"]),
        falcon_h1_34b=cell_of(root, "falcon_h1_34b",
                              ["attention+ssm"] * 2),
        k_exaone_236b_a23b=cell_of(root, "k_exaone_236b_a23b",
                                   ["window_attention", "attention"]))


def mosaic_text(match):
    """A Mosaic kernel's payload (MLIR bytecode, base64) as its text
    without source locations."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    raw = base64.b64decode(match.group(1))
    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    try:
        with ctx:
            text = ir.Module.parse(raw).operation.get_asm(
                enable_debug_info=False)
    except Exception:       # already text (an optimized module's copy)
        text = re.sub(r"(?m)^#loc.*\n| ?loc\((?:[^()]|\([^()]*\))*\)", "",
                      raw.decode("utf-8", "replace"))
    return "MOSAIC<<%s>>" % text


def stripped(text):
    """`text` without what names the tree: the instructions' metadata, the
    tables of file and function names and the stack frames at the end of an
    optimized module, the locations inside a Mosaic payload."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames"
                  r"|\d+ [\"{].*)\n", "", text)
    return re.sub(r'\\?["2]{1,2}body\\?["2]{1,2}: ?\\?["2]{1,2}'
                  r'([A-Za-z0-9+/=]+)\\?["2]{1,2}', mosaic_text, text)


def phases(pred, n_slots, bucket):
    """{phase: (math over (state, *args), the arguments' specs)}; every
    phase behind ONE signature, so that a module's parameters are named
    alike whatever a tree calls them."""
    def flat(fn):
        return lambda state, *args: fn(state, *args)
    return {ph: (flat(fn), specs) for ph, (fn, specs) in _phases(
        pred, n_slots, bucket).items()}


def _phases(pred, n_slots, bucket):
    return {"step": (pred._step_math(), pred._step_specs(n_slots)),
            "step_logits": (pred._step_logits, pred._table_specs(n_slots)),
            "prefill": (pred._prefill_math,
                        (jax.ShapeDtypeStruct((1, bucket), np.int32),
                         jax.ShapeDtypeStruct((), np.int32)))}


def fingerprint(dec, pred, ph, n_slots, bucket, specs):
    """The phase's compile-cache fingerprint, under the key its `*_fn`
    resolves it by, as JSON."""
    key = {"step": ("step", n_slots, int(dec.STEP_WINDOW)),
           "step_logits": ("step_logits", n_slots) + (
               ("picks",) if pred._step_picks else ()),
           "prefill": ("prefill", bucket)}[ph]
    return json.dumps(pred._fingerprint(key, specs), sort_keys=True,
                      indent=1, default=str)


def described_v5e():
    """The first device of a described v5e 2x2 (no chip)."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


def v5e_phases(dec, pk, device, name, meta, slots, only=None):
    """{phase: (fingerprint, StableHLO, the TPU's optimized HLO)} of `step`
    and `prefill` (or the phases `only` names) of a weightless predictor of
    `meta` at `slots` slots, compiled for the described `device` with the
    Mosaic kernels forced; the texts as `stripped` leaves them."""
    on = jax.sharding.SingleDeviceSharding(device)
    pred = object.__new__(dec.GenerativePredictor)
    pred.meta, pred._block_meta = meta, dec.block_of(meta)
    pred._kv_dtype, pred._tp_size, pred._device = "float32", 0, device
    pred._kv_scales = None
    state = {n: jax.ShapeDtypeStruct(s, np.float32, sharding=on)
             for n, s in dec.decode_state_shapes(meta).items()}
    if pred._block_meta["weight_dtype"] == "bfloat16":
        state = {n: jax.ShapeDtypeStruct(
            s.shape, ml_dtypes.bfloat16 if dec._bf16_at_rest(n, s)
            else s.dtype, sharding=on) for n, s in state.items()}
    # what `_fingerprint` reads of an opened artifact
    pred._model_fp, pred._state_host = "described:" + name, state
    texts = {}
    for ph, (fn, specs) in phases(pred, slots, 128).items():
        if ph == "step_logits" or (only and ph not in only):
            continue
        fp = fingerprint(dec, pred, ph, slots, 128, specs)
        specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on)
                 for s in specs]
        donate = tuple(range(1, 1 + pred._n_tables)) if ph == "step" else ()
        with pk.mosaic_lowering():
            low = jax.jit(fn, donate_argnums=donate,
                          compiler_options=dec._TPU_PHASE_OPTIONS).lower(
                              state, *specs)
            texts[ph] = (fp, stripped(low.as_text()),
                         stripped(low.compile().as_text()))
    return texts


def main(root, out):
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.makedirs(out, exist_ok=True)
    from paddle_tpu.inference import decode as dec
    from paddle_tpu.ops import pallas_kernels as pk
    assert dec.__file__.startswith(root), dec.__file__
    jax.config.update("jax_enable_compilation_cache", False)

    def write(tag, text):
        with open(os.path.join(out, tag), "w") as f:
            f.write(text)

    # the tests' tiny stacks (`tests/test_decode_sliding.py` extends
    # `tests/test_decode_ssm.py`'s), the tree's own copy of them
    from tests import test_decode_sliding as tiny
    stacks = {name: (block, tiny.OLD_TINY)
              for name, block in tiny.OLD_STACKS.items()}
    stacks["kexaone"] = (tiny.WINDOW_BLOCK, tiny.TINY)
    for name, (block, size) in sorted(stacks.items()):
        d = tempfile.mkdtemp()
        dec.build_tiny_decode_model(d, block=block, **size)
        # an int8 cache is the all-attention multi-head stacks'
        for kv in ("float32", "int8") if name in ("gpt2", "olmoe") else (
                "float32",):
            pred = dec.load_decode_predictor(d, kv_cache_dtype=kv)
            state = {n: jax.ShapeDtypeStruct(np.shape(v),
                                             np.asarray(v).dtype)
                     for n, v in pred._state_host.items()}
            for ph, (fn, specs) in phases(pred, 4, 16).items():
                tag = "%s_%s_%s" % (name, kv, ph)
                write(tag + ".fingerprint",
                      fingerprint(dec, pred, ph, 4, 16, specs))
                low = jax.jit(fn).lower(state, *specs)
                write(tag + ".stablehlo", stripped(low.as_text()))
                write(tag + ".hlo", stripped(low.compile().as_text()))
                write(tag + ".jaxpr", stripped(str(
                    jax.make_jaxpr(fn)(state, *specs))))

    # the cells' widths on a described v5e, Mosaic kernels in the text
    device = described_v5e()
    for name, (meta, slots) in cells(root).items():
        for ph, (fp, stablehlo, hlo) in v5e_phases(
                dec, pk, device, name, meta, slots).items():
            write("v5e_%s_%s.fingerprint" % (name, ph), fp)
            write("v5e_%s_%s.stablehlo" % (name, ph), stablehlo)
            write("v5e_%s_%s.hlo" % (name, ph), hlo)
    print("dumped", len(os.listdir(out)))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
