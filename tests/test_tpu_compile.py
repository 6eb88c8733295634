"""The main path's kernels, compiled by the TPU's own compiler without a chip.

Interpret mode cannot see what Mosaic refuses: a block below the (8, 128)
tile floor, a reshape of a packed tile with no layout, a VMEM overrun.  The
TPU compiler is installed wherever jax[tpu] is and compiles for a chip that
is DESCRIBED, not attached (on-chip-measurement guide §2.3), so every shape
below is lowered with `interpret=False` and compiled for one v5e — about a
second each, no chip time.  PR 21 added this file after `decode_attention`
turned out never to have compiled for a TPU at any shape.

A compile that passes is not a chip run: results and times come from
`chip_smoke.py`.
"""

import os

import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on the first device of a described v5e 2x2."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip("cannot describe a v5e topology: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to jax's persistent cache
    but cannot be read back without the chip — the next run would warn and
    compile again — so the cache is off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compile_for_chip(fn, one_chip, *specs):
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=one_chip)
            for shape, dt in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the executable"
    return text


# the serve phase of chip_smoke.py (FLAGS.serving_decode_slots slots of a
# GPT-2-small cache) and a wider, longer table
DECODE_GEOMETRIES = {"n8_s1024_h12_d64": (8, 1024, 12, 64),
                     "n32_s2048_h16_d128": (32, 2048, 16, 128),
                     # the olmoe_1b_7b configuration's slot table
                     "n8_s4096_h16_d128": (8, 4096, 16, 128)}
DECODE_DTYPES = {"fp32": ("float32", "float32"),
                 "bf16": ("bfloat16", "bfloat16"),
                 "int8kv": ("float32", "int8")}


def decode_specs(N, S, H, D, q_dt, kv_dt, h_scales=None):
    # the caches as a slot table holds them: a position one flat row
    specs = [((N, H, D), q_dt), ((N, S, H * D), kv_dt),
             ((N, S, H * D), kv_dt), ((N,), "int32")]
    if kv_dt == "int8":
        specs.append(((2, h_scales or H), "float32"))
    return specs


@pytest.mark.parametrize("dtypes", sorted(DECODE_DTYPES))
@pytest.mark.parametrize("geometry", sorted(DECODE_GEOMETRIES))
def test_decode_attention_compiles(one_chip, geometry, dtypes):
    q_dt, kv_dt = DECODE_DTYPES[dtypes]

    def fn(q, k, v, lengths, *scales):
        return pk.decode_attention(q, k, v, lengths, interpret=False,
                                   kv_scales=scales[0] if scales else None)

    compile_for_chip(fn, one_chip, *decode_specs(
        *DECODE_GEOMETRIES[geometry], q_dt, kv_dt))


# the decode cells' slot tables as the step holds them (a position one flat
# row of its K/V heads' values, `slot_state.KINDS`): (N, S, query
# heads, K/V heads, D), the table's last axis K/V heads x D
CELL_TABLES = {"gpt2_small": (32, 1024, 12, 12, 64),
               "olmoe_1b_7b": (8, 4096, 16, 16, 128),
               "lfm2_24b_a2b": (32, 4096, 32, 8, 64)}


@pytest.mark.parametrize("cell,kv_dt", [
    (c, dt) for c in sorted(CELL_TABLES) for dt in ("float32", "int8")
    if not (c == "lfm2_24b_a2b" and dt == "int8")])   # no int8 GQA cache
def test_bounded_decode_attention_compiles_at_the_cells_tables(
        one_chip, cell, kv_dt):
    """The kernel whose K/V stream stops at a slot's length (`lengths`
    and each slot's last live block are scalar-prefetch operands, the
    second read by the index maps) over a layer of
    each cell's STACKED table, as `_attend_table` calls it: Mosaic takes
    it, and it is ONE custom call a layer (the benchmark's attention
    readers count every Mosaic call of the step as attention)."""
    N, S, H, Hc, D = CELL_TABLES[cell]

    def fn(q, k, v, lengths, *scales):
        return pk.decode_attention(q, k, v, lengths, interpret=False,
                                   kv_scales=scales[0] if scales else None,
                                   layer=1)

    specs = [((N, H, D), "float32"), ((2, N, S, Hc * D), kv_dt),
             ((2, N, S, Hc * D), kv_dt), ((N,), "int32")]
    if kv_dt == "int8":
        specs.append(((2, H), "float32"))
    text = compile_for_chip(fn, one_chip, *specs)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # no copy of a table's layer is made for the call, and the table goes
    # to it in the layout the device gives it (row-major: nothing pinned,
    # nothing padded)
    assert "[%d,%d,%d]" % (N, S, Hc * D) not in text
    assert re.search(r"\[2,%d,%d,%d\]\{3,2,1,0" % (N, S, Hc * D), text)


@pytest.mark.parametrize("kv_dt", ["float32", "int8"])
def test_decode_attention_head_slice_compiles(one_chip, kv_dt):
    """One member's head block of a 4-way tensor-parallel split of the
    12-head table: Hl = 3 (its 192 lanes of the 768-lane row), scales
    sliced from the full [2, 12] table."""
    N, S, H, D, Hl = 8, 1024, 12, 64, 3

    def fn(q, k, v, lengths, *scales):
        return pk.decode_attention_head_slice(
            q, k, v, lengths, head_offset=Hl, n_local_heads=Hl,
            interpret=False, kv_scales=scales[0] if scales else None)

    compile_for_chip(fn, one_chip, *decode_specs(
        N, S, Hl, D, "float32", kv_dt, h_scales=H))


@pytest.mark.parametrize("shape", [(2, 4096, 16, 128), (8, 512, 8, 64)],
                         ids=["b2_s4096_h16_d128", "b8_s512_h8_d64"])
def test_flash_attention_fwd_bwd_compiles(one_chip, shape):
    def loss(q, k, v):
        return jnp.sum(pk.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32))

    text = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                            *[(shape, "bfloat16")] * 3)
    assert text.count("tpu_custom_call") >= 3     # fwd, dq, dkv


@pytest.mark.parametrize("m,k,n,dt", [(256, 2048, 8192, "bfloat16"),
                                      (32, 2048, 2048, "float32")])
def test_dequant_matmul_compiles(one_chip, m, k, n, dt):
    compile_for_chip(
        lambda x, w, s: pk.dequant_matmul(x, w, s, interpret=False),
        one_chip, ((m, k), dt), ((k, n), "int8"), ((n,), "float32"))


def test_routed_ffn_compiles_to_grouped_matmul_kernels(one_chip):
    """`decode.moe_ffn` at OLMoE's published widths, a decode step's and a
    prefill's token counts: the three expert matmuls are XLA's
    grouped-matmul kernels (Mosaic custom calls named `ragged-dot-*`, which
    visit only the groups that hold rows), not a dense all-experts
    expansion, and the program holds no second copy of the 1.6 GB of expert
    weights (a scan over experts had XLA hoist a bf16 copy of all of them
    out of the loop)."""
    from paddle_tpu.inference.decode import moe_ffn
    D, E, F, K = 2048, 64, 1024, 8
    for tokens in (8, 1024):
        args = [jax.ShapeDtypeStruct(shape, np.float32, sharding=one_chip)
                for shape in ((tokens, D), (D, E), (E, D, F), (E, D, F),
                              (E, F, D))]
        compiled = jax.jit(lambda h, r, g, u, d: moe_ffn(
            h, r, g, u, d, K)).lower(*args).compile()
        text = compiled.as_text()
        grouped = [ln for ln in text.splitlines()
                   if "tpu_custom_call" in ln
                   and ln.strip().startswith("%ragged-dot-none")]
        assert len(grouped) == 3, len(grouped)
        flops = compiled.cost_analysis()["flops"]
        assert flops < 1.2 * tokens * K * 3 * 2 * D * F + 1e9, flops
        assert compiled.memory_analysis().temp_size_in_bytes < 4e8


# ---------------------------------------------------------------------------
# the decode step as a whole: the slot table is updated in place (PR 27).
# Every argument is left in the device's OWN layout here, as at run time:
# jax 0.9 loses a pinned output layout on a persistent-cache hit, so the
# table must be row-major by the device's choice (`slot_state.py`), not
# by a pin
# ---------------------------------------------------------------------------

STEP_MODELS = {
    # benchmark/configs/gpt2_small.json at its 32 slots and full depth: the
    # depth matters, XLA's rematerialisation pass misjudges the step only
    # once the tables pass ~5.6 GB (`slot_state._TPU_PHASE_OPTIONS`)
    "gpt2_small": (dict(vocab_size=50257, d_model=768, n_heads=12,
                        n_layers=12, max_seq_len=1024, eos_id=0), 32),
    # benchmark/configs/olmoe_1b_7b.json at its 8 slots, depth cut to 2
    "olmoe_1b_7b": (dict(vocab_size=50304, d_model=2048, n_heads=16,
                         n_layers=2, max_seq_len=4096, eos_id=0,
                         norm="rmsnorm", norm_eps=1e-5, position="rope",
                         rope_theta=10000.0, qk_norm=True,
                         ffn="moe_swiglu", n_experts=64,
                         experts_per_token=8, expert_width=1024), 8),
}


def described_predictor(meta, device, kv="float32"):
    """A GenerativePredictor with no weights, placed on a DESCRIBED chip:
    enough of it to trace a phase and build the lane's jitted call."""
    from paddle_tpu.inference import decode as dec
    pred = object.__new__(dec.GenerativePredictor)
    pred.meta, pred._block_meta = meta, dec.block_of(meta)
    pred._kv_dtype, pred._tp_size, pred._device = kv, 0, device
    pred._kv_scales = None if kv == "float32" else np.full(
        (2, meta["n_layers"], meta["n_heads"], 1), 0.01, np.float32)
    on = jax.sharding.SingleDeviceSharding(device)
    state = {n: jax.ShapeDtypeStruct(s, np.float32, sharding=on)
             for n, s in dec.decode_state_shapes(meta).items()}
    return pred, state


def compile_phase(pred, state, math_fn, specs, tables=(0, 1)):
    """The phase as `_resolve` builds it (`_phase_jit`: the slot state at
    `tables` among the arguments donated, the TPU's options), compiled for
    the described chip with every argument in the device's own layout."""
    on = jax.sharding.SingleDeviceSharding(pred._device)
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on)
             for s in specs]
    with pk.mosaic_lowering():
        return pred._phase_jit(math_fn, tables).lower(
            state, *specs).compile()


def assert_table_updated_in_place(compiled, table_shape, n_kernels,
                                  temporaries=None):
    """What PERF.md (PR 27) predicts of the module: both tables aliased to
    outputs, temporaries under a tenth of one table (or `temporaries`
    bytes), and no select, concatenate, copy, pad or transpose whose result
    is a whole table or a whole layer."""
    import re
    L, N, S, W = table_shape
    table = L * N * S * W * 4
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * table, \
        (ma.alias_size_in_bytes, table)
    assert ma.temp_size_in_bytes < (temporaries or table / 10), \
        ma.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n_kernels
    big = re.compile(r"\[(%d,)?%d,%d,%d\]" % (L, N, S, W))
    bad = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ([\w\-]+)\(", ln)
        if m and big.search(m.group(2)) and m.group(3) in (
                "select", "concatenate", "copy", "pad", "transpose"):
            bad.append((m.group(1), m.group(2), m.group(3)))
    assert not bad, bad[:6]
    return text


@pytest.mark.parametrize("model", sorted(STEP_MODELS))
def test_decode_step_updates_the_table_in_place(one_chip, model):
    """The lane's step executable, which is a WINDOW of up to
    `decode.STEP_WINDOW` trips (PR 30), at the geometry of each decode
    cell: `gpt2_small` at 32 slots and 12 layers, `olmoe_1b_7b` at 8 slots
    and the cell's 4 layers."""
    meta, slots = STEP_MODELS[model]
    routed = model == "olmoe_1b_7b"
    if routed:
        meta = dict(meta, n_layers=4)
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device)
    specs = pred._step_specs(slots)
    compiled = compile_phase(pred, state, pred._step_math(), specs)
    # a position is one flat row of its heads' values, 768 lanes (2048 at
    # OLMoE): whole tiles, so row-major is the device's own layout for the
    # table and the table holds the data's bytes and no other
    assert pred.table_shape(slots) == (
        meta["n_layers"], slots, meta["max_seq_len"], meta["d_model"])
    ma = compiled.memory_analysis()
    if not routed:
        # K and V of 32 slots are 2 x 1.21 GB where padded rows held 2 x
        # 3.22: the step's arguments (tables and 0.65 GB of weights) were
        # 7.09 GB
        assert pred.kv_cache_bytes(slots) == 2 * 12 * 32 * 1024 * 768 * 4
        assert ma.argument_size_in_bytes <= 3.3e9, ma.argument_size_in_bytes
    # its `while` body carries the table, and no table- or layer-sized copy
    # may sit inside the loop either.  XLA hoists the bf16 rounding of the
    # dense matmuls' weights out of the loop: the head and, a layer, the
    # four projections (and gpt2_small's MLP), two bytes a weight:
    # gpt2_small 0.25 GB, under a tenth of its table; olmoe_1b_7b at the
    # cell's depth 0.21 + 4 x 0.034 = 0.34 GB (the issue's 0.35 GB was
    # PR 29's reading at 2 layers, 0.28, with room)
    D, V, L = meta["d_model"], meta["vocab_size"], meta["n_layers"]
    hoisted = 2 * (D * V + L * (4 * D * D + (0 if routed else 8 * D * D)))
    text = assert_table_updated_in_place(
        compiled, pred.table_shape(slots), n_kernels=L,
        temporaries=hoisted + 0.02e9)
    # one Mosaic call a layer and no other (`gpt2_small.json` takes every
    # custom call of the step for the kernel)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len([ln for ln in calls if "kernel_metadata={}" in ln]) == L
    assert routed or len(calls) == L
    # ONE executable for every trip count: the trips of a dispatch are its
    # last argument, a scalar the one `while` is bounded by at run time
    assert [(s.shape, str(s.dtype)) for s in specs[-2:]] \
        == [((slots,), "int32"), ((), "int32")]
    assert text.count(" while(") == 1 and ("ragged-dot" in text) == routed
    assert re.search(r"ENTRY [^\n]*: s32\[\]\) -> ", text), \
        "max_trips (the last argument) is not a runtime parameter"
    if routed:
        # the 3.2 GB of experts go to the grouped-matmul kernels as they
        # are: nothing whose result is a whole expert tensor but a
        # parameter (or a view of one)
        E, D, F = (meta[k] for k in ("n_experts", "d_model", "expert_width"))
        whole = re.compile(r"\[%d,(%d,%d|%d,%d)\]" % (E, D, F, F, D))
        made = [m.group(0) for m in re.finditer(
            r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
            if whole.search(m.group(2)) and m.group(3) not in (
                "parameter", "bitcast", "get-tuple-element")]
        assert not made, made[:6]


def test_step_logits_updates_the_table_in_place(one_chip):
    """The one-step phase with no window around it (`step_logits_fn`, what
    a logit-level comparison against a reference reads): in place, with
    nothing hoisted, so temporaries under a tenth of a table."""
    meta, slots = STEP_MODELS["gpt2_small"]
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device)
    compiled = compile_phase(pred, state, pred._step_logits,
                             pred._table_specs(slots))
    text = assert_table_updated_in_place(compiled, pred.table_shape(slots),
                                         n_kernels=meta["n_layers"])
    assert " while(" not in text


@pytest.mark.parametrize("members", [2, 4])
def test_tensor_parallel_step_holds_a_flat_shard_a_member(members):
    """The step of a tensor-parallel mesh (`_tp_shard_map`) for `members`
    described v5e chips, GPT-2 small's heads at a vocabulary the mesh
    divides, two layers: a member's shard of the flat table is a flat table
    of its own, [L, N, S, H / m * Dh], which its one Mosaic call a layer
    takes as it is.  Two members hold 384 lanes, whole tiles: row-major by
    the device's choice, both tables aliased, NO temporaries (a table that
    kept the heads apart, [.., 6, 64], cost 0.54 GB of copies here; 0.27
    at four members: PR 41's readings of the parent).  Four members hold 3
    heads = 192 lanes, which the device lays out with S innermost: one
    copy of a shard at 256 lanes remains (0.07 GB), a quarter of the
    parent's."""
    from jax.experimental import topologies
    from paddle_tpu.inference import decode as dec
    from paddle_tpu.parallel.mesh import MeshGroup
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip("cannot describe a v5e topology: %s" % e)
    group = MeshGroup(topo.devices[:members])
    meta = dict(STEP_MODELS["gpt2_small"][0], vocab_size=50304, n_layers=2)
    pred = object.__new__(dec.GenerativePredictor)
    pred.meta, pred._block_meta = meta, dec.block_of(meta)
    pred._kv_dtype, pred._kv_scales = "float32", None
    pred._tp_size, pred._tp_prefill_seq, pred._device = members, 128, group
    slots = 32
    assert pred.table_shape(slots) == (2, slots, 1024, 768)
    state, specs = pred._mesh_specs(
        group, {n: jax.ShapeDtypeStruct(shape, np.float32) for n, shape
                in dec.decode_state_shapes(meta).items()},
        pred._step_specs(slots), jax)
    assert specs[0].sharding.shard_shape(specs[0].shape) \
        == (2, slots, 1024, 768 // members)
    fn = pred._tp_shard_map(pred._step_math(tp=pred._tp_ctx()),
                            pred._step_math(), state, specs, group, jax)
    with pk.mosaic_lowering():
        compiled = pred._phase_jit(fn, range(2)).lower(
            state, *specs).compile()
    shard = 2 * slots * 1024 * (768 // members) * 4
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * shard
    assert ma.temp_size_in_bytes < (shard / 10 if members == 2
                                    else 1.5 * shard), ma.temp_size_in_bytes
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2


def _verify_specs(pred, slots, k):
    cache, _, lengths, _, active = pred._table_specs(slots)
    return (cache, cache, lengths,
            jax.ShapeDtypeStruct((slots, k + 1), np.dtype(np.int32)), active)


def test_verify_updates_the_table_in_place(one_chip):
    """`verify_fn(32, 4)`: five rows a slot and layer land in the carried
    table by the step's scatter, five kernel calls a layer read it, the
    rejected suffix leaves by a scatter of zeros.  Before PR 28 the phase
    broadcast each layer to N x C pseudo-slots (1.34 GB a layer and
    table here) beside two stacked tables and did not fit the chip."""
    meta, slots = STEP_MODELS["gpt2_small"]
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device)
    compiled = compile_phase(pred, state, pred._verify_math,
                             _verify_specs(pred, slots, 4))
    assert_table_updated_in_place(compiled, pred.table_shape(slots),
                                  n_kernels=5 * meta["n_layers"])


def test_int8_table_step_compiles_in_place(one_chip):
    meta, slots = STEP_MODELS["gpt2_small"]
    meta = dict(meta, n_layers=2)
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device, kv="int8")
    compiled = compile_phase(pred, state, pred._step_math(),
                             pred._step_specs(slots))
    ma = compiled.memory_analysis()
    table = int(np.prod(pred.table_shape(slots)))    # int8, rows of 768
    assert ma.alias_size_in_bytes >= 2 * table
    assert ma.temp_size_in_bytes < table / 10


# benchmark/configs/lfm2_24b_a2b.json at its 32 slots: the leading dense conv
# layer, the attention layer and one routed conv layer of its five, at the
# published widths but 8 of the 64 experts (a compile's seconds, not a
# kernel's shape)
LFM2 = dict(vocab_size=65536, d_model=2048, n_heads=32, n_kv_heads=8,
            n_layers=3, max_seq_len=4096, eos_id=0, norm="rmsnorm",
            norm_eps=1e-5, position="rope", rope_theta=1e6, qk_norm="head",
            layer_types=["conv", "attention", "conv"], conv_kernel=3,
            n_dense_layers=1, dense_width=11776, ffn="moe_swiglu",
            n_experts=8, experts_per_token=4, expert_width=1536,
            norm_topk_prob=True, router="sigmoid_bias", head="tied")


def test_grouped_query_decode_attention_compiles(one_chip):
    """32 query heads over a table of 8 K/V heads of 64, flat rows of 512
    lanes, stacked, as `_attend_table` calls it for LFM2: Mosaic takes the
    block-diagonal queries of four heads a K/V head (interpret mode takes
    anything)."""
    N, S, Hq, Hkv, D = 32, 4096, 32, 8, 64
    with pk.mosaic_lowering():
        compile_for_chip(
            lambda q, k, v, n: pk.decode_attention(q, k, v, n, scale=0.125,
                                                   layer=0),
            one_chip, ((N, Hq, D), "float32"),
            ((1, N, S, Hkv * D), "float32"), ((1, N, S, Hkv * D), "float32"),
            ((N,), "int32"))


def test_hybrid_step_updates_both_kinds_of_slot_state_in_place(one_chip):
    """The step window of a stack with conv layers beside a grouped-query
    attention layer: the K/V tables (the ATTENTION layer's only) and the
    conv-state table are all three donated and aliased to outputs, one
    Mosaic call an attention layer, and the `while` carries all three."""
    slots = 32
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(LFM2, device)
    assert pred.table_shape(slots) == (1, slots, 4096, 8 * 64)
    assert pred.conv_state_shape(slots) == (2, slots, 2, 2048)
    specs = pred._step_specs(slots)
    compiled = compile_phase(pred, state, pred._step_math(), specs,
                             tables=range(3))
    D, V = LFM2["d_model"], LFM2["vocab_size"]
    hoisted = 2 * (D * V + 2 * 4 * D * D + 3 * D * LFM2["dense_width"])
    text = assert_table_updated_in_place(
        compiled, pred.table_shape(slots), n_kernels=1,
        temporaries=hoisted + 0.02e9)
    ma = compiled.memory_analysis()
    conv = 4 * int(np.prod(pred.conv_state_shape(slots)))
    table = 4 * int(np.prod(pred.table_shape(slots)))
    assert ma.alias_size_in_bytes >= 2 * table + conv
    assert text.count(" while(") == 1 and "ragged-dot" in text
    assert re.search(r"ENTRY [^\n]*: f32\[2,32,2,2048\]", text), \
        "the conv state is not an argument of its own"


# benchmark/configs/openpangu_ultra_moe_718b.json at its 64 slots: the
# leading dense layer and one routed layer of its five, at the published
# widths (a compile's seconds, not a kernel's shape), weights as the
# artifact keeps them
PANGU = dict(vocab_size=19200, d_model=7680, n_heads=128, n_layers=2,
             max_seq_len=4096, eos_id=0, norm="rmsnorm", norm_eps=1e-5,
             position="rope", rope_theta=25.6e6, layer_types=["mla", "mla"],
             q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
             qk_rope_head_dim=64, v_head_dim=128, sandwich_norm=True,
             n_dense_layers=1, dense_width=18432, ffn="moe_swiglu",
             n_experts=256, experts_per_token=8, expert_width=2048,
             norm_topk_prob=True, router="sigmoid", routed_scaling=2.5,
             n_shared_experts=1, experts_held=[96, 8],
             weight_dtype="bfloat16")


def test_latent_decode_attention_compiles(one_chip):
    """128 absorbed query heads over ONE 640-lane row a position of a
    stacked latent table, as `_mla_absorbed` calls it at the published row:
    Mosaic takes both MXU contractions, bf16 operands from a float32 table,
    and the value lanes as a slice of the staged rows."""
    N, S, H, R, V = 64, 4096, 128, 640, 512
    with pk.mosaic_lowering():
        compile_for_chip(
            lambda q, t, n: pk.latent_decode_attention(
                q, t, n, V, 192 ** -0.5, layer=1),
            one_chip, ((N, H, R), "float32"), ((2, N, S, R), "float32"),
            ((N,), "int32"))


def test_latent_step_updates_its_one_table_in_place(one_chip):
    """The step window of an MLA stack with bfloat16 weights at rest: ONE
    latent table, 640-lane rows, donated and aliased to its output, one
    Mosaic call a layer inside the one `while`; the bf16 weights go to
    their matmuls as they are (no float32 copy of the held experts, nor of
    any other weight, is made: the temporaries are under a tenth of the
    table)."""
    from paddle_tpu.inference import decode as dec
    slots = 64
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(PANGU, device)
    state = {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}
    assert pred.table_shape(slots) == (2, slots, 4096, 640)
    assert pred._n_tables == 1 and len(pred._step_specs(slots)) == 6
    compiled = compile_phase(pred, state, pred._step_math(),
                             pred._step_specs(slots), tables=range(1))
    ma = compiled.memory_analysis()
    table = 4 * int(np.prod(pred.table_shape(slots)))
    assert ma.alias_size_in_bytes >= table
    assert ma.temp_size_in_bytes < table / 10, ma.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count(" while(") == 1 and "ragged-dot" in text
    assert text.count("tpu_custom_call") >= 2
    # nothing whose result is a float32 copy of the held experts' stacks
    whole = re.compile(r"f32\[8,(7680,2048|2048,7680)\]")
    made = [m.group(0) for m in re.finditer(
        r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text) if whole.search(m.group(2))]
    assert not made, made[:6]
    big = re.compile(r"\[2,64,4096,640\]")
    bad = [m.group(0) for m in re.finditer(
        r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
        if big.search(m.group(2)) and m.group(3) in (
            "select", "concatenate", "copy", "pad", "transpose")]
    assert not bad, bad[:6]


# benchmark/configs/falcon_h1_34b.json at its 96 slots: the scanned-state
# table of four attention+ssm layers, 32 heads of [128, 256] fp32 over 2
# groups, 1.61 GB
SSM_TABLE, SSM_GROUPS = (4, 96, 32, 128, 256), 2


def _custom_calls(text):
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def test_ssm_update_compiles_in_place_at_the_cells_table(one_chip):
    """`pk.ssm_update` at layer 1 of the cell's stacked table, the table
    donated: Mosaic takes it (a float32 scalar-prefetch vector, the
    in-kernel transposes, 1 MiB blocks double-buffered in and out), the
    table is aliased to its result and nothing table- or layer-sized is
    copied, selected or sliced around the call.  Its custom call carries
    `kernel_metadata` that is NOT empty, while `decode_attention`'s still
    reads `kernel_metadata={}`: that substring is how
    `benchmark/configs/falcon_h1_34b.json` finds the attention kernel."""
    L, N, Hs, P, Ns = SSM_TABLE
    specs = [(SSM_TABLE, "float32"), ((N, Hs), "float32"),
             ((N, Hs, P), "float32"), ((N, SSM_GROUPS, Ns), "float32"),
             ((N, SSM_GROUPS, Ns), "float32"), ((N,), "bool")]
    args = [jax.ShapeDtypeStruct(s, np.dtype(dt), sharding=one_chip)
            for s, dt in specs]
    with pk.mosaic_lowering():
        compiled = jax.jit(
            lambda ss, *rest: pk.ssm_update(ss, *rest, layer=1),
            donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    calls = _custom_calls(text)
    assert len(calls) == 1 and "kernel_metadata={}" not in calls[0]
    assert "ssm_update" in calls[0]
    table = 4 * int(np.prod(SSM_TABLE))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= table, ma.alias_size_in_bytes
    assert ma.temp_size_in_bytes < table / 1000, ma.temp_size_in_bytes
    big = re.compile(r"f32\[(%d,)?%d,%d,%d,%d\]" % SSM_TABLE)
    made = [m.group(0) for m in re.finditer(
        r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
        if big.search(m.group(2)) and m.group(3) not in (
            "custom-call", "parameter", "get-tuple-element")]
    assert not made, made[:6]
    # the attention kernel's call still has no metadata of its own
    with pk.mosaic_lowering():
        attention = compile_for_chip(
            lambda q, k, v, n: pk.decode_attention(q, k, v, n, layer=0),
            one_chip, ((N, 20, 128), "float32"),
            ((1, N, 1024, 512), "float32"), ((1, N, 1024, 512), "float32"),
            ((N,), "int32"))
    assert "kernel_metadata={}" in _custom_calls(attention)[0]


def test_state_space_step_updates_its_three_kinds_of_state_in_place(
        one_chip):
    """The step window of `falcon_h1_34b` at the cell's 96 slots, bfloat16
    weights at rest: K/V rows, conv windows and the scanned state are all
    donated and aliased, a layer makes TWO Mosaic calls (the attention
    kernel, found by its empty `kernel_metadata`, and `ssm_update`, which
    carries its own), and no instruction but those calls makes a table- or
    layer-sized scanned state (XLA's form made five a layer: a reduce over
    it and four select-and-update fusions)."""
    import json
    import os
    from paddle_tpu.inference import decode as dec
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "falcon_h1_34b.json")) as f:
        cfg = json.load(f)
    meta, slots = cfg["model"], cfg["deployment"]["decode_slots"]
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device)
    state = {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}
    assert pred.ssm_state_shape(slots) == SSM_TABLE
    compiled = compile_phase(pred, state, pred._step_math(),
                             pred._step_specs(slots), tables=range(4))
    text = compiled.as_text()
    calls = _custom_calls(text)
    layers = meta["n_layers"]
    assert len([c for c in calls if "kernel_metadata={}" in c]) == layers
    named = [c for c in calls if "ssm_update" in c
             and "kernel_metadata={}" not in c]
    assert len(named) == layers and len(calls) == 2 * layers
    # ... each ONE line of the text, its name and its op_name together:
    # the benchmark's reader of the `ssm_update` scope finds the four calls
    from benchmark import moe_trace
    scoped = moe_trace.scope_instruction_names(text, "ssm_update")
    assert {c.split(" = ")[0].strip().lstrip("%") for c in named} <= scoped
    ma = compiled.memory_analysis()
    held = sum(4 * int(np.prod(s)) for s in (
        pred.table_shape(slots), pred.table_shape(slots),
        pred.conv_state_shape(slots), SSM_TABLE))
    assert ma.alias_size_in_bytes >= held, (ma.alias_size_in_bytes, held)
    big = re.compile(r"f32\[(%d,)?%d,%d,%d,%d\]" % SSM_TABLE)
    made = [(m.group(1), m.group(3)) for m in re.finditer(
        r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
        if big.search(m.group(2).split("{")[0]) and m.group(3) in (
            "select", "concatenate", "copy", "pad", "transpose", "fusion",
            "reduce", "dynamic-update-slice", "dynamic-slice")]
    assert not made, made[:8]
    assert text.count(" while(") == 1


# benchmark/configs/granite_4_0_h_small.json at its 96 slots: the
# scanned-state table of nine ssm layers, 128 heads of [64, 128] fp32 in one
# group, 3.62 GB.  A head's dt x and y are HALF a lane row: two heads a row
GRANITE_TABLE = (9, 96, 128, 64, 128)


def _mosaic_module(call):
    """A Mosaic call's payload as MLIR text, without source locations."""
    import base64
    import json
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir
    body = json.loads(re.search(r"backend_config=(.*)$", call).group(1))[
        "custom_call_config"]["body"]
    ctx = jmlir.make_ir_context()
    with ctx:
        ctx.allow_unregistered_dialects = True
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)


@pytest.mark.parametrize("table,groups,block", [
    (GRANITE_TABLE, 1, 32), (SSM_TABLE, SSM_GROUPS, 8)],
    ids=["heads_of_64", "heads_of_128"])
def test_ssm_update_lowers_to_one_mosaic_call_at_both_head_sizes(
        one_chip, table, groups, block):
    """`pk.ssm_update` at a head of 64 features (128 x 64 x 128, the shape
    `ssm_update_block_heads` answered None for: the three-pass fallback) is
    ONE Mosaic call, the table aliased, nothing table- or layer-sized made
    around it; a head of 128 (32 x 128 x 256) lowers as before: one head a
    lane row, blocks of 8, no packed row anywhere in its module."""
    L, N, Hs, P, Ns = table
    assert pk.ssm_update_block_heads(Hs, P, Ns, mosaic=True) == block
    assert pk.ssm_update_heads_per_row(Hs, P) == 128 // min(P, 128)
    specs = [(table, "float32"), ((N, Hs), "float32"),
             ((N, Hs, P), "float32"), ((N, groups, Ns), "float32"),
             ((N, groups, Ns), "float32"), ((N,), "bool")]
    args = [jax.ShapeDtypeStruct(s, np.dtype(dt), sharding=one_chip)
            for s, dt in specs]
    with pk.mosaic_lowering():
        compiled = jax.jit(
            lambda ss, *rest: pk.ssm_update(ss, *rest, layer=L - 1),
            donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    calls = _custom_calls(text)
    assert len(calls) == 1 and "ssm_update" in calls[0]
    assert "kernel_metadata={}" not in calls[0]
    size = 4 * int(np.prod(table))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= size, ma.alias_size_in_bytes
    assert ma.temp_size_in_bytes < size / 1000, ma.temp_size_in_bytes
    big = re.compile(r"f32\[(%d,)?%d,%d,%d,%d\]" % table)
    made = [m.group(0) for m in re.finditer(
        r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
        if big.search(m.group(2)) and m.group(3) not in (
            "custom-call", "parameter", "get-tuple-element")]
    assert not made, made[:6]
    module = _mosaic_module(calls[0])
    # the dt x block a grid step stages: [rows of the block, 128 lanes]
    rows = "memref<1x%dx128xf32" % (block * P // 128)
    assert rows in module, module[:400]
    # a head's state is [64, 128] there and [128, 256] here
    assert ("vector<64x128xf32>" in module) == (P == 64)


def test_recurrent_moe_step_holds_nine_ssm_updates_and_no_fallback(
        one_chip):
    """The step window of `granite_4_0_h_small` at its 96 slots, bfloat16
    weights at rest: the attention layer's K/V rows, nine conv windows and
    nine scanned states are donated and aliased; the step holds ONE Mosaic
    call without metadata (the attention layer's decode kernel) and NINE
    named `ssm_update`, one a Mamba layer, and no instruction but those
    makes a table- or layer-sized scanned state (the three-pass fallback
    made a reduce over it and select-and-update fusions)."""
    import json
    import os
    from paddle_tpu.inference import decode as dec
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "granite_4_0_h_small.json")) as f:
        cfg = json.load(f)
    meta, slots = cfg["model"], cfg["deployment"]["decode_slots"]
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device)
    state = {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}
    table = (9, slots) + GRANITE_TABLE[2:]
    assert pred.ssm_state_shape(slots) == table
    assert pred.table_shape(slots) == (1, slots, 1024, 1024)
    compiled = compile_phase(pred, state, pred._step_math(),
                             pred._step_specs(slots), tables=range(4))
    text = compiled.as_text()
    calls = _custom_calls(text)
    assert len([c for c in calls if "kernel_metadata={}" in c
                and "ragged" not in c.split(" = ")[0]]) >= 1
    named = [c for c in calls if "ssm_update" in c
             and "kernel_metadata={}" not in c]
    assert len(named) == 9
    from benchmark import moe_trace
    scoped = moe_trace.scope_instruction_names(text, "ssm_update")
    assert {c.split(" = ")[0].strip().lstrip("%") for c in named} <= scoped
    assert moe_trace.scope_instruction_names(text, "moe_ffn", "ragged-dot")
    ma = compiled.memory_analysis()
    held = sum(4 * int(np.prod(s)) for s in (
        pred.table_shape(slots), pred.table_shape(slots),
        pred.conv_state_shape(slots), table))
    assert ma.alias_size_in_bytes >= held, (ma.alias_size_in_bytes, held)
    assert ma.temp_size_in_bytes < 0.2e9, ma.temp_size_in_bytes
    big = re.compile(r"f32\[(%d,)?%d,%d,%d,%d\]" % table)
    made = [(m.group(1), m.group(3)) for m in re.finditer(
        r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
        if big.search(m.group(2).split("{")[0]) and m.group(3) in (
            "select", "concatenate", "copy", "pad", "transpose", "fusion",
            "reduce", "dynamic-update-slice", "dynamic-slice")]
    assert not made, made[:8]
    assert text.count(" while(") == 1


def test_sparse_linear_step_and_chunked_prefill_compile_for_the_chip(
        one_chip):
    """The step window of `minicpm_sala_9b` at the cell's 24 slots and a
    slot of 32,768 rows, bfloat16 weights at rest: the K/V rows, the linear
    layers' states and the indexer's cache are donated and aliased, a sparse
    layer makes ONE Mosaic call (`sparse_decode_attention`, under the scopes
    `sparse_attention`, beside stage 1's plain XLA under `sparse_select`)
    and a linear layer one (`ssm_update`), each with metadata of its own,
    and no table- or layer-sized copy is made.  (The prefill's buckets:
    `test_a_chunked_prefill_holds_the_flash_body_of_its_stage_2`.)"""
    import json
    import os
    from benchmark import moe_trace
    from paddle_tpu.inference import decode as dec
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "minicpm_sala_9b.json")) as f:
        cfg = json.load(f)
    meta, slots = cfg["model"], cfg["deployment"]["decode_slots"]
    device = list(one_chip.device_set)[0]
    pred, state = described_predictor(meta, device)
    state = {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}
    assert pred._table_names == ("kc", "vc", "ss", "ki")
    compiled = compile_phase(pred, state, pred._step_math(),
                             pred._step_specs(slots), tables=range(4))
    text = compiled.as_text()
    calls = _custom_calls(text)
    assert not [c for c in calls if "kernel_metadata={}" in c]
    sparse = [c for c in calls if "sparse_decode_attention" in c]
    linear = [c for c in calls if "ssm_update" in c]
    assert (len(sparse), len(linear), len(calls)) == (2, 6, 8)
    # a sparse call's grid is (24, 2, ceil(64 / T)), which the text does not
    # show: its operands do.  Five scalar-prefetch vectors (the tiles'
    # numbers one a tile: 24 x 2 x ceil(64 / T) x T of them), q, a row of
    # column positions a grid step, and the two carried TABLES THEMSELVES,
    # left in HBM
    from paddle_tpu.ops import pallas_kernels
    T = pallas_kernels.sparse_tiles_per_step(
        meta["sparse_topk"], meta["sparse_block"], meta["head_dim"], 4)
    assert T >= 8
    steps = slots * meta["n_kv_heads"] * -(-meta["sparse_topk"] // T)
    table = "f32[2,%d,%d,%d]" % (slots, meta["max_seq_len"],
                                 meta["n_kv_heads"] * meta["head_dim"])
    def made(name):
        """(shape, opcode) of the instruction that defines `name`."""
        (m,) = re.findall(r"^\s*%s = (\w+\[[\d,]*\])\S* ([\w\-]+)\("
                          % re.escape(name), text, re.M)
        return m

    for c in sparse:
        ops = re.findall(r"%[\w.\-]+", c.split("custom-call(")[1].split(
            "), custom_call_target")[0])
        assert len(ops) == 9, ops
        assert [made(o)[0] for o in ops[:7]] == [
            "s32[%d]" % (steps * T), "s32[%d]" % (slots * 2),
            "s32[%d]" % slots, "s32[%d]" % (steps + 1), "s32[%d]" % steps,
            "f32[%d,2,16,128]" % slots,
            "s32[%d,1,%d]" % (steps, T * meta["sparse_block"])], ops
        # ... each the table as the layer's row write left it (in place:
        # the temporaries below hold nothing table-sized), never a copy
        k_op, v_op = (made(o) for o in ops[7:])
        assert k_op[0] == v_op[0] == table and ops[7] != ops[8], (k_op, v_op)
        assert "copy" not in (k_op[1], v_op[1]), (k_op, v_op)
    for scope, named in (("sparse_attention", sparse),
                         ("ssm_update", linear),
                         ("linear_attention", linear)):
        assert {c.split(" = ")[0].strip().lstrip("%") for c in named} \
            <= moe_trace.scope_instruction_names(text, scope), scope
    assert moe_trace.scope_instruction_names(text, "sparse_select")
    ma = compiled.memory_analysis()
    held = pred.kv_cache_bytes(slots)
    assert held == cfg["deployment"]["kv_table_bytes"] \
        + cfg["deployment"]["ssm_state_table_bytes"] \
        + cfg["deployment"]["index_table_bytes"]
    assert ma.alias_size_in_bytes >= held, (ma.alias_size_in_bytes, held)
    assert ma.temp_size_in_bytes < 0.1e9, ma.temp_size_in_bytes
    assert text.count(" while(") == 1


@pytest.fixture(scope="module")
def sparse_linear_predictor(one_chip):
    """(`minicpm_sala_9b`'s meta, a weightless predictor of it on the
    described chip, its state's specs with bfloat16 weights at rest)."""
    import json
    import os
    from paddle_tpu.inference import decode as dec
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "minicpm_sala_9b.json")) as f:
        meta = json.load(f)["model"]
    pred, state = described_predictor(meta, list(one_chip.device_set)[0])
    return meta, pred, {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}


# the 24,576 bucket's temporaries with stage 2 in plain XLA (PR 49's
# executable, compiled here for the same described chip)
PARENT_PREFILL_TEMPORARIES = 1.4976e9


@pytest.mark.parametrize("bucket", [8192, 16384, 24576])
def test_a_chunked_prefill_holds_the_flash_body_of_its_stage_2(
        sparse_linear_predictor, bucket):
    """A prefill bucket of `minicpm_sala_9b`, its chunks of 2,048 positions
    inside ONE executable: the chunk body makes ONE Mosaic call a sparse
    layer, `sparse_prefill_attention` (metadata of its own, under the scope
    `sparse_attention`, where the benchmark's reader of that scope finds it
    by name; a name the step's kernel's readers do not match), over the
    chunk's queries as XLA laid them out [K/V heads, G, C, Dh] in bfloat16,
    the bucket's K and V rows as they are and the selection a BLOCK (never
    a position) with the queries last; no tile of scores [512, 2, 16, 2048]
    is a temporary any more, and the temporaries are a chunk's, no larger
    than they were.  The selection's `top_k` is a sort of [512, 2, blocks]
    with the QUERIES on the lanes (layout {0,2,1}), as it was before the
    kernel: sorted along the lanes it took 27 times as long on the chip
    (PERF.md section 6, PR 50), and which one XLA picks follows from how the
    selection leaves `_chunk_attention`'s map."""
    from benchmark import moe_trace
    meta, pred, state = sparse_linear_predictor
    assert bucket in meta["prefill_buckets"]
    compiled = compile_phase(
        pred, state, pred._prefill_math,
        (jax.ShapeDtypeStruct((1, bucket), np.int32),
         jax.ShapeDtypeStruct((), np.int32)), tables=())
    text = compiled.as_text()
    calls = _custom_calls(text)
    names = [c.split(" = ")[0].strip().lstrip("%") for c in calls]
    assert len(calls) == 2 and not [c for c in calls
                                    if "kernel_metadata={}" in c]
    assert all(n.startswith("sparse_prefill_attention") for n in names)
    assert not [n for n in names if "sparse_decode_attention" in n]
    assert set(names) <= moe_trace.scope_instruction_names(
        text, "sparse_attention")
    for scope in ("sparse_select", "sparse_attention", "linear_attention",
                  "ssm_scan"):
        assert moe_trace.scope_instruction_names(text, scope), scope
    C, Hc, Dh = meta["prefill_chunk"], meta["n_kv_heads"], meta["head_dim"]
    G = meta["n_heads"] // Hc
    for c in calls:
        ops = re.findall(r"%[\w.\-]+", c.split("custom-call(")[1].split(
            "), custom_call_target")[0])
        shapes = [re.findall(r"^\s*%s = (\w+\[[\d,]*\])" % re.escape(o),
                             text, re.M)[0] for o in ops]
        assert c.split(" = ")[1].startswith(
            "f32[%d,%d]" % (C, meta["n_heads"] * Dh)), c[:200]
        assert shapes == [
            "s32[2]", "bf16[%d,%d,%d,%d]" % (Hc, G, C, Dh),
            "f32[%d,%d]" % (bucket, Hc * Dh),
            "f32[%d,%d]" % (bucket, Hc * Dh),
            "f32[%d,%d,%d]" % (Hc, bucket // meta["sparse_block"], C)], \
            shapes
    assert "f32[%d,%d,%d,%d]" % (
        512, Hc, G, C) not in text
    sorts = [ln.split(" = (")[1].split(":")[0] for ln in text.splitlines()
             if " sort(" in ln and "/sparse_select/" in ln]
    assert sorts == ["f32[512,%d,%d]{0,2,1" % (
        Hc, bucket // meta["sparse_block"])] * 2, sorts
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= PARENT_PREFILL_TEMPORARIES, temporaries


# ---------------------------------------------------------------------------
# a stack whose attending layers have their own geometries by kind and by
# leaf (PR 51): `mimo_v2_flash` at the published widths
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kinds_predictor(one_chip):
    """(`mimo_v2_flash`'s configuration, a weightless predictor of it on the
    described chip, its state's specs with bfloat16 weights at rest)."""
    import json
    from paddle_tpu.inference import decode as dec
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "mimo_v2_flash.json")) as f:
        cfg = json.load(f)
    pred, state = described_predictor(cfg["model"],
                                      list(one_chip.device_set)[0])
    return cfg, pred, {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}


def test_kinds_step_updates_four_tables_of_four_widths_in_place(
        kinds_predictor):
    """The step window of `mimo_v2_flash` at the cell's 96 slots: the full
    layers' K and V tables (768 and 512 lanes a row) and the window layers'
    K and V rings (1,536 and 1,024) are four leaves of four shapes, all
    donated and aliased; a layer makes ONE Mosaic call, the decode kernel
    (seven a trip: K tiles and V tiles of their own widths, the window
    layers' with their sinks), and the arguments and temporaries are what
    `deployment.decode_slots_arithmetic` says."""
    cfg, pred, state = kinds_predictor
    slots = cfg["deployment"]["decode_slots"]
    shapes = [s.shape for s in pred._step_specs(slots)[:4]]
    assert shapes == [(2, slots, 4096, 768), (2, slots, 4096, 512),
                      (5, slots, 128, 1536), (5, slots, 128, 1024)]
    compiled = compile_phase(pred, state, pred._step_math(),
                             pred._step_specs(slots), tables=range(4))
    text = compiled.as_text()
    kernels = [c for c in _custom_calls(text) if "kernel_metadata={}" in c]
    assert len(kernels) == 7
    ma = compiled.memory_analysis()
    held = sum(4 * int(np.prod(s)) for s in shapes)
    assert held == pred.kv_cache_bytes(slots) == 4655677440
    assert ma.alias_size_in_bytes >= held, (ma.alias_size_in_bytes, held)
    # 4.457 GB of weights + 4.656 of tables; nothing table-sized beside them
    assert 9.10e9 < ma.argument_size_in_bytes < 9.13e9
    assert ma.temp_size_in_bytes < 0.05e9, ma.temp_size_in_bytes
    for L, N, S, W in shapes:
        big = re.compile(r"f32\[(%d,)?%d,%d,%d\]" % (L, N, S, W))
        made = [(m.group(1), m.group(3)) for m in re.finditer(
            r"(%[\w.\-]+) = (\S+) ([\w\-]+)\(", text)
            if big.search(m.group(2).split("{")[0]) and m.group(3) in (
                "select", "concatenate", "copy", "pad", "transpose")]
        assert not made, made[:6]
    assert text.count(" while(") == 1
    from benchmark import moe_trace
    names = {c.split(" = ")[0].strip().lstrip("%") for c in kernels}
    window = moe_trace.scope_instruction_names(text, "window_attention")
    full = moe_trace.scope_instruction_names(text, "full_attention")
    assert len(names & window) == 5 and len(names & full) == 2


@pytest.mark.parametrize("bucket,temporaries", [(512, 0.35e9),
                                                (1024, 0.68e9)])
def test_kinds_prefill_compiles_within_its_temporaries(kinds_predictor,
                                                       bucket, temporaries):
    """A prefill bucket of `mimo_v2_flash`: its attention plain XLA (no call
    of the decode kernel; the grouped matmuls of the routed FFN are XLA's), its
    rows returned as the four tables hold a position, temporaries what the
    configuration's arithmetic counts (0.326 / 0.643 GB; 0.305 / 0.644 since
    PR 57's routed layers, whose k-sum stays behind their `cond`: inside
    both branches it read 0.353 / 0.717)."""
    cfg, pred, state = kinds_predictor
    assert bucket in cfg["model"]["prefill_buckets"]
    on = jax.sharding.SingleDeviceSharding(pred._device)
    specs = (jax.ShapeDtypeStruct((1, bucket), np.int32, sharding=on),
             jax.ShapeDtypeStruct((), np.int32, sharding=on))
    with pk.mosaic_lowering():
        compiled = pred._phase_jit(pred._prefill_math, ()).lower(
            state, *specs).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < temporaries, ma.temp_size_in_bytes
    out = jax.eval_shape(pred._prefill_math, state, *specs)
    assert [o.shape for o in out[1:]] == [
        (2, 1, bucket, 768), (2, 1, bucket, 512), (5, 1, 128, 1536),
        (5, 1, 128, 1024)]
    assert not [c for c in _custom_calls(compiled.as_text())
                if "kernel_metadata={}" in c]


# --- PR 57: a member's routed FFN works over the rows that stay ------------

@pytest.fixture(scope="module")
def held_predictor(one_chip):
    """(`granite_4_0_h_small`'s configuration cut to an ssm and an attention
    layer, a weightless predictor of it on the described chip, its state's
    specs with bfloat16 weights at rest)."""
    import json
    from paddle_tpu.inference import decode as dec
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "granite_4_0_h_small.json")) as f:
        cfg = json.load(f)
    meta = dict(cfg["model"], n_layers=2, layer_types=["ssm", "attention"])
    pred, state = described_predictor(meta, list(one_chip.device_set)[0])
    return cfg, pred, {n: jax.ShapeDtypeStruct(
        s.shape, jnp.bfloat16 if dec._bf16_at_rest(n, s) else np.float32,
        sharding=s.sharding) for n, s in state.items()}


@pytest.mark.parametrize("phase", ["step", "256", "512", "256x4", "512x2"])
def test_a_held_routed_layer_runs_its_grouped_matmuls_over_cap_rows(
        held_predictor, phase):
    """Granite's one-prompt and group prefills of both buckets, 18 of 72
    experts held: every routed layer is ONE `conditional`; one branch's
    three `ragged-dot` kernels take `held_cap` rows (1,408 / 2,688 of 2,560
    / 5,120; 5,376 of 10,240 a group), the other's every pair's row, and
    the compiler tiles a grouped matmul's rows as `decode._row_tile` says:
    by the largest power of two that divides them, which `held_cap`'s odd
    number of tiles keeps at the tile it chose (128 or 256; the full-size
    branch's rows get 512).  Its step window (96 slots x top-10 = 960 pairs
    a trip) would get tiles of 64 at 576 rows as at 960: no `conditional`,
    the six kernels over every pair's row as the parent ran them."""
    from paddle_tpu.inference import decode as dec
    cfg, pred, state = held_predictor
    slots, k = cfg["deployment"]["decode_slots"], 10
    on = jax.sharding.SingleDeviceSharding(pred._device)
    if phase == "step":
        pairs = slots * k
        compiled = compile_phase(pred, state, pred._step_math(),
                                 pred._step_specs(slots), tables=range(4))
    else:
        bucket, _, prompts = phase.partition("x")
        bucket, prompts = int(bucket), int(prompts or 1)
        assert prompts in (1, pred.prefill_width(bucket))
        pairs = prompts * bucket * k
        lead = (prompts,) if prompts > 1 else ()
        specs = (jax.ShapeDtypeStruct((prompts, bucket), np.int32,
                                      sharding=on),
                 jax.ShapeDtypeStruct(lead, np.int32, sharding=on))
        with pk.mosaic_lowering():
            compiled = pred._phase_jit(
                pred._prefill_group_math if prompts > 1
                else pred._prefill_math, ()).lower(state, *specs).compile()
    text = compiled.as_text()
    cap = dec.held_cap(pairs, 18, 72)
    assert cap == pairs if phase == "step" else \
        pairs / 2 <= cap < 0.61 * pairs
    assert text.count(" conditional(") == (0 if phase == "step" else 2)
    rows = re.findall(r"bf16\[(\d+),(?:4096|768)\]\{1,0\}, "
                      r"bf16\[18,\d+,\d+\]\{2,1,0\}\}, [^\n]*?"
                      r'ragged_dot_tiling="(\d+),', text)
    assert sorted(rows) == sorted(
        [(str(n), str(dec._row_tile(n))) for n in {cap, pairs}] * 6), rows
    assert dec._row_tile(cap) == {960: 64, 2560: 128, 5120: 128,
                                  10240: 256}[pairs]


# sha256 of the optimized HLO of the PARENT's step (PR 56, b41f14a) at the
# configuration's widths, two layers, for a described v5e, as
# tools/decode_hlo_dump.py writes it (`v5e_<config>_step.hlo`)
PARENT_STEP_HLO = {
    "olmoe_1b_7b":
    "1287c90f480da896a538e26033182d91768cc34e3310d133c54f75062020b4a3",
    "lfm2_24b_a2b":
    "b1f6e8721adb0bef7622771d526202afab25d497059c26f11ffddec6a2bc48c6"}


@pytest.mark.parametrize("config", sorted(PARENT_STEP_HLO))
def test_a_stack_that_holds_all_its_experts_runs_the_parents_step(
        one_chip, config):
    """`held is None` (OLMoE, LFM2) was not touched: the step executable's
    text is the parent's byte for byte."""
    import hashlib
    from paddle_tpu.inference import decode as dec
    from tools import decode_hlo_dump as dump
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    meta, slots = dump.cells(here)[config]
    texts = dump.v5e_phases(dec, pk, list(one_chip.device_set)[0], config,
                            meta, slots, only=("step",))
    assert hashlib.sha256(texts["step"][2].encode()).hexdigest() \
        == PARENT_STEP_HLO[config]
