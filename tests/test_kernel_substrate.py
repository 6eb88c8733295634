"""Unified oracle/parity harness for the tiled-contraction kernel
substrate (ROOFLINE.md "Kernel substrate") + the int8 KV-cache decode
path (QUANTIZE.md "Quantized KV cache").

Every Pallas family — flash fwd/bwd, decode attention (fp32 AND int8
cache), fused dequant-matmul — instantiates ONE driver
(ops/pallas_kernels.tiled_contraction); this file sweeps each family
against its plain-XLA oracle across dtypes x geometries (tileable,
untileable-fallback, batch-1), then pins the int8 KV-cache contracts:
cache bytes <= 0.27x fp32 at equal slots, greedy self-bit-stability,
fp32-vs-int8 top-1 agreement >= 0.99 on the tiny fixture, slot-reuse
zero-leakage, rollback bit-identity, and spec-decode accept rate 1.0
for the same-cache-dtype twin.

The *_smoke tests are the ci_checks.sh `kernels` gate (exit 15)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture
def store(tmp_path):
    from paddle_tpu import compile_cache as cc
    old = fluid.get_flags(["compile_cache", "compile_cache_dir"])
    root = str(tmp_path / "cc_store")
    fluid.set_flags({"compile_cache": True, "compile_cache_dir": root})
    cc.reset_stats()
    yield root
    fluid.set_flags(old)
    cc.reset_stats()


def _qkv(B, S, H, D, dtype, seed=0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(B, S, H, D).astype(np.float32) * 0.3).astype(dtype)
    return mk(), mk(), mk()


def _decode_operands(N, S, H, D, kv_dtype, seed=1):
    """(q, k_cache, v_cache, lengths, kv_scales) for one decode shape,
    the caches as a slot table holds them, [N, S, H * D] (a position one
    flat row, the heads side by side); int8 caches come with matching
    per-head scales."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(N, H, D).astype(np.float32))
    kf = rng.randn(N, S, H, D).astype(np.float32)
    vf = rng.randn(N, S, H, D).astype(np.float32)
    lengths = np.concatenate([[S], rng.randint(1, S + 1, size=N - 1)]) \
        .astype(np.int32) if N > 1 else np.array([S], np.int32)
    def flat(t):
        return jnp.asarray(t.reshape(N, S, H * D))

    if kv_dtype != "int8":
        return q, flat(kf), flat(vf), lengths, None
    ks = np.abs(kf).max(axis=(0, 1, 3)) * 1.25 / 127.0
    vs = np.abs(vf).max(axis=(0, 1, 3)) * 1.25 / 127.0
    k8 = flat(np.clip(np.round(
        kf / ks[None, None, :, None]), -127, 127).astype(np.int8))
    v8 = flat(np.clip(np.round(
        vf / vs[None, None, :, None]), -127, 127).astype(np.int8))
    return q, k8, v8, lengths, np.stack([ks, vs]).astype(np.float32)


# ---------------------------------------------------------------------------
# the parity matrix: every family x dtype x geometry vs its oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,causal,S,blocks", [
    ("float32", False, 64, (16, 16)),
    ("float32", True, 64, (16, 32)),
    ("bfloat16", True, 64, (32, 16)),
    ("float32", True, 63, None),       # prime-ish S: XLA fallback path
    ("float32", False, 64, (64, 64)),  # single-tile degenerate grid
])
def test_flash_family_parity(dtype, causal, S, blocks):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention
    from paddle_tpu.parallel.ring_attention import local_attention
    q, k, v = _qkv(2, S, 2, 16, dtype)
    kw = dict(zip(("block_q", "block_kv"), blocks)) if blocks else {}
    out = flash_attention(q, k, v, causal=causal, **kw)
    ref = local_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    assert float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_family_parity(causal):
    """The two transposed-stationarity bwd instantiations against the
    XLA-autodiff oracle."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention
    from paddle_tpu.parallel.ring_attention import local_attention
    q, k, v = _qkv(1, 32, 2, 8, "float32", seed=3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    gk = jax.grad(loss(lambda *a, **kw: flash_attention(
        *a, block_q=8, block_kv=8, **kw)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(local_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        assert float(jnp.abs(a - b).max()) < 5e-4


@pytest.mark.parametrize("kv_dtype,N,S,bkv", [
    ("float32", 3, 32, 8),
    ("float32", 1, 32, 16),            # batch-1 slot table
    ("float32", 3, 31, None),          # untileable S: fallback
    ("int8", 3, 32, 8),
    ("int8", 1, 32, 32),               # batch-1, whole-cache tile
    ("int8", 3, 31, None),             # int8 fallback path
])
def test_decode_family_parity(kv_dtype, N, S, bkv):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (
        decode_attention, decode_attention_reference)
    q, kc, vc, lengths, scales = _decode_operands(N, S, 2, 8, kv_dtype)
    out = decode_attention(q, kc, vc, lengths, block_kv=bkv,
                           kv_scales=scales)
    ref = decode_attention_reference(q, kc, vc, lengths,
                                     kv_scales=scales)
    assert out.shape == (N, 2, 8)
    assert float(jnp.abs(out - ref).max()) < 2e-5


# GPT-2 small's heads, OLMoE's, LFM2's grouped-query table: (H, Hc, Dh)
_FLAT_GEOMETRIES = [(12, 12, 64), (16, 16, 128), (32, 8, 64)]


@pytest.mark.parametrize("form", ["single", "stacked"])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("H,Hc,D", _FLAT_GEOMETRIES,
                         ids=["h%d_kv%d_d%d" % g for g in _FLAT_GEOMETRIES])
def test_flat_row_body_against_the_reference(H, Hc, D, kv_dtype, form):
    """The body over FLAT rows [.., S, Hc * D] (block-diagonal queries,
    both contractions on the MXU at fp32) against the plain-XLA reference,
    whole and one head at a time over that head's lanes of the row (K/V
    head c is lanes c * D .. (c + 1) * D), at the served stacks' head
    geometries: lengths 0, 1, a block's edge and its neighbours, S; fp32 and
    int8 caches; a single layer and a layer of the stacked table.  No
    farther from the reference than the VPU body it replaced was on these
    very cases (the parent's 4.8e-7 to 1.13e-6 beside this body's 5.1e-7
    to 1.13e-6, values up to 3.5: PR 41's readings), and a slot of length
    0 is finite."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    N, S, bkv = 6, 64, 16
    q, k, v, _, scales = _decode_operands(N, S, Hc, D, kv_dtype, seed=H)
    if H != Hc:
        q = jnp.asarray(np.random.RandomState(H).randn(N, H, D)
                        .astype(np.float32))
    lengths = np.array([0, 1, bkv - 1, bkv, bkv + 1, S], np.int32)
    flat = [k, v]
    layer = None
    if form == "stacked":
        layer = 1
        flat = [jnp.stack([jnp.zeros_like(t), t]) for t in flat]
    if kv_dtype == "int8" and H != Hc:
        with pytest.raises(ValueError, match="query heads over"):
            pk.decode_attention(q, *flat, lengths, block_kv=bkv,
                                kv_scales=np.ones((2, H), np.float32),
                                layer=layer)
        return
    got = np.asarray(pk.decode_attention(q, *flat, lengths, block_kv=bkv,
                                         kv_scales=scales, layer=layer))
    want = np.asarray(pk.decode_attention_reference(q, k, v, lengths,
                                                    kv_scales=scales))
    assert got.shape == (N, H, D) and np.isfinite(got).all()
    assert np.abs(got - want)[1:].max() <= 1.2e-6
    G = H // Hc
    for a in range(0, H, 5):
        lanes = slice(a // G * D, (a // G + 1) * D)
        alone = np.asarray(pk.decode_attention_reference(
            q[:, a:a + 1], k[..., lanes], v[..., lanes], lengths,
            kv_scales=None if scales is None else scales[:, a:a + 1]))
        np.testing.assert_allclose(alone[:, 0], want[:, a], rtol=2e-6,
                                   atol=2e-6)


# ---------------------------------------------------------------------------
# the bounded K/V stream: slot b's blocks stop at kv_last_block(lengths[b])
# ---------------------------------------------------------------------------

_BKV, _S = 8, 32
# 1, bkv - 1, bkv, bkv + 1, S - 1, S, mixed over the slots
_EDGE_LENGTHS = np.array([1, _BKV - 1, _BKV, _BKV + 1, _S - 1, _S], np.int32)


def _bounded_case(form, kv_dtype, seed=5):
    """(call(lengths, k, v) -> out [N, H, D], reference(lengths, k, v), k, v)
    of one entry of the decode family: `form` in plain | stacked | gqa |
    gqa_stacked | head_slice | head_slice_stacked."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    N = len(_EDGE_LENGTHS)
    H, Hc = (32, 8) if form.startswith("gqa") else (4, 4)
    q, k, v, _, scales = _decode_operands(N, _S, Hc, 8, kv_dtype, seed=seed)
    if H != Hc:
        q = jnp.asarray(np.random.RandomState(seed).randn(N, H, 8)
                        .astype(np.float32))
    stacked = form.endswith("stacked")
    layer = 1 if stacked else None

    def table(t):
        # the layer of a stacked table that is NOT attended holds NaN
        # (float) / 127 (int8): a kernel that strayed there would show
        if not stacked:
            return t
        other = jnp.full_like(t, 127 if kv_dtype == "int8" else np.nan)
        return jnp.stack([other, t])

    if form.startswith("head_slice"):
        # a member's 2 heads of 4 (of 8 lanes each), its scales sliced out
        # of the full table
        def call(lengths, k, v):
            return pk.decode_attention_head_slice(
                q[:, 2:], table(k[..., 16:]), table(v[..., 16:]), lengths,
                head_offset=2, n_local_heads=2, block_kv=_BKV,
                kv_scales=scales, layer=layer)

        def ref(lengths, k, v):
            return pk.decode_attention_reference(
                q[:, 2:], k[..., 16:], v[..., 16:], lengths,
                kv_scales=None if scales is None else scales[:, 2:])
    else:
        def call(lengths, k, v):
            return pk.decode_attention(q, table(k), table(v), lengths,
                                       block_kv=_BKV, kv_scales=scales,
                                       layer=layer)

        def ref(lengths, k, v):
            return pk.decode_attention_reference(q, k, v, lengths,
                                                 kv_scales=scales)
    return call, ref, k, v


_BOUNDED = [(form, dt) for form in ("plain", "stacked", "head_slice",
                                    "head_slice_stacked")
            for dt in ("float32", "int8")] \
    + [("gqa", "float32"), ("gqa_stacked", "float32")]


@pytest.fixture
def whole_rows(monkeypatch):
    """Enter to make `decode_attention` stream every block of every slot,
    as it did before its stream was bounded: the rule says "the last
    block" for every length, the mask still follows the true lengths."""
    import contextlib
    from paddle_tpu.ops import pallas_kernels as pk

    @contextlib.contextmanager
    def enter():
        with monkeypatch.context() as m:
            m.setattr(pk, "kv_last_block",
                      lambda lengths, bkv, n_blocks, xp=np:
                      n_blocks - 1 + 0 * lengths)
            yield
    return enter


@pytest.mark.parametrize("form,kv_dtype", _BOUNDED)
def test_decode_bounded_stream_is_the_whole_row_stream(form, kv_dtype,
                                                       whole_rows):
    """Lengths on and around every block edge: within rounding of the
    reference, and BIT-EQUAL to the same kernel made to stream whole
    rows (a wholly dead block added exp(_NEG_INF - m) = 0)."""
    call, ref, k, v = _bounded_case(form, kv_dtype)
    for shift in range(len(_EDGE_LENGTHS)):
        lengths = np.roll(_EDGE_LENGTHS, shift)
        got = np.asarray(call(lengths, k, v))
        np.testing.assert_allclose(got, np.asarray(ref(lengths, k, v)),
                                   rtol=2e-5, atol=2e-5)
        with whole_rows():
            whole = np.asarray(call(lengths, k, v))
        assert np.array_equal(got, whole), (form, kv_dtype, shift)


@pytest.mark.parametrize("form", ["plain", "stacked", "gqa", "gqa_stacked",
                                  "head_slice"])
def test_decode_never_reads_past_a_slots_last_live_block(form, whole_rows):
    """Every position past a slot's last live block poisoned with NaN:
    the result is finite and equal to the clean table's.  A kernel that
    streams whole rows multiplies the poison by its zero weight and
    returns NaN (the second half: the poison is real)."""
    import jax.numpy as jnp
    call, _, k, v = _bounded_case(form, "float32")
    lengths = _EDGE_LENGTHS
    dead_from = -(-lengths // _BKV) * _BKV                     # [N]
    dead = (np.arange(_S)[None] >= dead_from[:, None])[:, :, None]
    assert dead.any()
    clean = np.asarray(call(lengths, k, v))
    kp, vp = (jnp.where(dead, np.nan, t) for t in (k, v))
    got = np.asarray(call(lengths, kp, vp))
    assert np.all(np.isfinite(got))
    assert np.array_equal(got, clean)
    with whole_rows():
        assert not np.all(np.isfinite(np.asarray(call(lengths, kp, vp))))


@pytest.mark.parametrize("bkv,n_blocks", [(8, 4), (128, 8), (128, 32),
                                          (1, 1), (16, 1)])
def test_kv_last_block_rule(bkv, n_blocks):
    """last = max(ceil(len / bkv), 1) - 1, never past the table; grid
    step j stages block min(j, last) and computes iff j <= last.  The
    host's numpy and the kernel's jax.numpy spell it alike."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import kv_last_block
    S = bkv * n_blocks
    lengths = np.arange(0, S + 3, dtype=np.int32)   # past S: lengths + 1
    want = np.array([min(max(-(-int(n) // bkv), 1), n_blocks) - 1
                     for n in lengths])
    got = kv_last_block(lengths, bkv, n_blocks)
    assert isinstance(got, np.ndarray) and np.array_equal(got, want)
    assert np.array_equal(
        np.asarray(kv_last_block(jnp.asarray(lengths), bkv, n_blocks,
                                 xp=jnp)), want)
    assert got[0] == 0 and got[1] == 0                # length 0: block 0
    assert got[S] == n_blocks - 1 == got[S + 2]       # full, and past it
    if bkv > 1:
        assert got[bkv] == 0 and got[bkv + 1] == min(1, n_blocks - 1)
    j = np.arange(n_blocks)[:, None]
    staged = np.minimum(j, got[None])                 # [n_blocks, lengths]
    live = j <= got[None]
    # a slot's stream is last + 1 distinct blocks, 0 .. last, in order;
    # every dead step repeats the last live block
    assert np.array_equal(live.sum(0), got + 1)
    assert np.array_equal(staged.max(0), got)
    assert np.all(staged[~live] == np.broadcast_to(got, staged.shape)[~live])


def test_decode_int8_requires_scales():
    from paddle_tpu.ops.pallas_kernels import decode_attention
    q, kc, vc, lengths, _ = _decode_operands(2, 32, 2, 8, "int8")
    with pytest.raises(ValueError, match="kv_scales"):
        decode_attention(q, kc, vc, lengths)


@pytest.mark.parametrize("M,K,N,blocks,act", [
    (8, 16, 32, (4, 8, 16), "float32"),
    (1, 32, 16, (1, 16, 8), "float32"),   # batch-1 serving bucket
    (8, 32, 64, (4, 16, 32), "bfloat16"),
    (3, 7, 13, None, "float32"),          # nothing tiles: fallback
])
def test_dequant_family_parity(M, K, N, blocks, act):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (
        dequant_matmul, dequant_matmul_reference)
    rng = np.random.RandomState(M + N)
    x = jnp.asarray(rng.randn(M, K).astype(np.float32)).astype(act)
    wq = jnp.asarray(rng.randint(-127, 128, (K, N)).astype(np.int8))
    s = jnp.asarray(rng.rand(N).astype(np.float32) * 0.1 + 0.01)
    kw = dict(zip(("block_m", "block_k", "block_n"), blocks)) \
        if blocks else {}
    out = dequant_matmul(x, wq, s, out_dtype=np.float32, **kw)
    ref = dequant_matmul_reference(x, wq, s, out_dtype=np.float32)
    assert float(jnp.abs(out - ref).max()) < 1e-3


# ---------------------------------------------------------------------------
# ssm_update: the state-space recurrence step, one pass over a slot's state
# ---------------------------------------------------------------------------


def _ssm_operands(L, N, Hs, P, Ns, G, seed=3):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(L, N, Hs, P, Ns).astype(np.float32)),
            jnp.asarray(np.exp(-2 * rng.rand(N, Hs)).astype(np.float32)),
            jnp.asarray(rng.randn(N, Hs, P).astype(np.float32)),
            jnp.asarray(rng.randn(N, G, Ns).astype(np.float32)),
            jnp.asarray(rng.randn(N, G, Ns).astype(np.float32)))


# which slots run: all, one idle inside, idle slots AHEAD of the first
# runner and behind the last, only the last, none
_SSM_ACTIVE = {"all": [1, 1, 1, 1, 1], "one_idle": [1, 1, 0, 1, 1],
               "idle_ahead_and_behind": [0, 0, 1, 0, 1, 0],
               "last_only": [0, 0, 0, 1], "first_only": [1, 0, 0],
               "none": [0, 0, 0]}


@pytest.mark.parametrize("heads,block", [(4, 4), (8, 2)],
                         ids=["one_block", "four_blocks"])
@pytest.mark.parametrize("active", sorted(_SSM_ACTIVE))
def test_ssm_update_against_the_reference(active, heads, block,
                                          monkeypatch):
    """The kernel in interpret mode against `ssm_update_reference`: G = 2
    groups of heads, a table of two layers updated at layer 1.  A slot
    that does not run keeps its state bit for bit and reads y = 0; layer 0
    is bit-identical after; where every slot's state is one block of the
    grid and where it is four (a block then lies inside one group or the
    other)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    run = np.asarray(_SSM_ACTIVE[active], bool)
    P, Ns = 8, 16
    monkeypatch.setattr(pk, "_SSM_BLOCK_BYTES", 4 * block * P * Ns)
    assert pk.ssm_update_block_heads(heads, P, Ns) == block
    ss, decay, dtx, Bm, Cm = _ssm_operands(2, len(run), heads, P, Ns, 2)
    y, table = pk.ssm_update(ss, decay, dtx, Bm, Cm, jnp.asarray(run), 1)
    y0, table0 = pk.ssm_update_reference(ss, decay, dtx, Bm, Cm,
                                         jnp.asarray(run), 1)
    assert y.shape == (len(run), heads, P) and table.shape == ss.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(table), np.asarray(table0),
                               rtol=1e-6, atol=1e-6)
    got, was = np.asarray(table), np.asarray(ss)
    assert np.array_equal(got[0], was[0])             # the other layer
    assert np.array_equal(got[1][~run], was[1][~run])  # slots that sat out
    assert not np.asarray(y)[~run].any()
    if run.any():
        assert not np.array_equal(got[1][run], was[1][run])
        # the recurrence by hand, one value
        n = int(np.flatnonzero(run)[0])
        h, p, k = heads - 1, 3, 5
        g = h // (heads // 2)
        want = (float(decay[n, h]) * was[1, n, h, p, k]
                + float(dtx[n, h, p]) * float(Bm[n, g, k]))
        assert abs(got[1, n, h, p, k] - want) < 1e-5


@pytest.mark.parametrize("heads,P,Ns,groups,block", [
    (32, 64, 128, 1, 32), (16, 64, 128, 2, 16), (8, 32, 128, 1, 8),
    (16, 8, 16, 2, 16)], ids=["granite", "two_groups", "four_a_row",
                              "sixteen_a_row"])
@pytest.mark.parametrize("active", ["idle_ahead_and_behind", "none",
                                    "first_only"])
def test_ssm_update_with_heads_side_by_side_in_a_lane_row(
        active, heads, P, Ns, groups, block, monkeypatch):
    """A head of 64 features (Granite-4.0-H: two heads a 128-lane row of dt
    x and y; 32, 16 and 8 features ride the same body, four, eight and
    sixteen a row) in interpret mode against `ssm_update_reference`: the
    state bit for bit under one jit each (both compute decay * S + dt x
    (outer) B in fp32, element by element), the read-out to rounding, a
    slot that does not run keeps its state and reads y = 0, the other layer
    untouched, the table taken in place (aliased)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    run = np.asarray(_SSM_ACTIVE[active], bool)
    assert pk.ssm_update_heads_per_row(heads, P) == 128 // P
    assert pk.ssm_update_block_heads(heads, P, Ns, mosaic=False) == block
    if active == "none" and heads == 32:
        # two blocks a slot: the grid walks a running slot's blocks
        monkeypatch.setattr(pk, "_SSM_BLOCK_BYTES", 4 * 16 * P * Ns)
        assert pk.ssm_update_block_heads(heads, P, Ns, mosaic=False) == 16
    ss, decay, dtx, Bm, Cm = _ssm_operands(2, len(run), heads, P, Ns,
                                           groups)
    kernel = jax.jit(lambda t, *o: pk.ssm_update(t, *o, 1),
                     donate_argnums=(0,))
    oracle = jax.jit(lambda t, *o: pk.ssm_update_reference(t, *o, 1))
    ops = (decay, dtx, Bm, Cm, jnp.asarray(run))
    y0, table0 = oracle(ss, *ops)
    was = np.asarray(ss)
    held = ss + 0.0
    y, table = kernel(held, *ops)
    assert held.is_deleted()                    # the table, in place
    assert "input_output_aliases" in str(jax.make_jaxpr(
        lambda t, *o: pk.ssm_update(t, *o, 1))(ss, *ops))
    assert y.shape == (len(run), heads, P) and table.shape == was.shape
    np.testing.assert_array_equal(np.asarray(table), np.asarray(table0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    got = np.asarray(table)
    assert np.array_equal(got[0], was[0])              # the other layer
    assert np.array_equal(got[1][~run], was[1][~run])  # slots that sat out
    assert not np.asarray(y)[~run].any()
    if run.any():
        n = int(np.flatnonzero(run)[0])
        h, p, k = heads - 1, P - 3, 5          # the LAST head of a row
        g = h // (heads // groups)
        want = (float(decay[n, h]) * was[1, n, h, p, k]
                + float(dtx[n, h, p]) * float(Bm[n, g, k]))
        assert abs(got[1, n, h, p, k] - want) < 1e-5


def test_ssm_update_block_follows_the_shape_and_refuses_by_name():
    """The block of heads is the shape's (what fits `_SSM_BLOCK_BYTES` and
    divides the heads), not a flag: Falcon-H1's 32 heads of [128, 256]
    fp32 go 8 a step (1 MiB).  Under Mosaic a block is whole groups of 8
    heads, and a stack whose heads do not make one, or whose [P, Ns] is
    not whole tiles, has no kernel (None: the reference); operands that do
    not fit the table are a typed error."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    assert pk.ssm_update_block_heads(32, 128, 256, mosaic=True) == 8
    assert pk.ssm_update_block_heads(48, 128, 128, mosaic=True) == 16
    assert pk.ssm_update_block_heads(8, 256, 512, mosaic=True) is None
    assert pk.ssm_update_block_heads(12, 128, 256, mosaic=True) is None
    assert pk.ssm_update_block_heads(8, 64, 128, mosaic=True) is None
    # heads of 64 go two a lane row: whole groups of 8 rows are 16 heads,
    # Granite-4.0-H's 128 heads of [64, 128] go 32 a step (1 MiB)
    assert pk.ssm_update_block_heads(128, 64, 128, mosaic=True) == 32
    assert pk.ssm_update_block_heads(64, 32, 128, mosaic=True) == 64
    assert pk.ssm_update_block_heads(128, 64, 64, mosaic=True) is None
    assert pk.ssm_update_block_heads(128, 48, 128, mosaic=True) is None
    assert pk.ssm_update_heads_per_row(128, 64) == 2
    assert pk.ssm_update_heads_per_row(32, 128) == 1
    assert pk.ssm_update_heads_per_row(5, 64) == 1     # no whole rows
    assert pk.ssm_update_block_heads(4, 8, 16, mosaic=False) == 4
    assert pk.ssm_update_block_heads(3, 1024, 1024, mosaic=False) == 1
    ss, decay, dtx, Bm, Cm = _ssm_operands(1, 2, 4, 8, 16, 2)
    with pytest.raises(ValueError, match="ssm_update"):
        pk.ssm_update(ss, decay, dtx[:, :2], Bm, Cm, jnp.ones(2, bool), 0)
    with pytest.raises(ValueError, match="ssm_update"):
        pk.ssm_update(ss, decay, dtx, Bm[:, :1], Cm, jnp.ones(2, bool), 0)


def test_substrate_parity_smoke():
    """The ci_checks `kernels` gate body: one tileable pass per family
    against its oracle on the shared core — fast, no fixtures."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (
        decode_attention, decode_attention_reference, dequant_matmul,
        dequant_matmul_reference, flash_attention)
    from paddle_tpu.parallel.ring_attention import local_attention
    q, k, v = _qkv(1, 32, 2, 8, "float32", seed=9)
    assert float(jnp.abs(
        flash_attention(q, k, v, causal=True, block_q=8, block_kv=8)
        - local_attention(q, k, v, causal=True)).max()) < 2e-5
    for kv_dtype in ("float32", "int8"):
        dq, kc, vc, lengths, scales = _decode_operands(
            2, 32, 2, 8, kv_dtype)
        assert float(jnp.abs(
            decode_attention(dq, kc, vc, lengths, block_kv=8,
                             kv_scales=scales)
            - decode_attention_reference(dq, kc, vc, lengths,
                                         kv_scales=scales)).max()) \
            < 2e-5
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
    wq = jnp.asarray(rng.randint(-127, 128, (16, 32)).astype(np.int8))
    s = jnp.asarray(np.full(32, 0.02, np.float32))
    assert float(jnp.abs(
        dequant_matmul(x, wq, s, block_m=4, block_k=8, block_n=16)
        - dequant_matmul_reference(x, wq, s)).max()) < 1e-4


# ---------------------------------------------------------------------------
# tuned block-geometry entries resolve across every namespace
# ---------------------------------------------------------------------------


def test_tuned_entries_resolve_every_namespace(store):
    """The substrate consolidation must not orphan the tuning
    registry: a recorded winner in each namespace (flash, DEC_* fp32,
    DEC_* int8, dequant) still resolves at trace time."""
    from paddle_tpu.ops import attention_tuning as at
    cfg = at.AttentionConfig(16, 32, 8, 8)
    at.record(64, 16, True, "float32", cfg)
    assert at.get_config(64, 16, True, "float32") == cfg
    at.record_decode(32, 8, "float32", 16)
    assert at.get_decode_config(32, 8, "float32") == 16
    at.record_decode(32, 8, "int8", 32)
    assert at.get_decode_config(32, 8, "int8") == 32
    # the two cache dtypes tune independently (distinct key families)
    assert at.get_decode_config(32, 8, "float32") == 16
    at.record_dequant(8, 32, 16, "float32", 4, 16, 8)
    assert at.get_dequant_config(8, 32, 16, "float32") == (4, 16, 8)


@pytest.mark.slow
def test_tune_kernels_driver_smoke(tmp_path):
    """The unified autotuner sweeps all three families, records
    winners into the registry, and each resolves (`"resolves": true`
    rows + DEC_*_int8 keys present).  slow-marked subprocess (the
    PR 12 rule) — the ci_checks `kernels` gate still runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tune_kernels.py"),
         "--smoke", "--cache_dir", str(tmp_path / "reg")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [json.loads(ln) for ln in out.stdout.splitlines() if ln]
    tuned = [r for r in rows if r.get("metric") == "tuned"]
    assert {r["family"] for r in tuned} == {"flash", "decode",
                                            "dequant"}
    assert all(r["resolves"] for r in tuned)
    assert any(r.get("kv_dtype") == "int8" for r in tuned
               if r["family"] == "decode")


# ---------------------------------------------------------------------------
# int8 KV cache: the session-level contracts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm(tmp_path_factory):
    from paddle_tpu.inference.decode import build_tiny_decode_model
    d = str(tmp_path_factory.mktemp("kvlm") / "lm")
    build_tiny_decode_model(d, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=64)
    return d


_PRED_CACHE = {}


def _open(tiny_lm, kv):
    """Module-cached predictors: every phase compiles once per
    (artifact, cache dtype) across the whole file — tier-1 budget is
    tight (the compile, not the math, is the cost here)."""
    from paddle_tpu.inference.decode import GenerativePredictor
    key = (tiny_lm, kv)
    if key not in _PRED_CACHE:
        _PRED_CACHE[key] = GenerativePredictor(tiny_lm,
                                               kv_cache_dtype=kv)
    return _PRED_CACHE[key]


def test_kv_dtype_resolution_and_normalize(tiny_lm):
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             normalize_kv_dtype)
    assert normalize_kv_dtype(None) == "float32"
    assert normalize_kv_dtype("fp32") == "float32"
    assert normalize_kv_dtype("int8") == "int8"
    with pytest.raises(ValueError):
        normalize_kv_dtype("int4")
    # artifact default is fp32; the explicit knob wins; clones inherit
    assert GenerativePredictor(tiny_lm).kv_cache_dtype == "float32"
    q8 = _open(tiny_lm, "int8")
    assert q8.kv_cache_dtype == "int8"
    assert q8.clone_to(None).kv_cache_dtype == "int8"
    # the FLAGS default kicks in when nothing pins the dtype
    old = fluid.get_flags(["serving_kv_cache_dtype"])
    try:
        fluid.set_flags({"serving_kv_cache_dtype": "int8"})
        assert GenerativePredictor(tiny_lm).kv_cache_dtype == "int8"
    finally:
        fluid.set_flags(old)


def test_int8_cache_bytes_smoke(tiny_lm):
    """Static AND measured cache bytes <= 0.27x fp32 at equal slots
    (the acceptance bound), and the closed form matches the live
    session's arrays."""
    fp, q8 = _open(tiny_lm, "float32"), _open(tiny_lm, "int8")
    assert q8.kv_cache_bytes(8) <= 0.27 * fp.kv_cache_bytes(8)
    sf, s8 = fp.new_session(8), q8.new_session(8)
    assert s8.cache_bytes() <= 0.27 * sf.cache_bytes()
    assert s8.cache_bytes() == q8.kv_cache_bytes(8)
    assert sf.cache_bytes() == fp.kv_cache_bytes(8)


def test_int8_top1_agreement_and_bit_stability_smoke(tiny_lm):
    """fp32-vs-int8 greedy top-1 agreement >= 0.99 on the tiny decode
    fixture, and the int8 stream is bit-stable against itself."""
    from paddle_tpu.inference.decode import greedy_decode
    fp, q8 = _open(tiny_lm, "float32"), _open(tiny_lm, "int8")
    prompts = [[3, 5, 7], [9, 4], [1, 2, 3, 4, 5], [8], [6, 6, 2, 9],
               [12, 30], [21, 7, 14]]
    agree = total = 0
    for p in prompts:
        a, _ = greedy_decode(fp, p, 16)
        b, _ = greedy_decode(q8, p, 16)
        assert b == greedy_decode(q8, p, 16)[0], \
            "int8 stream not bit-stable against itself"
        m = 0
        for x, y in zip(a, b):
            if x != y:
                break
            m += 1
        agree += m
        total += max(len(a), len(b))
    assert agree / total >= 0.99, \
        "fp32-vs-int8 top-1 agreement %.3f < 0.99" % (agree / total)


def test_int8_slot_reuse_zero_leakage(tiny_lm):
    """A freed int8 slot holds exact int8 zeros and its next occupant
    streams bit-exactly vs a fresh single-slot session — the chaos
    decode-disconnect invariant under the quantized cache."""
    from paddle_tpu.inference.decode import greedy_decode
    q8 = _open(tiny_lm, "int8")
    sess = q8.new_session(2)
    # occupy, advance, free — then check exact zeros at the byte level
    sess.prefill(0, [3, 5, 7])
    sess.prefill(1, [4, 4])
    for _ in range(3):
        sess.decode()
    sess.free(0)
    assert sess.slot_is_zero(0)
    assert np.asarray(sess._kc).dtype == np.int8
    # reuse slot 0 while slot 1 keeps decoding; parity vs fresh session
    t0 = sess.prefill(0, [9, 4])
    out = [t0]
    while len(out) < 6:
        out.append(int(sess.decode()[0]))
    ref, _ = greedy_decode(q8, [9, 4], 6)
    assert out == ref


def test_int8_rollback_bit_identity(tiny_lm):
    """DecodeSession.rollback under the quantized cache: rolled-back
    slots are bit-identical to never-advanced ones (the spec-decode
    draft-sync primitive survives quantization)."""
    q8 = _open(tiny_lm, "int8")
    sess = q8.new_session(2)
    sess.prefill(0, [3, 5, 7])
    kc0 = np.asarray(sess._kc).copy()
    vc0 = np.asarray(sess._vc).copy()
    last0 = int(sess.last_tokens[0])
    sess.decode()
    sess.decode()
    sess.rollback(0, 2, last_token=last0)
    assert (np.asarray(sess._kc) == kc0).all()
    assert (np.asarray(sess._vc) == vc0).all()
    assert int(sess.lengths[0]) == 3


def test_int8_spec_twin_accept_rate_one(tiny_lm):
    """The spec-decode accept-rate probe: with target AND draft on the
    int8 cache (same artifact twin) every drafted token verifies —
    accept rate reads exactly 1.0, streams match target-only decode."""
    from paddle_tpu.inference.decode import (SpeculativeDecodeSession,
                                             greedy_decode)
    q8 = _open(tiny_lm, "int8")
    twin = _open(tiny_lm, "int8")
    sess = SpeculativeDecodeSession(q8, twin, 2, 3)
    sess.prefill(0, [3, 5, 7])
    sess.prefill(1, [9, 4])
    committed = {0: [], 1: []}
    for _ in range(4):
        toks, counts = sess.step()
        for slot in (0, 1):
            committed[slot] += list(toks[slot, :counts[slot]])
    assert not sess.degraded
    assert sess.proposed > 0 and sess.accepted == sess.proposed
    # the committed stream (after the prefill token) must be the plain
    # greedy continuation of the same prompt on the same cache dtype
    for slot, prompt in ((0, [3, 5, 7]), (1, [9, 4])):
        ref, _ = greedy_decode(q8, prompt, 32)
        n = min(len(committed[slot]), len(ref) - 1)
        assert n > 0 and committed[slot][:n] == ref[1:1 + n]


# ---------------------------------------------------------------------------
# static pricing + serving surfaces
# ---------------------------------------------------------------------------


def test_resources_price_kv_dtype(tiny_lm):
    """Satellite pin: the decode KV closed form prices the cache dtype
    — analyze_artifact statically reads ~0.25x KV bytes for an
    int8-cache load, exactly matching the predictor's accounting."""
    from paddle_tpu.analysis import analyze_artifact
    r_fp = analyze_artifact(tiny_lm, decode_slots=4)
    r_q8 = analyze_artifact(tiny_lm, decode_slots=4,
                            kv_cache_dtype="int8")
    # fp32: 2 * L * slots * S * H * Dh * 4; int8: /4 + scale table
    assert r_fp.kv_cache_bytes == 2 * 2 * 4 * 64 * 2 * 8 * 4
    assert r_q8.kv_cache_bytes == 2 * 2 * 4 * 64 * 2 * 8 + 2 * 2 * 2 * 4
    assert r_q8.kv_cache_bytes <= 0.27 * r_fp.kv_cache_bytes
    assert r_q8.peak_bytes < r_fp.peak_bytes
    # both closed forms agree with the predictor's own accounting
    assert _open(tiny_lm, "float32").kv_cache_bytes(4) \
        == r_fp.kv_cache_bytes
    assert _open(tiny_lm, "int8").kv_cache_bytes(4) \
        == r_q8.kv_cache_bytes
    # a decode_meta pin prices itself with no override
    from paddle_tpu.inference.decode import (build_tiny_decode_model,
                                             save_decode_model)
    from paddle_tpu.native import wire
    import tempfile
    d2 = os.path.join(tempfile.mkdtemp(), "lm8")
    build_tiny_decode_model(d2, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=64)
    with open(os.path.join(d2, "decode_meta.bin"), "rb") as f:
        meta = wire.decode(f.read())
    meta["kv_cache_dtype"] = "int8"
    with open(os.path.join(d2, "decode_meta.bin"), "wb") as f:
        f.write(wire.encode(meta))
    assert analyze_artifact(d2, decode_slots=4).kv_cache_bytes \
        == r_q8.kv_cache_bytes


def test_serving_int8_kv_end_to_end(tiny_lm, tmp_path):
    """The full wire: load_model(kv_cache_dtype='int8') -> reply +
    describe carry the dtype, stats carry measured cache bytes at
    ~0.25x, streams are bit-exact vs a direct int8 session, and the
    fp32 twin loaded beside it stays fp32 (no collision)."""
    from paddle_tpu.inference.decode import greedy_decode
    from paddle_tpu.serving import InferenceServer, ServingClient
    server = InferenceServer().start()
    cli = ServingClient(server.endpoint)
    try:
        loaded = cli.load_model("lm8", tiny_lm, decode_slots=2,
                                kv_cache_dtype="int8")
        assert loaded["kv_cache_dtype"] == "int8"
        loaded_fp = cli.load_model("lmfp", tiny_lm, decode_slots=2)
        assert loaded_fp["kv_cache_dtype"] == "float32"
        reply = cli.stats()
        assert reply["models"]["lm8"]["kv_cache_dtype"] == "int8"
        assert reply["models"]["lmfp"]["kv_cache_dtype"] == "float32"
        stats = reply["stats"]["models"]
        q8 = _open(tiny_lm, "int8")
        fp = _open(tiny_lm, "float32")
        assert stats["lm8"]["kv_cache_dtype"] == "int8"
        assert stats["lm8"]["kv_cache_bytes"] == q8.kv_cache_bytes(2)
        assert stats["lmfp"]["kv_cache_bytes"] == fp.kv_cache_bytes(2)
        assert stats["lm8"]["kv_cache_bytes"] \
            <= 0.27 * stats["lmfp"]["kv_cache_bytes"]
        # served int8 stream == direct int8 session, token for token
        got = [t for ch in cli.infer_stream("lm8", [3, 5, 7],
                                            max_new_tokens=8,
                                            deadline_ms=60000.0)
               for t in ch]
        ref, _ = greedy_decode(q8, [3, 5, 7], 8)
        assert got == ref
        with pytest.raises(Exception):
            cli.load_model("bad", tiny_lm, kv_cache_dtype="int4")
    finally:
        cli.close()
        server.shutdown(drain=False, timeout=10.0)


def test_int8_kv_phase_fingerprints_isolated(tiny_lm, store):
    """fp32 and int8 executables never collide in the persistent
    compile cache: the same artifact opened both ways produces
    disjoint fingerprints (kv_dtype is a fingerprint field)."""
    fp, q8 = _open(tiny_lm, "float32"), _open(tiny_lm, "int8")
    import jax
    L, H, Dh, _ = fp._dims()
    specs = (jax.ShapeDtypeStruct((1, 8), np.dtype(np.int32)),
             jax.ShapeDtypeStruct((), np.dtype(np.int32)))
    fp_a = fp._fingerprint(("prefill", 8), specs)
    fp_b = q8._fingerprint(("prefill", 8), specs)
    assert fp_a != fp_b and fp_a["kv_dtype"] == "float32" \
        and fp_b["kv_dtype"] == "int8"


@pytest.mark.slow
def test_chaos_decode_disconnect_int8_smoke():
    """The chaos scenario under the quantized cache, as a subprocess
    (the CI re-run satellite): freed slots zeroed, zero leakage.
    slow-marked (the PR 12 rule) — runs in the ci_checks `kernels`
    gate, which invokes pytest without -m."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "--scenario", "decode-disconnect-int8"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PASS decode-disconnect (kv=int8)" in out.stdout


@pytest.mark.slow
def test_bench_kv_dtype_ab_smoke():
    """bench_serving --decode --kv_dtype both: records carry the
    kv columns with the ratio and agreement bounds met."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bench_serving.py"),
         "--decode", "--decode_mode", "cb", "--kv_dtype", "both",
         "--decode_slots", "2", "--qps", "6", "--duration", "2",
         "--smoke"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    by_kv = {r.get("kv_cache_dtype"): r for r in rows
             if r.get("metric") == "serving_decode"}
    assert set(by_kv) == {"float32", "int8"}
    for r in by_kv.values():
        assert r["bit_exact"] is True
    q8 = by_kv["int8"]
    assert q8["kv_bytes_ratio_vs_fp32"] <= 0.27
    assert q8["kv_measured_ratio_vs_fp32"] <= 0.27
    assert q8["kv_top1_agreement"] >= 0.99
