"""Pallas TPU kernels for the ops XLA's fusion won't schedule optimally.

No direct reference analogue — the reference's hand-written CUDA kernels
(paddle/legacy/cuda, operators/math/*.cu) fill this role; on TPU the op set
that merits hand kernels is much smaller because XLA fuses elementwise
chains into matmuls. Flash attention is the headline case: the [S, S] score
matrix never leaves VMEM, with online-softmax accumulation over K/V blocks
(see /opt/skills/guides/pallas_guide.md).

Every contraction family here — flash attention fwd/bwd, decode
attention, fused dequant-matmul — instantiates ONE tiled-contraction
driver (`tiled_contraction`, the Tensor Processing Primitives shape,
PAPERS.md): the driver owns the grid/BlockSpec plumbing, the streamed
operand staging, fp32 accumulator init on the first reduction tile and
finalize on the last, compiler dimension semantics, and the
interpret-vs-Mosaic dispatch; a family plugs in a small epilogue pair
(`tile`/`finalize`) — online softmax for flash fwd + decode, transposed-
stationarity gradient folds for flash bwd, in-register dequant with a
per-channel (or per-head, for the int8 KV cache) scale at finalize for
the quantized families.  Block geometry resolves per shape at trace time
through ops/attention_tuning.py (FLAGS override > tuning registry >
heuristic); `tools/tune_kernels.py` sweeps and writes every namespace.

The kernels run in interpret mode off-TPU so the same code paths are unit
tested on the CPU mesh; `interpret=None` defers the choice to lowering
time so cross-platform exports embed the real Mosaic modules for tpu.
"""

import contextlib
import functools
import threading

import numpy as np

from . import attention_tuning

__all__ = ["tiled_contraction", "flash_attention", "decode_attention",
           "kv_last_block",
           "decode_attention_reference",
           "latent_decode_attention", "latent_decode_attention_reference",
           "decode_attention_head_slice", "lowering_for_tpu",
           "fused_bottleneck",
           "bottleneck_reference", "dequant_matmul",
           "dequant_matmul_reference", "mosaic_lowering"]

# Finite mask value (not -inf): exp(_NEG_INF - finite) underflows to an
# exact 0, and the logsumexp of a fully-masked row stays finite, so the
# ring-hop merge (parallel/ring_attention.py) never sees inf - inf.
_NEG_INF = -1e30
_TINY = 1e-20
_MIN_LANES = attention_tuning.MIN_LANES


_DISPATCH = threading.local()


@contextlib.contextmanager
def mosaic_lowering(enable=True):
    """Force the interpret-vs-Mosaic choice for ``interpret=None`` call
    sites in this thread. functionalizer.export_step_for_tpu enters this
    while tracing, so off-chip TPU exports from a CPU-only host embed the
    real Mosaic kernels."""
    prev = getattr(_DISPATCH, "force_kernel", None)
    _DISPATCH.force_kernel = bool(enable)
    try:
        yield
    finally:
        _DISPATCH.force_kernel = prev


def lowering_for_tpu():
    """Whether the trace in hand lowers for a TPU: this thread's
    `mosaic_lowering` choice where one is made, the default backend
    otherwise.  What `interpret=None` resolves by, and what code with a
    TPU form of its own asks (`inference/decode.py::_contract`)."""
    import jax
    force = getattr(_DISPATCH, "force_kernel", None)
    return (jax.default_backend() == "tpu") if force is None else force


def _interpret_dispatch(call, interpret, *ops):
    """Kernel-vs-interpret dispatch shared by every Pallas entry point:
    an explicit `interpret` wins; None resolves at TRACE time — the real
    kernel when the trace targets TPU (tpu backend, or inside a
    mosaic_lowering() export context), interpret emulation elsewhere.

    This jax's lax.platform_dependent cannot serve here: it stages the
    dead Mosaic branch into single-platform CPU jits, whose pallas
    lowering rejects interpret=False outright."""
    if interpret is None:
        interpret = not lowering_for_tpu()
    return call(interpret, *ops)


def _causal_tile_live(iq, ik, block_q, block_kv):
    """A (q-tile, kv-tile) pair intersects the causal lower triangle iff
    the tile's first k row is <= its last q row."""
    return ik * block_kv <= (iq + 1) * block_q - 1


def _causal_tile_mask(s, iq, ik, block_q, block_kv):
    import jax
    import jax.numpy as jnp
    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kpos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    return jnp.where(kpos > qpos, _NEG_INF, s)


# ---------------------------------------------------------------------------
# tiled-contraction substrate (the TPP refactor, PAPERS.md / ROOFLINE.md
# "Kernel substrate"): one parameterized driver owns everything the
# kernel families used to hand-copy — grid/BlockSpec plumbing, streamed
# operand staging, accumulator init on the first reduction tile,
# finalize on the last, compiler dimension semantics, interpret
# dispatch.  A family is a `tile`/`finalize` epilogue pair plugged into
# the driver; the shared epilogue helpers below (online softmax,
# softmax finalize, in-register dequant staging) are the reusable
# pieces those pairs compose from.
# ---------------------------------------------------------------------------


class _TileCtx(object):
    """What one grid step of a tiled contraction sees: the staged
    operand refs, the output refs, the accumulator scratch refs, the
    scalar-prefetch refs (`scalars`, whole in SMEM) and the grid
    coordinates (`ids`; `reduce_id`/`n_reduce` index the streamed
    reduction axis)."""

    __slots__ = ("ins", "outs", "scratch", "scalars", "ids", "reduce_id",
                 "n_reduce")

    def __init__(self, ins, outs, scratch, scalars, ids, reduce_id,
                 n_reduce):
        self.ins = ins
        self.outs = outs
        self.scratch = scratch
        self.scalars = scalars
        self.ids = ids
        self.reduce_id = reduce_id
        self.n_reduce = n_reduce


def tiled_contraction(operands, *, grid, reduce_axis, in_specs,
                      out_specs, out_shape, scratch=(), scratch_fill=(),
                      tile=None, finalize=None, tile_live=None,
                      scalar_prefetch=(), interpret=None):
    """THE tiled-contraction core every kernel family instantiates.

    `grid` runs with "parallel" semantics on every axis except
    `reduce_axis` (the streamed axis, "arbitrary"): whatever operand
    re-stages along that axis streams through the pipeline while the
    rest stay resident — the staging IS the BlockSpec index map.  Each
    scratch buffer resets to its `scratch_fill` value on the first
    reduction tile and `finalize(ctx)` writes the outputs from the
    accumulators on the last (normalization, per-channel dequant
    scales, and dtype casts live there).  `tile(ctx)` folds one
    reduction tile into the accumulators; `tile_live(ids, *scalars)`
    optionally gates dead tiles out of the compute.  Under a plain
    index map (flash attention's causal upper triangle) a dead tile's
    DMA is already in flight and the compute is what is saved.

    `scalar_prefetch` operands (small int32 arrays) are in SMEM before
    the grid starts: every index map takes their refs after the grid
    coordinates, `tile_live` after `ids`, and the body finds them as
    `ctx.scalars`.  That is what lets a family bound its STREAM by a
    runtime value: an index map that repeats the block it staged last
    makes the pipeline issue no copy (it re-stages an operand only when
    its block index changes), so a tile that is dead by `tile_live` AND
    repeats its predecessor's index costs the grid step's overhead and
    nothing else (decode attention: a slot's K/V blocks past its
    length).  With none given the call is the plain grid it always was.
    `interpret=None` resolves interpret-vs-Mosaic at trace time
    (_interpret_dispatch), like every kernel here always has."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_in = len(operands)
    n_out = len(out_shape) if isinstance(out_shape, (list, tuple)) else 1
    n_pre = len(scalar_prefetch)
    fills = tuple(scratch_fill) + (0.0,) * (len(scratch)
                                            - len(scratch_fill))

    def kern(*refs):
        scalars, refs = refs[:n_pre], refs[n_pre:]
        ids = tuple(pl.program_id(i) for i in range(len(grid)))
        ctx = _TileCtx(refs[:n_in], refs[n_in:n_in + n_out],
                       refs[n_in + n_out:], scalars, ids,
                       ids[reduce_axis], pl.num_programs(reduce_axis))

        if ctx.scratch:
            @pl.when(ctx.reduce_id == 0)
            def _init():
                for ref, fill in zip(ctx.scratch, fills):
                    ref[...] = jnp.full_like(ref, fill)

        if tile_live is not None:
            @pl.when(tile_live(ids, *scalars))
            def _tile():
                tile(ctx)
        else:
            tile(ctx)

        @pl.when(ctx.reduce_id == ctx.n_reduce - 1)
        def _finalize():
            finalize(ctx)

    sem = tuple("arbitrary" if i == reduce_axis else "parallel"
                for i in range(len(grid)))

    plumbing = dict(grid=grid, in_specs=list(in_specs),
                    out_specs=out_specs, scratch_shapes=list(scratch))
    if n_pre:
        plumbing = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, **plumbing))

    def call(interp, *ops):
        return pl.pallas_call(
            kern, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=sem),
            interpret=interp, **plumbing,
        )(*ops)

    return _interpret_dispatch(call, interpret, *scalar_prefetch,
                               *operands)


def _online_softmax_tile(s, pv_of, acc_ref, m_ref, l_ref):
    """Online-softmax epilogue shared by flash forward and decode
    attention: fold one masked f32 score tile `s` [R, BKV] into the
    running row max / normalizer / accumulator, rescaling prior
    contributions by alpha.  `pv_of(p)` contracts the tile
    probabilities against the resident value tile — an MXU matmul in
    every family."""
    import jax.numpy as jnp
    m_prev = m_ref[...]                            # [R, LANES]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])                  # [R, BKV] f32
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)[:, None]
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv_of(p)
    m_ref[...] = m_new


def _softmax_finalize(acc_ref, m_ref, l_ref):
    """Normalize a finished online-softmax accumulator; returns
    (o_f32, lse) for the caller to cast/write — any constant per-row
    scale (the int8 KV epilogue's per-head V scale) folds in after the
    divide, once per output element."""
    import jax.numpy as jnp
    l = jnp.maximum(l_ref[:, :1], _TINY)
    return acc_ref[...] / l, m_ref[:, :1] + jnp.log(l)


def _stage_dequant(w, dtype):
    """In-register dequant staging (QUANTIZE.md; TPP's fused
    dequant-contraction shape): an int8 tile streamed from HBM is cast
    to the compute dtype the moment it lands in VMEM — float weights /
    KV rows never exist in HBM.  Symmetric per-channel (or per-head)
    scales distribute over the reduction, so they apply ONCE at
    finalize, never per streamed element."""
    return w.astype(dtype)


def _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_kv,
                      interpret):
    """q,k,v [BH, S, D] -> (o [BH, S, D], lse [BH, S] f32): the
    online-softmax instantiation — Q and the (acc, m, l) state resident
    per (bh, q-block) output tile, K/V tiles streamed on the reduction
    axis."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape

    def tile(ctx):
        q_ref, k_ref, v_ref = ctx.ins
        acc_ref, m_ref, l_ref = ctx.scratch
        qb = q_ref[0]                                  # [BQ, D]
        kb = k_ref[0]                                  # [BKV, D]
        vb = v_ref[0]
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_tile_mask(s, ctx.ids[1], ctx.ids[2], block_q,
                                  block_kv)
        _online_softmax_tile(
            s,
            lambda p: jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32),
            acc_ref, m_ref, l_ref)

    def finalize(ctx):
        o_ref, lse_ref = ctx.outs
        acc_ref, m_ref, l_ref = ctx.scratch
        o, lse = _softmax_finalize(acc_ref, m_ref, l_ref)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0] = lse

    live = None
    if causal:
        live = lambda ids: _causal_tile_live(  # noqa: E731
            ids[1], ids[2], block_q, block_kv)
    o, lse = tiled_contraction(
        (q, k, v),
        grid=(BH, S // block_q, S // block_kv),
        reduce_axis=2,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch=[pltpu.VMEM((block_q, D), jnp.float32),
                 pltpu.VMEM((block_q, _MIN_LANES), jnp.float32),
                 pltpu.VMEM((block_q, _MIN_LANES), jnp.float32)],
        scratch_fill=(0.0, _NEG_INF, 0.0),
        tile=tile, finalize=finalize, tile_live=live,
        interpret=interpret)
    return o, lse[..., 0]


def _flash_bwd_pallas(q, k, v, do, lse, di, scale, causal, block_q,
                      block_kv, interpret):
    """Fused backward: two instantiations with transposed stationarity
    (the dq pass streams K/V under resident q/do rows; the dkv pass
    streams q/do rows under a resident K/V block, so neither gradient
    needs a cross-program reduction).  di = rowsum(do * o) - dlse (the
    dlse term folds the lse output's cotangent into the same ds
    formula: d lse_i / d s_ij = p_ij)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    nq, nk = S // block_q, S // block_kv
    lse = lse[..., None]
    di = di[..., None]

    def dq_tile(ctx):
        q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref = ctx.ins
        (acc_ref,) = ctx.scratch
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        dob = do_ref[0]
        lseb = lse_ref[0]                              # [BQ, 1]
        dib = di_ref[0]
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_tile_mask(s, ctx.ids[1], ctx.ids[2], block_q,
                                  block_kv)
        p = jnp.exp(s - lseb)                          # [BQ, BKV] f32
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - dib) * scale).astype(kb.dtype)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def dq_finalize(ctx):
        dq_ref = ctx.outs[0]
        dq_ref[0] = ctx.scratch[0][...].astype(dq_ref.dtype)

    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kvspec = pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0))
    live = None
    if causal:
        live = lambda ids: _causal_tile_live(  # noqa: E731
            ids[1], ids[2], block_q, block_kv)
    dq = tiled_contraction(
        (q, k, v, do, lse, di),
        grid=(BH, nq, nk),
        reduce_axis=2,
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch=[pltpu.VMEM((block_q, D), jnp.float32)],
        tile=dq_tile, finalize=dq_finalize, tile_live=live,
        interpret=interpret)

    # kv-stationary twin: grid axis 1 walks KV blocks, the reduction
    # axis streams Q/dO/lse/di row tiles under the resident K/V block
    def dkv_tile(ctx):
        q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref = ctx.ins
        dk_acc, dv_acc = ctx.scratch
        qb = q_ref[0]
        dob = do_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        lseb = lse_ref[0]
        dib = di_ref[0]
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_tile_mask(s, ctx.ids[2], ctx.ids[1], block_q,
                                  block_kv)
        p = jnp.exp(s - lseb)                          # [BQ, BKV] f32
        pv = p.astype(dob.dtype)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            pv, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - dib) * scale).astype(qb.dtype)
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def dkv_finalize(ctx):
        dk_ref, dv_ref = ctx.outs
        dk_acc, dv_acc = ctx.scratch
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    qspec_t = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    rowspec_t = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    kvspec_t = pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0))
    live_t = None
    if causal:
        live_t = lambda ids: _causal_tile_live(  # noqa: E731
            ids[2], ids[1], block_q, block_kv)
    dk, dv = tiled_contraction(
        (q, do, lse, di, k, v),
        grid=(BH, nk, nq),
        reduce_axis=2,
        in_specs=[qspec_t, qspec_t, rowspec_t, rowspec_t, kvspec_t,
                  kvspec_t],
        out_specs=[kvspec_t, kvspec_t],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        scratch=[pltpu.VMEM((block_kv, D), jnp.float32),
                 pltpu.VMEM((block_kv, D), jnp.float32)],
        tile=dkv_tile, finalize=dkv_finalize, tile_live=live_t,
        interpret=interpret)
    return dq, dk, dv


def _reference_lse(q, k, scale, causal):
    """Plain-XLA row logsumexp for the non-tileable fallback path (same
    finite-mask convention as the kernels)."""
    import jax.numpy as jnp
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(Sk)[None, :] > jnp.arange(Sq)[:, None]
        s = jnp.where(mask[None, :, None, :], _NEG_INF, s)
    m = jnp.max(s, axis=-1)
    return m + jnp.log(jnp.maximum(
        jnp.sum(jnp.exp(s - m[..., None]), axis=-1), _TINY))


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_kv=None, block_q_bwd=None, block_kv_bwd=None,
                    interpret=None, return_lse=False, block_k=None):
    """Fused attention: q,k,v [B, S, H, D] -> [B, S, H, D]
    (or (out, lse [B, S, H] f32) with return_lse — the residual the
    ring-attention hop merge consumes).

    Pallas kernel pair on TPU (interpret-mode elsewhere): a tiled
    forward emitting the row logsumexp, and a fused backward (dq +
    dkv kernels) via custom VJP. Block geometry defaults per shape
    through ops/attention_tuning.py (FLAGS override > tune cache >
    MXU-aligned heuristic); explicit block args win over all of it.
    Falls back to plain attention when no geometry divides S.
    `block_k` is the pre-tuning alias of `block_kv`."""
    import jax
    import jax.numpy as jnp

    B, S, H, D = q.shape
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))
    block_kv = block_kv or block_k
    cfg = attention_tuning.get_config(S, D, causal,
                                      jnp.dtype(q.dtype).name)
    bq = int(block_q or (cfg.block_q if cfg else 0))
    bkv = int(block_kv or (cfg.block_kv if cfg else 0))
    bq_b = int(block_q_bwd or (cfg.block_q_bwd if cfg else 0)) or bq
    bkv_b = int(block_kv_bwd or (cfg.block_kv_bwd if cfg else 0)) or bkv
    if (not bq or not bkv or S % bq or S % bkv or S % bq_b or S % bkv_b):
        from ..parallel.ring_attention import local_attention
        out = local_attention(q, k, v, causal=causal, scale=scale)
        if return_lse:
            return out, _reference_lse(q, k, scale, causal)
        return out
    # interpret=None defers the interpret-vs-Mosaic choice to LOWERING
    # time (_interpret_dispatch platform_dependent), so cross-platform
    # exports embed the real kernels for tpu

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    def from_bh(x):
        return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    @jax.custom_vjp
    def _fa(qb, kb, vb):
        return _flash_fwd_pallas(qb, kb, vb, scale, causal, bq, bkv,
                                 interpret)

    def _fa_fwd(qb, kb, vb):
        o, lse = _flash_fwd_pallas(qb, kb, vb, scale, causal, bq, bkv,
                                   interpret)
        return (o, lse), (qb, kb, vb, o, lse)

    def _fa_bwd(res, cts):
        qb, kb, vb, o, lse = res
        do, dlse = cts
        di = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                      axis=-1)
              - dlse.astype(jnp.float32))              # [BH, S]
        return _flash_bwd_pallas(qb, kb, vb, do.astype(qb.dtype), lse,
                                 di, scale, causal, bq_b, bkv_b,
                                 interpret)

    _fa.defvjp(_fa_fwd, _fa_bwd)

    o, lse = _fa(to_bh(q), to_bh(k), to_bh(v))
    if return_lse:
        return from_bh(o), lse.reshape(B, H, S).transpose(0, 2, 1)
    return from_bh(o)


# ---------------------------------------------------------------------------
# decode attention: the serving-side kernel (SERVING.md continuous
# batching). One new query token per KV-cache slot attends over that
# slot's cached prefix — the memory-roofline-bound shape ROOFLINE.md
# names for generation: ~zero FLOP reuse, the win is streaming the K/V
# slot cache through VMEM exactly once per step. The instantiation is
# q-stationary per slot (all heads resident) with kv-cache blocks
# streamed on the reduction axis under the shared online-softmax
# epilogue; positions at or past the slot's live length are masked with
# the same finite _NEG_INF convention as the training kernels.
#
# The int8 KV-cache variant (QUANTIZE.md "Quantized KV cache") streams
# the SAME tiles at one byte per element: `kv_scales` carries the
# per-head symmetric fp32 scales of the quantized cache, int8 tiles
# dequantize in-register via _stage_dequant, the K scale folds into the
# per-head score scale and the V scale applies once at finalize — a 4x
# cut of the byte stream that bounds decode (ROOFLINE.md), same kernel
# skeleton.  Block geometry resolves through the shared kernel-tuning
# registry keyed by the CACHE dtype (attention_tuning.get_decode_config
# — FLAGS override > tuned entry > MXU-aligned heuristic), so int8 and
# fp32 caches tune independently (DEC_*_int8 vs DEC_*_float32 keys).
# ---------------------------------------------------------------------------


def kv_last_block(lengths, block_kv, n_blocks, xp=np):
    """THE rule that bounds `decode_attention`'s K/V stream: the index
    of the last block of `block_kv` positions that holds a live position
    of a slot of `lengths` positions, max(ceil(lengths / block_kv), 1) - 1
    and at most `n_blocks - 1`.  Grid step j of the slot stages block
    min(j, last) and computes iff j <= last, so the slot's stream is
    last + 1 blocks: one for a slot of length 0 (its first, all masked),
    all `n_blocks` for a slot at (or, as an idle slot under `lengths + 1`
    can be, past) the table's end.  Elementwise; `xp` is numpy for the
    host's count of what a dispatch streamed (`DecodeSession`'s
    `kv_blocks_live`), jax.numpy where `decode_attention` works it out
    for its index maps and tile gate: one function for both."""
    return xp.clip((lengths + (block_kv - 1)) // block_kv, 1, n_blocks) - 1


def decode_attention_reference(q, k_cache, v_cache, lengths, scale=None,
                               kv_scales=None):
    """Plain-XLA oracle/fallback with identical masking semantics:
    q [N, H, D] one new token per slot, k/v caches [N, S, Hc * D] as a
    slot table holds them (a position one flat row, its Hc heads' D
    features side by side), lengths [N] live cached positions per slot
    -> [N, H, D].
    `kv_scales` [2, H] f32 (required iff the caches are int8) applies
    the same per-head dequant algebra as the kernel: K scale on the
    scores, V scale after the normalizing divide.  Caches of fewer heads
    than q's are grouped-query: query head a reads K/V head a // (H /
    cache heads), spelled here as a repeat of the K/V heads."""
    import jax.numpy as jnp
    N, S = k_cache.shape[0], k_cache.shape[1]
    H, D = q.shape[1], q.shape[-1]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))
    k_cache, v_cache = (t.reshape(N, S, -1, D) for t in (k_cache, v_cache))
    if k_cache.shape[2] != H:
        k_cache, v_cache = (jnp.repeat(t, H // t.shape[2], axis=2)
                            for t in (k_cache, v_cache))
    s = jnp.einsum("nhd,nshd->nhs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    if kv_scales is not None:
        sc = jnp.asarray(kv_scales, jnp.float32).reshape(2, H)
        s = s * sc[0][None, :, None]
    mask = jnp.arange(S)[None, None, :] >= \
        jnp.asarray(lengths).astype(jnp.int32)[:, None, None]
    s = jnp.where(mask, _NEG_INF, s)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.maximum(jnp.sum(p, axis=-1), _TINY)
    o = jnp.einsum("nhs,nshd->nhd", p,
                   v_cache.astype(jnp.float32)) / l[..., None]
    if kv_scales is not None:
        o = o * sc[1][None, :, None]
    return o.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, scale=None,
                     block_kv=None, interpret=None, kv_scales=None,
                     layer=None):
    """Slot-cache decode attention: q [N, H, D] (the one new token of
    each of N slots), k_cache/v_cache [N, S, Hc * D] (the slot table's
    cached keys/values, time-major, a position ONE FLAT ROW: its Hc K/V
    heads' D features side by side; fp32, bf16 or int8), lengths [N]
    int32 (live positions per slot — cached positions >= length are
    masked out) -> [N, H, D] in q's dtype.

    WHY FLAT ROWS.  A Mosaic operand is row-major with its last two axes
    in (8, 128) tiles.  With (Hc, D) last, GPT-2 small's (12, 64) is
    held and streamed as (16, 128), 2.67x its bytes; with (S, Hc * D)
    last, positions lie on the sublanes and 768 lanes are six full
    tiles: nothing is padded, for any head size whose Hc * D is a
    multiple of 128 (every stack served), and the table at rest is the
    kernel's operand (`inference/decode.py::slot_state_shapes`).

    THE BODY contracts a [block_kv, Hc * D] tile per head without taking
    the heads apart: the slot's queries go in BLOCK-DIAGONAL, [H, Hc * D]
    with head a's D values on the lanes of its K/V head and zeros
    elsewhere, so scores [H, block_kv] = q_bd tile^T and values [H, Hc *
    D] += p tile are two MXU contractions (as `latent_decode_attention`
    contracts its flat row), and head a's result is the lanes of its K/V
    head in row a, folded out after the call.  Both run at
    Precision.HIGHEST: fp32 products and fp32 sums, as the configuration
    states of its cache and as the VPU computed them when each head had
    a tile of its own (on the chip the two bodies differ from an fp32
    reference alike, by under 1e-6: PERF.md, PR 41); a zero lane adds an
    exact zero.  The MXU does H times the multiplications that count and
    is still far from its peak.

    GROUPED-QUERY: caches of Hc < H heads (H = G * Hc), query head a
    reading K/V head a // G: its row of the block diagonal lies on that
    head's lanes, so the one resident tile serves all G query heads of a
    K/V head in the same two contractions.  Float caches only.

    With `layer` (a static int) k_cache/v_cache are the STACKED slot
    table [L, N, S, Hc * D] and the kernel reaches that layer through its
    BlockSpec index maps, (layer, b, block, 0): no slice of the table
    is materialised for the custom call, so a decode step that carries the
    table and updates it in place keeps ONE buffer of it
    (`inference/decode.py::_step_core`).  Body, block geometry and
    arithmetic are those of the single-layer form, which stays for
    callers that hold one layer.

    With int8 caches, `kv_scales` [2, H] f32 (k-scales row 0, v-scales
    row 1 — the per-(layer,head) scales of the quantized slot table,
    sliced per layer by the decode step) is required: tiles dequantize
    in-register, float KV never materializes in HBM.

    Pallas instantiation of the tiled-contraction core on TPU
    (interpret emulation elsewhere) streaming kv-cache blocks under
    resident per-slot queries; block geometry via
    attention_tuning.get_decode_config keyed by the CACHE dtype
    (FLAGS.flash_block_kv override > kernel-tuning registry >
    heuristic). Falls back to the plain-XLA composition when no block
    edge divides the cache length.

    THE STREAM IS BOUNDED BY `lengths`: the vector and each slot's last
    live block, last(b) = `kv_last_block(lengths[b], block_kv, S /
    block_kv)` (worked out once a call, outside the grid), are
    scalar-prefetch operands; slot b's K and V index maps stage block
    min(j, last(b)) and the body runs under j <= last(b).  A block past
    a slot's length
    is neither copied from HBM (the pipeline copies only when
    the block index changes) nor computed: a slot costs ceil(length /
    block_kv) blocks and the loop overhead of the rest of its grid row,
    and a slot at full length what it always cost.  Such a
    block added exp(_NEG_INF - m) = 0 to the sums when it was streamed,
    so for every slot of length >= 1 the result is that of a whole-row
    stream; what the table holds past a slot's last live block is never
    read.  A slot with length 0 has no live position: its first block
    is staged, every position of it masked, and the result is
    well-defined garbage (finite: the mean of that block's V rows) that
    disturbs no other slot — the decode step gates dead slots out
    downstream."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, D = q.shape
    stacked = layer is not None
    if k_cache.ndim != 3 + stacked or k_cache.shape[-3] != N \
            or k_cache.shape[-1] % D:
        raise ValueError(
            "decode_attention: a stacked table [L, N, S, Hc * D] goes "
            "with a static `layer`, a single layer [N, S, Hc * D] "
            "without one, N and D the queries' %d and %d (got caches %s, "
            "layer=%r)" % (N, D, tuple(k_cache.shape), layer))
    S, W = k_cache.shape[-2:]
    Hc = W // D
    G = H // Hc
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))
    kv_dtype = jnp.dtype(k_cache.dtype)
    quant = kv_dtype == jnp.dtype(jnp.int8)
    if quant and kv_scales is None:
        raise ValueError(
            "decode_attention: int8 KV caches need kv_scales [2, H] "
            "(per-head fp32 dequant scales)")
    if G * Hc != H or (quant and G > 1):
        raise ValueError(
            "decode_attention: %d query heads over %d %s K/V heads"
            % (H, Hc, kv_dtype.name))
    bkv = int(block_kv or attention_tuning.get_decode_config(
        S, D, kv_dtype.name) or 0)
    if not bkv or S % bkv:
        if stacked:
            k_cache, v_cache = k_cache[layer], v_cache[layer]
        return decode_attention_reference(q, k_cache, v_cache, lengths,
                                          scale=scale,
                                          kv_scales=kv_scales)
    lengths = jnp.asarray(lengths).astype(jnp.int32).reshape(N)
    n_blocks = S // bkv
    # slot b's stream stops at its last live block: past it the index
    # map repeats that block, which the pipeline holds already and does
    # not copy again, and `tile_live` keeps the body off it.  The rule
    # runs once a call, outside the grid: a grid step only compares.
    last = kv_last_block(lengths, bkv, n_blocks, xp=jnp)
    if stacked:
        # the layer's axis is squeezed out of the block: the body sees
        # the (1, bkv, Hc * D) tile of the single-layer form
        layer = int(layer)
        kv_spec = pl.BlockSpec(
            (None, 1, bkv, W), lambda b, j, len_ref, last_ref: (
                layer, b, jnp.minimum(j, last_ref[b]), 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, bkv, W), lambda b, j, len_ref, last_ref: (
                b, jnp.minimum(j, last_ref[b]), 0))
    # own[a, c]: lane c of a row belongs to the K/V head query head a reads
    own = (jnp.arange(W)[None, :] // D) == (jnp.arange(H)[:, None] // G)
    q_bd = jnp.where(own, jnp.tile(q, (1, 1, Hc)), 0)     # [N, H, W]
    contract = functools.partial(
        jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    def tile(ctx):
        q_ref, k_ref, v_ref = ctx.ins[:3]
        len_ref = ctx.scalars[0]
        acc_ref, m_ref, l_ref = ctx.scratch
        kb = _stage_dequant(k_ref[0], jnp.float32)     # [BKV, W]
        vb = _stage_dequant(v_ref[0], jnp.float32)
        s = contract(q_ref[0].astype(jnp.float32), kb,
                     (((1,), (1,)), ((), ()))) * scale  # [H, BKV]
        if quant:
            # per-head K scale folds into the score scale, once per
            # score element — never per streamed cache element
            s = s * ctx.ins[3][0]                  # [H, 1] broadcast
        kpos = ctx.reduce_id * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (H, bkv), 1)
        s = jnp.where(kpos >= len_ref[ctx.ids[0]], _NEG_INF, s)
        _online_softmax_tile(
            s, lambda p: contract(p, vb, (((1,), (0,)), ((), ()))),
            acc_ref, m_ref, l_ref)

    def finalize(ctx):
        o_ref = ctx.outs[0]
        acc_ref, m_ref, l_ref = ctx.scratch
        o, _ = _softmax_finalize(acc_ref, m_ref, l_ref)
        if quant:
            o = o * ctx.ins[3][1]                  # per-head V scale
        o_ref[0] = o.astype(o_ref.dtype)

    operands = [q_bd, k_cache, v_cache]
    q_spec = pl.BlockSpec((1, H, W), lambda b, j, *_: (b, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    if quant:
        operands.append(jnp.asarray(kv_scales, jnp.float32).reshape(
            2, H, 1))
        in_specs.append(pl.BlockSpec((2, H, 1),
                                     lambda b, j, *_: (0, 0, 0)))
    out = tiled_contraction(
        tuple(operands),
        grid=(N, n_blocks),
        reduce_axis=1,
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, W), q.dtype),
        scratch=[pltpu.VMEM((H, W), jnp.float32),
                 pltpu.VMEM((H, _MIN_LANES), jnp.float32),
                 pltpu.VMEM((H, _MIN_LANES), jnp.float32)],
        scratch_fill=(0.0, _NEG_INF, 0.0),
        tile=tile, finalize=finalize,
        tile_live=lambda ids, len_ref, last_ref:
            ids[1] <= last_ref[ids[0]],
        # two [N] vectors in scalar memory before the grid starts, where
        # the index maps can read them: the mask's and the stream's
        scalar_prefetch=(lengths, last),
        interpret=interpret)
    # row a holds head a's result on the lanes of its K/V head, and on
    # the others what it would be against their values: take its own
    return jnp.sum(jnp.where(own.reshape(H, Hc, D),
                             out.reshape(N, H, Hc, D), 0), axis=2)


def latent_decode_attention_reference(q, table, lengths, value_lanes,
                                      scale):
    """Plain-XLA oracle/fallback of `latent_decode_attention`: q [N, H, R]
    (each head's absorbed query), table [N, S, R] (ONE latent row a cached
    position, shared by all H heads; its first `value_lanes` lanes are the
    values too), lengths [N] -> [N, H, value_lanes]: softmax over the live
    positions of q . row * scale, then the weighted sum of the rows' value
    lanes.  Masking as `decode_attention_reference`."""
    import jax.numpy as jnp
    S = table.shape[1]
    rows = table.astype(jnp.float32)
    s = jnp.einsum("nhr,nsr->nhs", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(S)[None, None, :] >= \
        jnp.asarray(lengths).astype(jnp.int32)[:, None, None]
    s = jnp.where(mask, _NEG_INF, s)
    p = jnp.exp(s - jnp.max(s, axis=-1)[..., None])
    l = jnp.maximum(jnp.sum(p, axis=-1), _TINY)
    o = jnp.einsum("nhs,nsv->nhv", p, rows[..., :value_lanes])
    return (o / l[..., None]).astype(q.dtype)


def latent_decode_attention(q, table, lengths, value_lanes, scale,
                            block_kv=None, interpret=None, layer=None):
    """Decode attention over LATENT rows (multi-head latent attention with
    the up-projections absorbed into the query and the output): q [N, H,
    R], one new token a slot, every head's query already in the rows' own
    space; table [N, S, R] float32, ONE row a cached position which all H
    heads read (a group of H on one K/V "head" whose V is the first
    `value_lanes` lanes of its K); lengths [N] i32 -> [N, H, value_lanes].
    With a static `layer`, table is the stacked slot table [L, N, S, R]
    and the kernel reaches the layer through its index map
    (`decode_attention` says why).

    `decode_attention`'s stream: grid (slot, block of `block_kv`
    positions), a slot's blocks past its length neither copied nor
    computed (`kv_last_block`, the same scalar-prefetch pair), online
    softmax over the blocks.  What differs is the body.  There a head
    meets its own row and the products are formed on the VPU; here H
    queries meet ONE row, 2 * (R + value_lanes) * H FLOP a position (278
    kFLOP at 128 heads of 576 lanes over 512 values), so a block is
    staged once and both contractions run on the MXU: scores [H, block] =
    q rows^T, values [H, value_lanes] += p rows[:, :value_lanes].  On the
    TPU their operands are rounded to bfloat16 with float32 accumulation,
    which is what the default precision does to every other matmul of a
    decode phase (a float32 Mosaic matmul is several passes, and would
    make this kernel compute-bound at 128 heads); in interpret mode they
    stay float32, as a float32 matmul off the TPU does.  Falls back to the
    reference when no block edge divides S.  A slot of length 0: as in
    `decode_attention`."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, R = q.shape
    V = int(value_lanes)
    stacked = layer is not None
    if stacked != (table.ndim == 4) or table.shape[-1] != R:
        raise ValueError(
            "latent_decode_attention: a stacked table [L, N, S, R] goes "
            "with a static `layer`, a single layer [N, S, R] without one, "
            "R the queries' %d lanes (got a table %s, layer=%r)"
            % (R, tuple(table.shape), layer))
    S = table.shape[-2]
    scale = float(scale)
    bkv = int(block_kv or attention_tuning.get_decode_config(
        S, R, jnp.dtype(table.dtype).name) or 0)
    if not bkv or S % bkv:
        return latent_decode_attention_reference(
            q, table[layer] if stacked else table, lengths, V, scale)
    if interpret is None:
        interpret = not lowering_for_tpu()
    operand = jnp.float32 if interpret else jnp.bfloat16
    lengths = jnp.asarray(lengths).astype(jnp.int32).reshape(N)
    n_blocks = S // bkv
    last = kv_last_block(lengths, bkv, n_blocks, xp=jnp)
    if stacked:
        layer = int(layer)
        row_spec = pl.BlockSpec(
            (None, 1, bkv, R), lambda b, j, len_ref, last_ref: (
                layer, b, jnp.minimum(j, last_ref[b]), 0))
    else:
        row_spec = pl.BlockSpec(
            (1, bkv, R), lambda b, j, len_ref, last_ref: (
                b, jnp.minimum(j, last_ref[b]), 0))

    def tile(ctx):
        q_ref, row_ref = ctx.ins
        acc_ref, m_ref, l_ref = ctx.scratch
        rows = row_ref[0].astype(operand)                   # [BKV, R]
        s = jax.lax.dot_general(
            q_ref[0].astype(operand), rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, BKV]
        kpos = ctx.reduce_id * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (H, bkv), 1)
        s = jnp.where(kpos >= ctx.scalars[0][ctx.ids[0]], _NEG_INF, s)
        _online_softmax_tile(
            s, lambda p: jnp.dot(p.astype(operand), rows[:, :V],
                                 preferred_element_type=jnp.float32),
            acc_ref, m_ref, l_ref)

    def finalize(ctx):
        o, _ = _softmax_finalize(*ctx.scratch)
        ctx.outs[0][0] = o.astype(ctx.outs[0].dtype)

    return tiled_contraction(
        (q, table),
        grid=(N, n_blocks),
        reduce_axis=1,
        in_specs=[pl.BlockSpec((1, H, R), lambda b, j, *_: (b, 0, 0)),
                  row_spec],
        out_specs=pl.BlockSpec((1, H, V), lambda b, j, *_: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H, V), q.dtype),
        scratch=[pltpu.VMEM((H, V), jnp.float32),
                 pltpu.VMEM((H, _MIN_LANES), jnp.float32),
                 pltpu.VMEM((H, _MIN_LANES), jnp.float32)],
        scratch_fill=(0.0, _NEG_INF, 0.0),
        tile=tile, finalize=finalize,
        tile_live=lambda ids, len_ref, last_ref:
            ids[1] <= last_ref[ids[0]],
        scalar_prefetch=(lengths, last),
        interpret=interpret)


def decode_attention_head_slice(q, k_cache, v_cache, lengths, head_offset,
                                n_local_heads, scale=None, block_kv=None,
                                interpret=None, kv_scales=None,
                                layer=None):
    """Tensor-parallel entry (SERVING.md "Tensor-parallel compute"):
    decode attention over one member's RESIDENT head block of the slot
    table. q/k_cache/v_cache are already the LOCAL head shards
    ([N, Hl, D] / [N, S, Hl * D], Hl = n_local_heads: the member's
    contiguous lanes of the flat row; or the stacked local table
    [L, N, S, Hl * D] with a static `layer`, as in `decode_attention`),
    but `kv_scales`
    arrives as the FULL per-layer table [2, H_total] (or [2, H_total,
    1]) — the scales are baked compile-time constants shared by every
    member, so each member dynamic-slices its own [2, Hl] window at
    `head_offset` (a traced `lax.axis_index * Hl` inside shard_map)
    and the in-register dequant stays local. Heads are independent,
    so per head the math is identical to `decode_attention` on the
    full table — bit-exact while XLA preserves the compiled reduction
    shape of the head block, ULP-level otherwise (a 1-head-wide block
    schedules the score contraction differently; pinned either way by
    tests/test_mesh_tp.py)."""
    import jax
    import jax.numpy as jnp
    Hl = int(n_local_heads)
    sc = None
    if kv_scales is not None:
        full = jnp.asarray(kv_scales, jnp.float32)
        full = full.reshape(2, -1)                  # [2, H_total]
        sc = jax.lax.dynamic_slice_in_dim(
            full, jnp.asarray(head_offset, jnp.int32), Hl, axis=1)
    return decode_attention(q, k_cache, v_cache, lengths, scale=scale,
                            block_kv=block_kv, interpret=interpret,
                            kv_scales=sc, layer=layer)


# ---------------------------------------------------------------------------
# fused dequant-matmul: the quantized-inference contraction (QUANTIZE.md).
# The serving flagship sits at 97% of HBM peak (bench.py MFU note) — on
# that roofline, weight BYTES are the step time, so the int8 weight tile
# is streamed from HBM as int8 and dequantized in-register against the
# resident activation tile (Tensor Processing Primitives' fused
# dequant-contraction shape, PAPERS.md): fp32/bf16 weights never touch
# HBM. Per-OUTPUT-channel scales distribute over the K reduction, so
# dequantization folds into the finalize step: acc[m, n] * scale[n] —
# one multiply per output element, not one per weight element.
# ---------------------------------------------------------------------------


def dequant_matmul_reference(x, w_q, scale, out_dtype=None):
    """Plain-XLA oracle/fallback with identical numerics contract:
    x [M, K] float, w_q [K, N] int8, scale [N] f32 per-output-channel ->
    [M, N].  The weight dequantizes through the ACTIVATION dtype (bf16
    activations see a bf16 weight — the same cast the kernel makes
    in-register) and the scale applies to the fp32 accumulator."""
    import jax
    import jax.numpy as jnp
    acc = jax.lax.dot_general(
        x, w_q.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out = acc * scale.astype(jnp.float32)
    return out.astype(out_dtype or x.dtype)


def dequant_matmul(x, w_q, scale, out_dtype=None, block_m=None,
                   block_k=None, block_n=None, interpret=None):
    """Fused dequant-matmul: x [M, K] (fp32/bf16 activations), w_q
    [K, N] int8 per-output-channel-quantized weights, scale [N] f32 ->
    [M, N] in `out_dtype` (default: x.dtype).

    Pallas instantiation of the tiled-contraction core on TPU
    (interpret emulation elsewhere) streaming int8 weight tiles under a
    resident activation tile with fp32 accumulation — the in-register
    dequant is the _stage_dequant cast, the per-channel scale applies
    once at finalize; block geometry resolves through the kernel-tuning
    registry namespace ``dequant_matmul``
    (attention_tuning.get_dequant_config: tuned entry > MXU-aligned
    heuristic; explicit block args override).  Falls back to the
    plain-XLA composition when no geometry tiles the shape — channel
    counts not divisible by any candidate block edge included."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w_q.shape[1]
    cfg = attention_tuning.get_dequant_config(
        M, K, N, jnp.dtype(x.dtype).name)
    bm = int(block_m or (cfg[0] if cfg else 0))
    bk = int(block_k or (cfg[1] if cfg else 0))
    bn = int(block_n or (cfg[2] if cfg else 0))
    if (not bm or not bk or not bn
            or M % bm or K % bk or N % bn):
        return dequant_matmul_reference(x, w_q, scale,
                                        out_dtype=out_dtype)
    scale2d = scale.reshape(1, N).astype(jnp.float32)

    def tile(ctx):
        x_ref, w_ref = ctx.ins[:2]
        (acc_ref,) = ctx.scratch
        xb = x_ref[...]                          # [BM, BK] activation
        wb = _stage_dequant(w_ref[...], xb.dtype)  # [BK, BN] int8->act
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def finalize(ctx):
        s_ref = ctx.ins[2]
        o_ref = ctx.outs[0]
        o_ref[...] = (ctx.scratch[0][...]
                      * s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)

    return tiled_contraction(
        (x, w_q, scale2d),
        grid=(M // bm, N // bn, K // bk),
        reduce_axis=2,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (M, N), jnp.dtype(out_dtype or x.dtype)),
        scratch=[pltpu.VMEM((bm, bn), jnp.float32)],
        tile=tile, finalize=finalize,
        interpret=interpret)


# ---------------------------------------------------------------------------
# framework op wrapper: fluid programs reach the kernel via this op type
# ---------------------------------------------------------------------------

from .registry import register_op  # noqa: E402


@register_op("flash_attention")
def _flash_attention_op(ctx):
    q = ctx.input("Q")
    k = ctx.input("K")
    v = ctx.input("V")
    if ctx.mesh is not None:
        # Mosaic kernels cannot be auto-partitioned by the SPMD
        # partitioner; ANY mesh-built program uses the plain-XLA
        # composition (partitionable, numerically equivalent). The
        # TRACE mesh's device count is deliberately not consulted —
        # programs are traced on small virtual meshes and exported
        # against bigger abstract ones, so mesh-present is the only
        # reliable "will be partitioned" signal. Sharded long-context
        # attention is served by the dedicated ring/Ulysses paths
        # (parallel/ring_attention.py), not by auto-sharding this
        # kernel; the mesh-free (single-device) path keeps Mosaic.
        from ..parallel.ring_attention import local_attention
        return _attention_via(ctx, q, k, v, local_attention)
    return _attention_via(ctx, q, k, v, flash_attention)


def _attention_via(ctx, q, k, v, attn_fn):
    reshaped = False
    if q.ndim == 3:           # [B, S, D] with num_heads attr
        H = int(ctx.attr("num_heads", 1))
        B, S, Dm = q.shape
        if Dm % H:
            raise ValueError(
                "flash_attention: hidden size %d not divisible by "
                "num_heads %d" % (Dm, H))
        q = q.reshape(B, S, H, Dm // H)
        k = k.reshape(B, S, H, Dm // H)
        v = v.reshape(B, S, H, Dm // H)
        reshaped = True
    out = attn_fn(q, k, v, causal=bool(ctx.attr("causal", False)))
    if reshaped:
        out = out.reshape(B, S, Dm)
    return {"Out": out}


# ---------------------------------------------------------------------------
# Fused ResNet bottleneck (inference): the whole residual block — three
# BN-folded convs, both relus, and the shortcut add — in one VMEM-resident
# kernel. This is the "cross-layer fused conv pipeline" lever from
# ROOFLINE.md: the unfused block round-trips every intermediate activation
# through HBM; fused, only the block input and output touch HBM, roughly
# halving activation traffic for the inference graph.
#
# Reference analogue: inference-time conv+bn+act fusion passes
# (paddle/fluid/framework/ir/conv_bn_fuse_pass.cc and the TensorRT engine's
# layer fusion); the reference stops at per-conv epilogue fusion — this
# kernel fuses ACROSS the three convs of a block, which only makes sense on
# TPU where VMEM is large enough to hold the intermediate tiles.
#
# Layout: NHWC only (channels in the lane dimension). 1x1 convs are plain
# [rows, Cin] @ [Cin, Cout] matmuls on the MXU; the 3x3 is nine shifted
# matmuls accumulated in fp32. Stride 2 (on the 3x3, ResNet v1.5 style like
# paddle_tpu/models/resnet.py) is handled with reshape-decimation — Mosaic
# has no general strided slice, but slicing an even run and dropping every
# other row via reshape lowers cleanly.
# ---------------------------------------------------------------------------


def _bottleneck_kernel(x_ref, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref,
                       b2_ref, ws_ref, bs_ref, o_ref, *, H, W, stride,
                       block_h, has_branch):
    """One (batch, row-block) program.

    x_ref    [1, H+2, W, C]   input, pre-padded by one zero row top/bottom
    w0_ref   [C, F]           1x1 reduce (BN-folded)      b0_ref [1, F]
    w1_ref   [9, F, F]        3x3 taps (BN-folded)        b1_ref [1, F]
    w2_ref   [F, C4]          1x1 expand (BN-folded)      b2_ref [1, C4]
    ws_ref   [C, C4]          projection shortcut         bs_ref [1, C4]
                              (aliased to w0/b0 when has_branch is False)
    o_ref    [1, block_h, Wo, C4]
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    s = stride
    bh = block_h
    Wo = W // s if s > 1 else W
    F = w0_ref.shape[1]
    C4 = w2_ref.shape[1]
    io = pl.program_id(1)
    o0 = io * bh                       # first output row of this program
    ext = s * bh + 2                   # conv0 rows incl. the 3x3 halo

    # -- conv0 (1x1) + bias + relu on the extended row window ------------
    # padded-row r of the window corresponds to padded image row s*o0 + r;
    # padded rows 0 and H+1 are the zero-pad ring: conv0 of a zero row is
    # relu(b0) != 0, but the 3x3's true pad operates on a1, so those rows
    # must be exact zeros — mask them.
    x_ext = x_ref[0, pl.ds(o0 * s, ext), :, :]           # [ext, W, C]
    a1 = jax.lax.dot_general(
        x_ext.reshape(ext * W, x_ext.shape[-1]), w0_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    a1 = jnp.maximum(a1 + b0_ref[0], 0.0).reshape(ext, W, F)
    row_ids = o0 * s + jax.lax.broadcasted_iota(jnp.int32, (ext, 1, 1), 0)
    a1 = jnp.where((row_ids >= 1) & (row_ids <= H), a1, 0.0)
    a1 = a1.astype(x_ref.dtype)

    # -- conv1 (3x3, stride s) as nine shifted matmuls -------------------
    zcol = jnp.zeros((ext, 1, F), a1.dtype)
    a1p = jnp.concatenate([zcol, a1, zcol], axis=1)      # [ext, W+2, F]
    acc = jnp.zeros((bh * Wo, F), jnp.float32)
    for dy in range(3):
        if s == 1:
            rows = a1p[dy:dy + bh]                       # [bh, W+2, F]
        else:
            rows = a1p[dy:dy + s * bh].reshape(
                bh, s, W + 2, F)[:, 0]                   # decimate rows
        for dx in range(3):
            if s == 1:
                tap = rows[:, dx:dx + Wo]                # [bh, Wo, F]
            else:
                tap = rows[:, dx:dx + s * Wo].reshape(
                    bh, Wo, s, F)[:, :, 0]               # decimate cols
            acc = acc + jax.lax.dot_general(
                tap.reshape(bh * Wo, F), w1_ref[dy * 3 + dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    h = jnp.maximum(acc + b1_ref[0], 0.0).astype(x_ref.dtype)

    # -- conv2 (1x1 expand) + shortcut + final relu ----------------------
    y = jax.lax.dot_general(h, w2_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + b2_ref[0]

    if has_branch:
        # projection shortcut: x strided by s in both dims, then 1x1
        xs = x_ref[0, pl.ds(o0 * s + 1, s * bh), :, :]
        if s > 1:
            xs = xs.reshape(bh, s, W, xs.shape[-1])[:, 0]
            xs = xs.reshape(bh, Wo, s, xs.shape[-1])[:, :, 0]
        short = jax.lax.dot_general(
            xs.reshape(bh * Wo, xs.shape[-1]), ws_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + bs_ref[0]
    else:
        # identity: C == C4 and s == 1
        xs = x_ref[0, pl.ds(o0 + 1, bh), :, :]
        short = xs.reshape(bh * Wo, C4).astype(jnp.float32)

    out = jnp.maximum(y + short, 0.0)
    o_ref[0] = out.reshape(bh, Wo, C4).astype(o_ref.dtype)


def _pick_block_h(Ho):
    for cand in (16, 14, 12, 8, 7, 6, 4, 2, 1):
        if Ho % cand == 0:
            return cand
    return 1


def _bottleneck_vmem_bytes(H, W, C, F, C4, stride, block_h, dtype_bytes,
                           has_branch=True):
    """Rough VMEM budget for one program: the padded input image, the
    fp32 conv0 window, all weight operands (the identity case passes
    w0 aliased in the ws slot, so its footprint is C*F, not C*C4), and
    the fp32 accumulator/shortcut/output tiles of the epilogue — a
    geometry that passes the gate without those could clear the estimate
    yet fail Mosaic VMEM allocation on chip instead of taking the XLA
    fallback."""
    ext = stride * block_h + 2
    ws_elems = C * C4 if has_branch else C * F
    Wo = W // stride
    return ((H + 2) * W * C * dtype_bytes            # x image block
            + ext * W * F * 4                        # a1 window (fp32)
            + ext * (W + 2) * F * dtype_bytes        # a1p
            + C * F * dtype_bytes + 9 * F * F * dtype_bytes
            + F * C4 * dtype_bytes + ws_elems * dtype_bytes
            + block_h * Wo * F * 4                   # conv1 acc (fp32)
            + block_h * Wo * C4 * 4 * 2              # y + shortcut (fp32)
            + block_h * Wo * C4 * dtype_bytes)       # output block


def bottleneck_reference(x, w0, b0, w1, b1, w2, b2, ws, bs, stride):
    """Plain-XLA oracle/fallback: the same BN-folded block as three
    conv_general_dilated calls (NHWC, HWIO filters)."""
    import jax
    import jax.numpy as jnp

    def conv(v, w, s, pad):
        return jax.lax.conv_general_dilated(
            v, w.astype(v.dtype), (s, s), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)

    a = jnp.maximum(conv(x, w0[None, None], 1, "VALID") + b0, 0.0)
    a = a.astype(x.dtype)
    h = jnp.maximum(
        conv(a, w1, stride, [(1, 1), (1, 1)]) + b1, 0.0).astype(x.dtype)
    y = conv(h, w2[None, None], 1, "VALID") + b2
    if ws is not None:
        short = conv(x, ws[None, None], stride, "VALID") + bs
    else:
        short = x.astype(jnp.float32)
    return jnp.maximum(y + short, 0.0).astype(x.dtype)


_VMEM_CAP = 13 * 1024 * 1024


def fused_bottleneck(x, w0, b0, w1, b1, w2, b2, ws=None, bs=None,
                     stride=1, interpret=None, block_h=None):
    """Fused ResNet bottleneck, inference only. NHWC activations.

    x  [N, H, W, C]
    w0 [C, F]  b0 [F]          1x1 reduce   (BN folded into w/b)
    w1 [3, 3, F, F]  b1 [F]    3x3, stride `stride`, pad 1
    w2 [F, C4]  b2 [C4]        1x1 expand
    ws [C, C4]  bs [C4]        projection shortcut (None -> identity)

    Falls back to the plain-XLA composition when the geometry doesn't
    tile (odd W under stride 2, indivisible rows) or the block would
    blow the VMEM budget.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, C = x.shape
    F = w0.shape[1]
    C4 = w2.shape[1]
    if w1.shape != (3, 3, F, F):
        raise ValueError("w1 must be [3, 3, F, F] with F matching w0; "
                         "got %s" % (w1.shape,))
    s = int(stride)
    has_branch = ws is not None
    if not has_branch and (s != 1 or C != C4):
        raise ValueError("identity shortcut requires stride 1 and C == C4")
    Ho = H // s if s > 1 else H
    Wo = W // s if s > 1 else W
    bh = block_h or _pick_block_h(Ho)
    dtype_bytes = jnp.dtype(x.dtype).itemsize
    # the reshape-decimation trick only handles s in (1, 2) with evenly
    # divisible geometry — anything else takes the plain-XLA path
    tileable = (s in (1, 2) and Ho % bh == 0
                and (s == 1 or (H % s == 0 and W % s == 0))
                and _bottleneck_vmem_bytes(
                    H, W, C, F, C4, s, bh, dtype_bytes,
                    has_branch) <= _VMEM_CAP)
    if not tileable:
        return bottleneck_reference(x, w0, b0, w1, b1, w2, b2, ws, bs, s)

    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0), (0, 0)))
    w1f = w1.reshape(9, F, F)
    wsx = ws if has_branch else w0          # alias: unused when no branch
    bsx = bs if has_branch else b0
    kern = functools.partial(
        _bottleneck_kernel, H=H, W=W, stride=s, block_h=bh,
        has_branch=has_branch)
    full = lambda a: pl.BlockSpec(a.shape, lambda b, i: (0,) * a.ndim)
    args = (w0, b0.reshape(1, F), w1f, b1.reshape(1, F), w2,
            b2.reshape(1, C4), wsx,
            bsx.reshape(1, -1))

    def call(interp, *ops):
        return pl.pallas_call(
            kern,
            grid=(N, Ho // bh),
            in_specs=[pl.BlockSpec((1, H + 2, W, C),
                                   lambda b, i: (b, 0, 0, 0))]
            + [full(a) for a in args],
            out_specs=pl.BlockSpec((1, bh, Wo, C4),
                                   lambda b, i: (b, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, C4), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interp,
        )(*ops)

    return _interpret_dispatch(call, interpret, xp, *args)


def _oihw_to_mat(w):
    """OIHW 1x1 filter [O, I, 1, 1] -> matmul layout [I, O]."""
    return w.reshape(w.shape[0], w.shape[1]).T


@register_op("fused_bottleneck")
def _fused_bottleneck_op(ctx):
    """Program-level fused bottleneck. Filters arrive in the framework's
    OIHW layout (layout-independent parameters, models/resnet.py) and are
    re-laid for the matmul kernel at trace time — XLA constant-folds the
    transposes of persistable weights into the compiled executable."""
    x = ctx.input("X")
    w0 = _oihw_to_mat(ctx.input("W0"))
    w1 = ctx.input("W1").transpose(2, 3, 1, 0)       # OIHW -> HWIO
    w2 = _oihw_to_mat(ctx.input("W2"))
    ws = ctx.input("Ws") if ctx.has_input("Ws") else None
    out = fused_bottleneck(
        x, w0, ctx.input("B0"), w1, ctx.input("B1"), w2, ctx.input("B2"),
        ws=None if ws is None else _oihw_to_mat(ws),
        bs=ctx.input("Bs") if ctx.has_input("Bs") else None,
        stride=int(ctx.attr("stride", 1)))
    return {"Out": out}
