#!/usr/bin/env python
"""ONE block-geometry autotuner for every Pallas kernel family
(ROOFLINE.md "Kernel substrate").

Every family in ops/pallas_kernels.py instantiates the same
tiled-contraction core, and every family resolves its block geometry
through the same kernel-tuning registry (COMPILE_CACHE.md) — so one
driver sweeps them all, replacing the three per-bench --tune paths
(bench_attention --tune stays as a compatibility alias for the flash
family):

  flash    (block_q, block_kv) fwd + (block_q_bwd, block_kv_bwd) —
           namespace ``flash_attention``, keys S*_D*_c*_<dtype>
  decode   block_kv of the decode-attention kernel over the slot cache,
           swept per KV-CACHE dtype (fp32 AND int8 — the int8 keys are
           DEC_S*_D*_int8: a 1-byte stream tunes to different tiles
           than a 4-byte one) — keys DEC_S*_D*_<kv_dtype>; this wires
           in the ``record_decode`` sweep ROADMAP carried as
           measurement debt
  dequant  (block_m, block_k, block_n) of the fused dequant-matmul —
           namespace ``dequant_matmul``, keys M*_K*_N*_<act_dtype>

Winners are committed through attention_tuning.record/record_decode/
record_dequant (the registry's atomic write-temp→fsync→rename
discipline); later traces of the same shape pick them up with zero
runtime cost.  One JSON line per measurement and per recorded winner.

    python tools/tune_kernels.py                       # all families
    python tools/tune_kernels.py --families decode --kv_dtypes int8
    python tools/tune_kernels.py --smoke               # tier-1 path
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

_VMEM_BUDGET = 12 * 1024 * 1024
_on_tpu = [False]


def emit(rec):
    print(json.dumps(rec), flush=True)


def _timer(fn, args, iters):
    """Mean seconds per call with a host fence before and after the
    timed window (the bench_attention idiom)."""
    import jax
    out = fn(*args)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0],
                     np.float32).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0],
                     np.float32).ravel()[0])
    return (time.perf_counter() - t0) / max(iters, 1)


def _edges(dim, cap, floor=2):
    from paddle_tpu.ops import attention_tuning as at
    return [c for c in at._CANDIDATES
            if floor <= c <= cap and dim % c == 0]


# ---------------------------------------------------------------------------
# flash family
# ---------------------------------------------------------------------------


def tune_flash(shapes, dtypes, causal, iters):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_tuning as at
    from paddle_tpu.ops.pallas_kernels import flash_attention
    tuned = []
    for (B, S, H, D) in shapes:
        for dtype in dtypes:
            rng = np.random.RandomState(11)
            mk = lambda: jnp.asarray(  # noqa: E731
                rng.randn(B, S, H, D) * 0.1, jnp.dtype(dtype))
            q, k, v = mk(), mk(), mk()
            itemsize = jnp.dtype(dtype).itemsize
            cap = 256 if _on_tpu[0] else 64
            cands = [(bq, bkv)
                     for bq in _edges(S, cap) for bkv in _edges(S, cap)
                     if at.attention_vmem_bytes(
                         D, bq, bkv, itemsize) <= _VMEM_BUDGET]
            best, best_ms = None, None
            for bq, bkv in cands:
                fn = jax.jit(
                    lambda q, k, v, bq=bq, bkv=bkv: flash_attention(
                        q, k, v, causal=causal, block_q=bq,
                        block_kv=bkv))
                try:
                    ms = _timer(fn, (q, k, v), iters) * 1e3
                except Exception as e:
                    emit({"metric": "tune_flash", "seq_len": S,
                          "dtype": dtype, "block_q": bq, "block_kv": bkv,
                          "error": type(e).__name__})
                    continue
                emit({"metric": "tune_flash", "seq_len": S,
                      "dtype": dtype, "block_q": bq, "block_kv": bkv,
                      "value": round(ms, 3), "unit": "ms"})
                if best_ms is None or ms < best_ms:
                    best, best_ms = (bq, bkv), ms
            if best is None:
                emit({"metric": "tune_flash", "seq_len": S,
                      "dtype": dtype, "error": "no tileable geometry"})
                continue
            cfg = at.AttentionConfig(best[0], best[1], best[0], best[1])
            at.record(S, D, bool(causal), dtype, cfg,
                      extra={"ms": round(best_ms, 3),
                             "tuner": "tune_kernels"})
            resolved = at.get_config(S, D, bool(causal), dtype)
            emit({"metric": "tuned", "family": "flash", "seq_len": S,
                  "head_dim": D, "dtype": dtype, "causal": bool(causal),
                  "config": cfg.asdict(), "ms": round(best_ms, 3),
                  "resolves": resolved == cfg})
            tuned.append(("flash", S, D, dtype))
    return tuned


# ---------------------------------------------------------------------------
# decode family (fp32 + int8 KV cache)
# ---------------------------------------------------------------------------


def tune_decode(shapes, kv_dtypes, iters):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_tuning as at
    from paddle_tpu.ops.pallas_kernels import decode_attention
    tuned = []
    for (N, S, H, D) in shapes:
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(N, H, D) * 0.1, jnp.float32)
        kf = rng.randn(N, S, H, D).astype(np.float32) * 0.1
        vf = rng.randn(N, S, H, D).astype(np.float32) * 0.1
        lengths = np.minimum(
            rng.randint(1, S + 1, size=N), S).astype(np.int32)
        for kv_dtype in kv_dtypes:
            if kv_dtype == "int8":
                ks = (np.abs(kf).max(axis=(0, 1, 3)) / 127.0 + 1e-8)
                vs = (np.abs(vf).max(axis=(0, 1, 3)) / 127.0 + 1e-8)
                kc = jnp.asarray(np.clip(np.round(
                    kf / ks[None, None, :, None]), -127, 127), jnp.int8)
                vc = jnp.asarray(np.clip(np.round(
                    vf / vs[None, None, :, None]), -127, 127), jnp.int8)
                scales = np.stack([ks, vs]).astype(np.float32)
            else:
                kc, vc, scales = jnp.asarray(kf), jnp.asarray(vf), None
            # as a slot table holds a position: one flat row of H * D
            kc, vc = (t.reshape(N, S, H * D) for t in (kc, vc))
            best, best_ms = None, None
            for bkv in _edges(S, 512 if _on_tpu[0] else 64):
                fn = jax.jit(
                    lambda q, kc, vc, bkv=bkv, scales=scales:
                    decode_attention(q, kc, vc, lengths, block_kv=bkv,
                                     kv_scales=scales))
                try:
                    ms = _timer(fn, (q, kc, vc), iters) * 1e3
                except Exception as e:
                    emit({"metric": "tune_decode", "seq_len": S,
                          "kv_dtype": kv_dtype, "block_kv": bkv,
                          "error": type(e).__name__})
                    continue
                emit({"metric": "tune_decode", "seq_len": S,
                      "kv_dtype": kv_dtype, "block_kv": bkv,
                      "value": round(ms, 3), "unit": "ms"})
                if best_ms is None or ms < best_ms:
                    best, best_ms = bkv, ms
            if best is None:
                emit({"metric": "tune_decode", "seq_len": S,
                      "kv_dtype": kv_dtype,
                      "error": "no tileable geometry"})
                continue
            at.record_decode(S, D, kv_dtype, best,
                             extra={"ms": round(best_ms, 3),
                                    "tuner": "tune_kernels"})
            resolved = at.get_decode_config(S, D, kv_dtype)
            emit({"metric": "tuned", "family": "decode", "seq_len": S,
                  "head_dim": D, "kv_dtype": kv_dtype, "block_kv": best,
                  "ms": round(best_ms, 3), "resolves": resolved == best})
            tuned.append(("decode", S, D, kv_dtype))
    return tuned


# ---------------------------------------------------------------------------
# dequant family
# ---------------------------------------------------------------------------


def tune_dequant(shapes, dtypes, iters, max_combos=48):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_tuning as at
    from paddle_tpu.ops.pallas_kernels import dequant_matmul
    tuned = []
    for (M, K, N) in shapes:
        rng = np.random.RandomState(3)
        w_q = jnp.asarray(
            rng.randint(-127, 128, size=(K, N)), jnp.int8)
        scale = jnp.asarray(
            np.abs(rng.randn(N)).astype(np.float32) * 0.01 + 1e-4)
        for dtype in dtypes:
            x = jnp.asarray(rng.randn(M, K) * 0.1, jnp.dtype(dtype))
            cap = 256 if _on_tpu[0] else 64
            combos = [(bm, bk, bn)
                      for bm in _edges(M, cap, floor=1)
                      for bk in _edges(K, cap * 2)
                      for bn in _edges(N, cap)][:max_combos]
            best, best_ms = None, None
            for bm, bk, bn in combos:
                fn = jax.jit(
                    lambda x, w, s, bm=bm, bk=bk, bn=bn: dequant_matmul(
                        x, w, s, block_m=bm, block_k=bk, block_n=bn))
                try:
                    ms = _timer(fn, (x, w_q, scale), iters) * 1e3
                except Exception as e:
                    emit({"metric": "tune_dequant", "shape": [M, K, N],
                          "dtype": dtype, "blocks": [bm, bk, bn],
                          "error": type(e).__name__})
                    continue
                emit({"metric": "tune_dequant", "shape": [M, K, N],
                      "dtype": dtype, "blocks": [bm, bk, bn],
                      "value": round(ms, 3), "unit": "ms"})
                if best_ms is None or ms < best_ms:
                    best, best_ms = (bm, bk, bn), ms
            if best is None:
                emit({"metric": "tune_dequant", "shape": [M, K, N],
                      "dtype": dtype, "error": "no tileable geometry"})
                continue
            at.record_dequant(M, K, N, dtype, *best,
                              extra={"ms": round(best_ms, 3),
                                     "tuner": "tune_kernels"})
            resolved = at.get_dequant_config(M, K, N, dtype)
            emit({"metric": "tuned", "family": "dequant",
                  "shape": [M, K, N], "dtype": dtype,
                  "blocks": list(best), "ms": round(best_ms, 3),
                  "resolves": resolved == best})
            tuned.append(("dequant", M, K, N, dtype))
    return tuned


def _parse_shapes(spec, arity, what):
    out = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        dims = [int(x) for x in part.split(",")]
        if len(dims) != arity:
            raise SystemExit("bad --%s entry %r (want %d dims)"
                             % (what, part, arity))
        out.append(tuple(dims))
    return out


def main():
    ap = argparse.ArgumentParser(
        description="unified Pallas kernel-family block-geometry "
                    "autotuner (writes the kernel-tuning registry)")
    ap.add_argument("--families", default="flash,decode,dequant",
                    help="comma list: flash,decode,dequant")
    ap.add_argument("--flash_shapes", default="4,1024,8,128",
                    help="semicolon list of B,S,H,D")
    ap.add_argument("--decode_shapes", default="8,2048,8,128",
                    help="semicolon list of N(slots),S(cache),H,D")
    ap.add_argument("--dequant_shapes", default="32,512,1024",
                    help="semicolon list of M,K,N")
    ap.add_argument("--dtypes", default="float32",
                    help="activation dtypes for flash/dequant")
    ap.add_argument("--kv_dtypes", default="float32,int8",
                    help="KV-cache dtypes for the decode family — the "
                         "int8 sweep writes the DEC_*_int8 keys")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cache_dir", default="",
                    help="kernel-tuning registry root "
                         "(FLAGS.compile_cache_dir override)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-safe shapes, 2 iters — the tier-1 "
                         "path proving the sweep-record-resolve loop")
    args = ap.parse_args()

    from bench import init_backend
    device = init_backend(smoke=args.smoke, tool="tune_kernels")
    _on_tpu[0] = device["platform"] == "tpu"
    if args.cache_dir:
        from paddle_tpu.flags import FLAGS
        FLAGS.compile_cache_dir = args.cache_dir
    if args.smoke:
        args.flash_shapes = "2,64,2,16"
        args.decode_shapes = "2,32,2,8"
        args.dequant_shapes = "8,32,16"
        args.iters = min(args.iters, 2)

    families = [f.strip() for f in args.families.split(",") if f.strip()]
    dtypes = [d.strip() for d in args.dtypes.split(",") if d.strip()]
    kv_dtypes = [d.strip() for d in args.kv_dtypes.split(",")
                 if d.strip()]
    tuned = []
    if "flash" in families:
        tuned += tune_flash(_parse_shapes(args.flash_shapes, 4,
                                          "flash_shapes"),
                            dtypes, args.causal, args.iters)
    if "decode" in families:
        tuned += tune_decode(_parse_shapes(args.decode_shapes, 4,
                                           "decode_shapes"),
                             kv_dtypes, args.iters)
    if "dequant" in families:
        tuned += tune_dequant(_parse_shapes(args.dequant_shapes, 3,
                                            "dequant_shapes"),
                              dtypes, args.iters)
    emit({"metric": "tune_kernels_done", "families": families,
          "entries": len(tuned), **device})
    return 0 if tuned else 2


if __name__ == "__main__":
    sys.exit(main())
