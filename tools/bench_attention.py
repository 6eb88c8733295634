"""Long-context attention microbench + block-geometry autotuner.

Benchmark mode compares the Pallas flash kernel pair (fwd + fused bwd,
ops/pallas_kernels.py) against the plain-XLA composition over a
sequence-length sweep on one chip — the perf evidence for the
long-context story (SURVEY §5, ROOFLINE.md attention section).

`--tune` turns the sweep into a measurement-driven search over
(block_q, block_kv) tile geometries: stage 1 times the forward per
candidate pair, stage 2 times fwd+bwd with the backward pair varying
over the stage-1 winner, and the winners are persisted to the
shape->config cache (ops/attention_tuning.py) that `flash_attention`
consults at trace time — so every later jit/export of the tuned shape
rides the measured-best geometry automatically.

Prints one JSON line per measurement:
  {"metric": "attention_fwd_bwd_ms", "seq_len": S, "variant":
   "flash"|"xla", "value": ms, "tflops": ...}
  {"metric": "attention_tune", "seq_len": S, "block_q": ..., ...}
  {"metric": "attention_tuned", "seq_len": S, "config": {...}}

CPU smoke: --smoke runs tiny shapes in interpret mode (tiny tile
candidates under --tune), so the full bench/tune/cache plumbing is
exercised without a chip — the tier-1 test in
tests/test_flash_attention.py does exactly that. Without --smoke the run
needs the chip (exit 3 otherwise).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# v5e VMEM is ~16 MB/core; the pipeline double-buffers streamed tiles,
# so gate candidates at half of a conservative budget
_VMEM_BUDGET = 7 * 1024 * 1024


def _candidates(S, smoke):
    # smoke keeps the grid 2x2: each interpret-mode candidate costs a
    # CPU jit compile and the tier-1 smoke test pays for every one
    base = (32, 64) if smoke else (128, 256, 512)
    edges = [b for b in base if S % b == 0 and b <= S]
    return [(bq, bk) for bq in edges for bk in edges]


def _timer(fn, args, iters):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0],
                     np.float32).ravel()[0])     # host fence
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0],
                     np.float32).ravel()[0])
    return (time.perf_counter() - t0) / iters


def tune_one(S, qkv, causal, iters, emit, cache_path):
    """Two-stage geometry search for one (seq, head_dim, dtype) shape;
    records the winner and returns it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_tuning
    from paddle_tpu.ops.pallas_kernels import flash_attention

    q, k, v = qkv
    D = q.shape[-1]
    dtype = jnp.dtype(q.dtype).name
    itemsize = jnp.dtype(q.dtype).itemsize
    smoke = S <= 1024 and not _on_tpu[0]
    cands = [c for c in _candidates(S, smoke)
             if attention_tuning.attention_vmem_bytes(
                 D, c[0], c[1], itemsize) <= _VMEM_BUDGET]
    if not cands:
        emit({"metric": "attention_tune", "seq_len": S,
              "error": "no tileable candidate geometry"})
        return None

    # stage 1: forward-only, pick the fwd pair
    best_fwd, best_ms = None, None
    for bq, bkv in cands:
        fn = jax.jit(lambda q, k, v, bq=bq, bkv=bkv: flash_attention(
            q, k, v, causal=causal, block_q=bq, block_kv=bkv))
        try:
            ms = _timer(fn, (q, k, v), iters) * 1e3
        except Exception as e:
            emit({"metric": "attention_tune", "seq_len": S, "stage": "fwd",
                  "block_q": bq, "block_kv": bkv, "error":
                  type(e).__name__,
                  "note": (str(e).splitlines() or [""])[0][:160]})
            continue
        emit({"metric": "attention_tune", "seq_len": S, "stage": "fwd",
              "block_q": bq, "block_kv": bkv, "value": round(ms, 3),
              "unit": "ms"})
        if best_ms is None or ms < best_ms:
            best_fwd, best_ms = (bq, bkv), ms
    if best_fwd is None:
        return None

    # stage 2: fwd+bwd with the fwd winner fixed, pick the bwd pair
    def make_step(bq_b, bkv_b):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=causal,
                                block_q=best_fwd[0], block_kv=best_fwd[1],
                                block_q_bwd=bq_b, block_kv_bwd=bkv_b)
            return jnp.sum(o.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    best_bwd, best_ms = None, None
    for bq, bkv in cands:
        try:
            ms = _timer(make_step(bq, bkv), (q, k, v), iters) * 1e3
        except Exception as e:
            emit({"metric": "attention_tune", "seq_len": S, "stage": "bwd",
                  "block_q": bq, "block_kv": bkv, "error":
                  type(e).__name__,
                  "note": (str(e).splitlines() or [""])[0][:160]})
            continue
        emit({"metric": "attention_tune", "seq_len": S, "stage": "bwd",
              "block_q": bq, "block_kv": bkv, "value": round(ms, 3),
              "unit": "ms"})
        if best_ms is None or ms < best_ms:
            best_bwd, best_ms = (bq, bkv), ms
    if best_bwd is None:
        best_bwd = best_fwd
    cfg = attention_tuning.AttentionConfig(
        best_fwd[0], best_fwd[1], best_bwd[0], best_bwd[1])
    path = attention_tuning.record(
        S, D, causal, dtype, cfg,
        extra={"fwd_bwd_ms": round(best_ms or 0.0, 3),
               "backend": "tpu" if _on_tpu[0] else "cpu-interpret"},
        path=cache_path)
    emit({"metric": "attention_tuned", "seq_len": S, "head_dim": D,
          "causal": causal, "dtype": dtype, "config": cfg.asdict(),
          "cache": path})
    return cfg


_on_tpu = [False]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head_dim", type=int, default=128)
    ap.add_argument("--seq_lens", default="1024,2048,4096,8192")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tune", action="store_true",
                    help="sweep (block_q, block_kv) geometries per seq "
                         "len and persist the winners to the trace-time "
                         "config cache before the flash-vs-xla rows")
    ap.add_argument("--tune_cache", default="",
                    help="cache file for --tune (default: "
                         "FLAGS.attention_tune_cache resolution)")
    args = ap.parse_args()

    from bench import init_backend
    device = init_backend(smoke=args.smoke, tool="bench_attention")
    on_tpu = device["platform"] == "tpu"
    _on_tpu[0] = on_tpu
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention
    from paddle_tpu.parallel.ring_attention import local_attention
    if args.tune_cache:
        from paddle_tpu.flags import set_flags
        set_flags({"attention_tune_cache": args.tune_cache})

    B, H, D = args.batch, args.heads, args.head_dim
    causal = bool(args.causal)
    seq_lens = [int(s) for s in args.seq_lens.split(",")]
    if not on_tpu:
        B, H, D = 2, 2, 64
        seq_lens = [s for s in seq_lens if s <= 512] or [128, 256]
        iters = 2
    else:
        iters = args.iters
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    def emit(rec):
        rec.update(device)
        print(json.dumps(rec), flush=True)

    def make_fn(attn):
        def loss_fn(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))
        grad = jax.grad(loss_fn, argnums=(0, 1, 2))

        def step(q, k, v):
            return grad(q, k, v)
        return jax.jit(step)

    # traced AFTER any --tune run below, so the flash variant rows ride
    # the freshly-tuned cache entries (trace-time consultation)
    flash = make_fn(lambda q, k, v: flash_attention(q, k, v,
                                                    causal=causal))
    naive = make_fn(lambda q, k, v: local_attention(q, k, v,
                                                    causal=causal))

    rng = np.random.RandomState(0)
    for S in seq_lens:
        q, k, v = (jax.device_put(
            rng.randn(B, S, H, D).astype(np.float32) * 0.1).astype(dtype)
            for _ in range(3))
        if args.tune:
            tune_one(S, (q, k, v), causal, iters, emit,
                     args.tune_cache or None)
        # fwd+bwd FLOPs: 4*B*H*S^2*D fwd matmuls x ~2.5 for the backward
        flops = 4.0 * B * H * S * S * D * 3.5 * (0.5 if causal else 1.0)
        for name, fn in (("flash", flash), ("xla", naive)):
            try:
                dt = _timer(fn, (q, k, v), iters)
                rec = {"metric": "attention_fwd_bwd_ms", "seq_len": S,
                       "variant": name, "value": round(dt * 1e3, 3),
                       "unit": "ms",
                       "tflops": round(flops / dt / 1e12, 2),
                       "batch": B, "heads": H, "head_dim": D,
                       "causal": causal}
            except Exception as e:  # OOM at long S is a RESULT
                rec = {"metric": "attention_fwd_bwd_ms", "seq_len": S,
                       "variant": name, "value": None,
                       "error": type(e).__name__,
                       "note": (str(e).splitlines() or [""])[0][:160]}
            emit(rec)


if __name__ == "__main__":
    main()
