"""Fused-bottleneck tuner: sweep block_h per ResNet-50 stage geometry on
the real chip and report the fastest (plus the XLA-composition baseline).

The kernel's one tiling knob is block_h (output rows per program); the
best value depends on Mosaic's relayout costs for the stride-2
reshape-decimation and on VMEM double-buffering, which can only be
measured on silicon:

    python tools/tune_bottleneck.py            # all ResNet-50 stages
    python tools/tune_bottleneck.py --stage 1  # one stage

Prints one JSON line per (stage, block_h) and a final "best" line per
stage — paste the best map into _pick_block_h if it disagrees with the
current divisor heuristic. CPU smoke: --smoke (tiny shapes, interpret).
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

# ResNet-50 bottleneck geometries (NHWC, after the stem):
#   stage, H=W, C_in, F, stride of the first block, n_blocks
STAGES = {
    1: dict(H=56, C=256, F=64, s_first=1, first_C=64),
    2: dict(H=56, C=256, F=128, s_first=2, first_C=256),
    3: dict(H=28, C=512, F=256, s_first=2, first_C=512),
    4: dict(H=14, C=1024, F=512, s_first=2, first_C=1024),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", type=int, default=0, help="0 = all")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    from bench import init_backend
    device = init_backend(smoke=args.smoke, tool="tune_bottleneck")
    on_tpu = device["platform"] == "tpu"
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (fused_bottleneck,
                                               bottleneck_reference)
    N = args.batch if on_tpu else 2
    iters = args.iters if on_tpu else 2
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    stages = [args.stage] if args.stage else sorted(STAGES)
    if not on_tpu:
        # shrink to smoke shapes with the same divisibility structure
        for st in STAGES.values():
            st["H"] = max(8, st["H"] // 8)
            st["C"] //= 8
            st["F"] //= 8
            st["first_C"] //= 8

    rng = np.random.RandomState(0)

    def t(*s):
        return jnp.asarray(rng.randn(*s).astype(np.float32) * 0.1, dtype)

    for stage in stages:
        st = STAGES[stage]
        # the stage's steady-state (identity) block dominates: n-1 of n;
        # its geometry is AFTER the stage's first (possibly strided) block
        F = st["F"]
        H_id = st["H"] if st["s_first"] == 1 else st["H"] // 2
        C_id = F * 4
        x = t(N, H_id, H_id, C_id)
        p = dict(w0=t(C_id, F), b0=t(F), w1=t(3, 3, F, F), b1=t(F),
                 w2=t(F, C_id), b2=t(C_id))

        def run(fn):
            out = fn()
            jax.block_until_ready(out)
            float(np.asarray(out[0, 0, 0, 0], np.float32))
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            float(np.asarray(out[0, 0, 0, 0], np.float32))
            return (time.perf_counter() - t0) / iters * 1e3

        base = jax.jit(lambda: bottleneck_reference(
            x, p["w0"], p["b0"], p["w1"], p["b1"], p["w2"], p["b2"],
            None, None, 1))
        ms = run(base)
        print(json.dumps({"stage": stage, "variant": "xla",
                          "H": H_id, "C": C_id, "F": F,
                          "value_ms": round(ms, 3)}))
        best = ("xla", ms)
        for bh in (4, 7, 8, 14, 16, 28):
            if H_id % bh:
                continue
            try:
                fn = jax.jit(lambda bh=bh: fused_bottleneck(
                    x, p["w0"], p["b0"], p["w1"], p["b1"], p["w2"],
                    p["b2"], stride=1, block_h=bh,
                    interpret=not on_tpu))
                ms = run(fn)
                rec = {"stage": stage, "variant": "fused", "block_h": bh,
                       "value_ms": round(ms, 3)}
                if ms < best[1]:
                    best = ("bh=%d" % bh, ms)
            except Exception as e:
                rec = {"stage": stage, "variant": "fused", "block_h": bh,
                       "error": type(e).__name__,
                       "note": (str(e).splitlines() or [""])[0][:160]}
            print(json.dumps(rec))
        summary = {"stage": stage, "best": best[0],
                   "best_ms": round(best[1], 3)}
        summary.update(device)
        print(json.dumps(summary))


if __name__ == "__main__":
    main()
