"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two paths users of this framework enter — a Fluid trainer and the
generation server — once, through their normal entry points, at the full
published width of one model each, on ONE TPU chip in ONE process:

  train    ResNet-50 (batch 256, bf16 AMP, NHWC) through fluid.Executor:
           startup, six `exe.run` steps on seeded host batches, one
           `run_loop(steps=4)`.
  serve    an InferenceServer in this process serving a GPT-2-small-geometry
           decode artifact (seeded weights) to four concurrent
           `infer_stream` clients, fp32 and int8 KV cache, each checked
           against the same model with the Mosaic decode kernel swapped for
           its plain-XLA reference; the kernel alone against its reference
           and, bit for bit, against a whole-row stream, at short, mixed
           and full lengths; a window of trips against one-trip dispatches.
  olmoe    the same server serving ONE layer of OLMoE-1B-7B at its
           published widths (RMSNorm, RoPE, qk-norm, 64 routed experts of
           1024, top-8: benchmark/configs/olmoe_1b_7b.json) to four
           concurrent `infer_stream` clients: one Mosaic `decode_attention`
           call and three grouped-matmul kernels a layer in the step, logits
           against benchmark/reference/olmoe_1b_7b.py.
  kernels  flash attention fwd+bwd, decode attention at a grouped-query
           table and at OLMoE's wide rows, latent decode attention and
           dequant_matmul, compiled (`interpret=False`) and compared with
           their references.

`--chips 4` runs instead — and only — what exists across chips:
ParallelExecutor against the single-device Executor, and four one-chip serving
lanes behind the router against one lane.

Every phase prints one JSON line; a phase that raises or fails a check ends
the script non-zero at once.  The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Without a TPU the script exits 3 before any phase and prints no result: it
never falls back to the CPU.  Numbers printed here are observations of one
run (compile seconds, step/token milliseconds, peak bytes), not a benchmark.
"""

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# Off-chip rehearsals import this module, rebind these and call the phases at
# a tiny size (on-chip-measurement guide §2.1); no command-line option does.
REQUIRED_PLATFORM = "tpu"
TRAIN = dict(batch_size=256, class_dim=1000, depth=50, dataset="imagenet",
             layout="NHWC")
TRAIN_HW = 224
# GPT-2 small: the widest public geometry build_tiny_decode_model's block
# (LayerNorm, learned positions, MHA, 4x ReLU MLP) has a counterpart for
SERVE = dict(vocab_size=50257, d_model=768, n_heads=12, n_layers=12,
             max_seq_len=1024, prefill_buckets=(128, 1024))
PROMPT_LENS = (5, 37, 128, 600)      # both prefill buckets, bucket edge
NEW_TOKENS = 32
# one layer of OLMoE at the widths of benchmark/configs/olmoe_1b_7b.json
OLMOE_LAYERS = 1
OLMOE_SLOTS = 4
OLMOE_PROMPT_LENS = (9, 300, 512, 700)     # both buckets, a bucket's edge
FLASH = dict(B=2, S=4096, H=16, D=128)
DEQUANT = dict(M=256, K=2048, N=8192)
# a grouped-query slot table: LFM2's rows (32 query heads over 8 K/V heads of
# 64: flat rows of 512 lanes) and cache length, 8 slots of the cell's 32
DECODE_GQA = (8, 4096, 32, 8, 64)
# OLMoE's slot table (16 heads of 128: flat rows of 2048 lanes) at the cell's
# 8 slots and cache length; GPT-2 small's (12 heads of 64: 768 lanes) is the
# serve phase's own
DECODE_WIDE = (8, 4096, 16, 16, 128)

# a latent slot table at openPangu-Ultra-MoE's published row: 128 absorbed
# query heads over ONE row a position of 512 + 64 = 576 values, held as 640
# lanes (slot_state.latent_row), the first 512 of them the values; 8 slots of the
# cell's 64
DECODE_LATENT = (8, 4096, 128, 576, 512)

# a scanned-state table at Falcon-H1-34B's published mixer: 32 heads of
# [128, 256] fp32 over 2 groups, two layers of 24 slots (the cell holds four
# of 96)
SSM_TABLE = (2, 24, 32, 128, 256, 2)

# a chunk of a MiniCPM-SALA prefill's stage 2: 2,048 queries of 32 heads over
# 2 K/V heads of 128, the 8,192 bucket's buffered rows, blocks of 64, chunk 2
# of 4 with the prompt ending inside it
SPARSE_PREFILL = dict(C=2048, B=8192, H=32, Hc=2, D=128, block=64, chunk=2,
                      true_len=5000, topk=64)

# Stated tolerances.
# serve, the kernel alone: decode_attention (block-diagonal queries against
# flat K/V rows, both contractions on the MXU at fp32 precision, online
# softmax over blocks of 128) vs its reference at full fp32 matmul precision
# on random O(1) inputs at the served geometry — same math, another
# summation order over <= 1024 positions.
TOL_DECODE_KERNEL = 5e-5
# ssm_update's read-out y = sum over 256 state values of new * C against the
# reference's, as a share of the largest |y|: the same fp32 products in
# another order of summation (the state it writes back is held to EQUAL the
# reference's bit for bit)
TOL_SSM_READOUT_REL = 1e-5
# sparse_prefill_attention (bf16 operands, fp32 sums and softmax) vs its
# reference, XLA's form at the default precision, which rounds the same
# operands to bf16: what is left is the rounding of the probabilities against
# another running maximum and the order of the sums, on outputs of O(1)
# (1.7e-4 to 2.2e-4 at the cell's shapes, my chip runs, PR 50)
TOL_SPARSE_PREFILL_KERNEL = 2e-3
# latent_decode_attention (both contractions on the MXU, operands rounded to
# bfloat16 as the default precision rounds every matmul's, fp32 accumulation
# and softmax) vs its reference at full fp32 precision ON THE SAME
# BF16-ROUNDED q and rows: what is left is the rounding of the probabilities
# (2^-9 relative) and the summation order, on outputs of O(1)
TOL_LATENT_KERNEL = 1e-2
# serve, the whole model: fp32 logits (std ~1) of the Mosaic-kernel step vs
# the reference-attention step on the same prefix.  The attention outputs
# agree to TOL_DECODE_KERNEL, but every other matmul of BOTH programs runs at
# the TPU's default precision (operands rounded to bf16), where a last-bit
# difference that lands on the other side of a rounding moves an activation
# by 2^-8 and cascades through 12 layers: the first chip run measured a max
# of 0.021 over 4 x 31 x 50257 logits.  So exact token equality over 128
# tokens is not a reasonable contract at this width; the contract is this
# bound, and that the reference's own top-1 is the served token nearly
# everywhere (a near-tie may flip).
TOL_LOGITS = 6e-2
MIN_TOP1_AGREEMENT = 0.9
# olmoe: fp32 logits of the served program (default precision, router at
# "highest") vs the plain reference ("highest" throughout) after ONE layer
# 2048 wide: the bound of the 12-layer GPT-2 comparison above holds it with
# room (0.045 over 73 positions, my chip run, PR 26).  A position whose 8th
# and 9th router probabilities lie within OLMOE_ROUTER_GAP in the reference
# may keep another 8th expert in the program (the router's input is rounded
# to bf16 upstream): that is another function, not a rounding, and in a
# ONE-layer model, where the experts' output is most of the residual stream,
# it moves a logit by 0.3-1.05 (4 of 51 such positions did, at gaps up to
# 4.3e-4; the other 47 stayed under 0.05; same run).  A near-tie may miss
# the bound up to OLMOE_TOL_FLIPPED; those that do are counted and bounded
# in share, since a fault moves every position and not a few.
OLMOE_ROUTER_GAP = 1e-3
OLMOE_TOL_FLIPPED = 1.5
OLMOE_MAX_FLIPPED_SHARE = 0.15
# kernels: bf16 flash attention vs fp32-math reference (bf16 has 8 mantissa
# bits; outputs are O(1), gradients are compared relative to their max)
TOL_FLASH_FWD = 2e-2
TOL_FLASH_BWD_REL = 3e-2
# dequant_matmul (bf16 x int8 -> fp32 accumulate, K=2048) vs reference:
# identical math, only the accumulation order differs
TOL_DEQUANT_REL = 1e-2
# --chips 4: |loss delta| of the 4-chip SPMD step vs one chip, 5 steps at
# lr 1e-3 (the rate __graft_entry__._dryrun_multichip_impl argues for: real
# updates, bounded amplification).  Its 5e-4 is for an fp32 BN stack and is
# out of reach here: this program runs bf16 AMP, where re-ordering a
# reduction alone moves the trajectory.  ONE chip fed the same batches with
# their samples permuted measured up to 2e-4 at step 0 and up to 2e-2 by
# step 4 (two permutations); the phase measures that floor again each run
# and prints it.  The 4-chip step measured 1.3e-3 at step 0 (the per-device
# batch is 64, not 256: other conv tilings, other points where fusions round
# to bf16) and up to 2.4e-2 later, i.e. at the floor (my chip runs, PR 21).
# The bounds are ~4x and ~2x those; a fault in the semantics (per-shard BN
# statistics, a gradient scaled by the device count) shows at O(0.1).
TOL_PARALLEL_STEP0 = 5e-3
TOL_PARALLEL = 5e-2


class SmokeFailure(AssertionError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **fields):
    print(json.dumps(dict(phase=phase, **fields)), flush=True)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def where(arr):
    """'tpu:0'-style label of every device an array lives on."""
    return sorted("%s:%d" % (d.platform, d.id) for d in arr.devices())


def require_on_chip(arr, what):
    plats = {d.platform for d in arr.devices()}
    require(plats == {REQUIRED_PLATFORM},
            "%s lives on %s, not on %s" % (what, sorted(plats),
                                           REQUIRED_PLATFORM))


def require_mosaic(text, at_least, what):
    """The executable whose optimized HLO is `text` holds the Mosaic kernel
    (not its plain-XLA reference, not interpret mode); returns the count."""
    n = text.count("tpu_custom_call")
    require(n >= at_least, "%s holds %d Mosaic custom calls, expected >= %d"
            % (what, n, at_least))
    return n


def require_donated(old_buffer):
    require(old_buffer.is_deleted(),
            "the executor step did not donate its state")


def dir_entries(path):
    """(files, bytes) of a cache directory; (0, 0) when it does not exist."""
    if not (path and os.path.isdir(path)):
        return 0, 0
    names = os.listdir(path)
    return len(names), sum(os.path.getsize(os.path.join(path, n))
                           for n in names)


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------

def train_batches(seed, n):
    rng = np.random.RandomState(seed)
    b = TRAIN["batch_size"]
    return [{"data": rng.randn(b, TRAIN_HW, TRAIN_HW, 3).astype(np.float32),
             "label": rng.randint(0, TRAIN["class_dim"], (b, 1))
             .astype(np.int64)} for _ in range(n)]


def phase_train(seed, devs):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet

    fluid.set_amp(True)
    try:
        main, startup, _, loss, _, _ = resnet.get_model(lr=0.01, **TRAIN)
        b0, b1 = train_batches(seed, 2)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace(0))
            # TPUPlace names jax's DEFAULT backend (fluid/core.py), so the
            # smoke, not the Place, is what insists on a chip
            dev = exe.place.jax_device()
            require(dev.platform == REQUIRED_PLATFORM and dev == devs[0],
                    "TPUPlace(0) resolved to %r" % (dev,))
            exe.run(startup)
            names = [n for n in functionalizer.persistable_names(main)
                     if scope.get(n) is not None]

            t0 = time.perf_counter()
            first, = exe.run(main, feed=b0, fetch_list=[loss])
            first_s = time.perf_counter() - t0
            losses, step_ms = [float(first.reshape(-1)[0])], []
            # the jitted step donates its state on a TPU
            # (Executor._get_jitted): the scope's old buffers must be gone
            # after the next step — asserted here, not inferred
            probe = scope.get(names[0])
            for feed in (b1, b0, b1, b0, b1):
                t0 = time.perf_counter()
                out, = exe.run(main, feed=feed, fetch_list=[loss])
                step_ms.append((time.perf_counter() - t0) * 1e3)
                losses.append(float(out.reshape(-1)[0]))
            require_donated(probe)

            t0 = time.perf_counter()
            out, = exe.run_loop(main, feed=b0, fetch_list=[loss], steps=4)
            loop_first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            out, = exe.run_loop(main, feed=b0, fetch_list=[loss], steps=4)
            loop_ms = (time.perf_counter() - t0) * 1e3 / 4
            losses.append(float(out.reshape(-1)[0]))

            require(all(np.isfinite(losses)), "non-finite loss: %s" % losses)
            # b0 before any update vs b0 after 13 more updates
            require(losses[-1] < losses[0],
                    "loss on the repeated batch did not fall: %s" % losses)
            for n in names:
                require_on_chip(scope.get(n), "persistable %r" % n)
            result_dev = where(scope.get(names[0]))
        steady = float(np.median(step_ms[1:]))
        emit("train", model="resnet50", batch=TRAIN["batch_size"],
             compile_s=round(first_s - steady / 1e3, 2),
             step_ms=round(steady, 2),
             run_loop_compile_s=round(loop_first_s - 4 * loop_ms / 1e3, 2),
             run_loop_step_ms=round(loop_ms, 2),
             losses=[round(x, 4) for x in losses],
             peak_bytes_in_use=peak_bytes(devs[0]),
             persistables=len(names), donated=True, device=result_dev)
    finally:
        fluid.set_amp(False)


# ---------------------------------------------------------------------------
# phase 2: the generation server
# ---------------------------------------------------------------------------

def build_artifact(root, seed):
    from paddle_tpu.inference.decode import build_tiny_decode_model
    return build_tiny_decode_model(os.path.join(root, "lm"), seed=seed,
                                   **SERVE)


def prompts(seed, lens):
    rng = np.random.RandomState(seed + 1)
    # token 0 is eos: keep it out of the prompts
    return [rng.randint(1, SERVE["vocab_size"], n).astype(np.int32)
            for n in lens]


def stream_all(endpoint, model, prompt_list):
    """One infer_stream client per prompt, all in flight together.
    Returns [(tokens, terminal_frame_info)] in prompt order."""
    from paddle_tpu.serving.server import ServingClient
    out = [None] * len(prompt_list)
    errors = []

    def one(i):
        try:
            client = ServingClient(endpoint)
            toks = []
            for delta in client.infer_stream(model, prompt_list[i],
                                             max_new_tokens=NEW_TOKENS):
                toks.extend(delta)
            out[i] = (toks, client.last_stream_info)
            client.close()
        except BaseException as e:     # re-raised on the main thread
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompt_list))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    require(all(not t.is_alive() for t in threads), "a stream hung")
    return out, wall


def check_streams(results, eos):
    for toks, info in results:
        require(info is not None and info.get("done")
                and info.get("finish_reason") in ("length", "eos"),
                "stream ended without its terminal frame: %r" % (info,))
        require(info["new_tokens"] == len(toks) and 1 <= len(toks)
                and (len(toks) == NEW_TOKENS or toks[-1] == eos),
                "stream of %d tokens, terminal frame %r" % (len(toks), info))


@contextlib.contextmanager
def reference_attention():
    """Rebind `pallas_kernels.decode_attention` to its plain-XLA reference
    (at full fp32 matmul precision — on a TPU the default would round the
    oracle's own einsums to bf16) for predictors TRACED inside the block:
    decode.py imports the name at trace time.  The AOT store is switched off
    meanwhile — its fingerprint does not see the rebinding."""
    import jax
    from paddle_tpu import flags
    from paddle_tpu.ops import pallas_kernels as pk

    def ref(q, k_cache, v_cache, lengths, scale=None, block_kv=None,
            interpret=None, kv_scales=None, layer=None):
        if layer is not None:       # the step hands it the stacked table
            k_cache, v_cache = k_cache[layer], v_cache[layer]
        with jax.default_matmul_precision("highest"):
            return pk.decode_attention_reference(
                q, k_cache, v_cache, lengths, scale=scale,
                kv_scales=kv_scales)

    kernel, store_on = pk.decode_attention, flags.FLAGS.compile_cache
    pk.decode_attention = ref
    flags.set_flags({"compile_cache": False})
    try:
        yield
    finally:
        pk.decode_attention = kernel
        flags.set_flags({"compile_cache": store_on})


def teacher_forced_logits(pred, prompt_list, served, n_slots):
    """Replay the served streams on a fresh session of `pred`: prefill each
    prompt into its own slot, then step with the SERVED token forced as every
    slot's input.  Returns (first_tokens, [logits [n_prompts, vocab] per
    step])."""
    sess = pred.new_session(n_slots)
    firsts = [sess.prefill(i, p) for i, p in enumerate(prompt_list)]
    steps = max(len(t) for t in served) - 1
    out = []
    for t in range(steps):
        for i, toks in enumerate(served):
            if t < len(toks) - 1:
                sess.last_tokens[i] = toks[t]
            elif sess.active[i]:
                sess.free(i)
        _, logits = sess.decode_logits()
        out.append(logits[:len(served)])
    return firsts, out


def check_bounded_stream(call, ref, operands, n_slots, S, bkv, what, tol):
    """A decode kernel whose stream stops at a slot's length, compiled for
    the chip, alone: `call(*operands, lengths)` at SHORT lengths (all inside
    a slot's first block), MIXED ones (on and around the kernel's block
    edges, 1 and S among them) and FULL rows: against `ref` (the max abs
    error is returned, held under `tol`) and, bit for bit, against the same
    kernel made to stream whole rows, which is what `decode_attention` did
    before its stream stopped at a slot's length (`pk.kv_last_block`
    answering "the last block" for every length; the mask follows the true
    lengths).  Also returns the microseconds a call of each, by length
    profile."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    edges = sorted({1, 2, bkv - 1, bkv, bkv + 1, S // 2, S - 1, S})
    profiles = {"short": [1 + (7 * i) % bkv for i in range(n_slots)],
                "mixed": (edges * n_slots)[:n_slots],
                "full": [S] * n_slots}
    lengths = jnp.asarray(profiles["mixed"], jnp.int32)

    def compiled():
        kernel = jax.jit(call).lower(*operands, lengths).compile()
        require_mosaic(kernel.as_text(), 1, what)
        return kernel

    bounded = compiled()
    rule = pk.kv_last_block
    pk.kv_last_block = lambda n, bkv, n_blocks, xp=np: n_blocks - 1 + 0 * n
    try:
        whole = compiled()
    finally:
        pk.kv_last_block = rule

    def timed(kernel, n):
        jax.block_until_ready(kernel(*operands, n))
        t0 = time.perf_counter()
        for _ in range(20):
            out = kernel(*operands, n)
        jax.block_until_ready(out)
        return out, round((time.perf_counter() - t0) / 20 * 1e6, 1)

    ref, err, us = jax.jit(ref), 0.0, {}
    for name, profile in sorted(profiles.items()):
        n = jnp.asarray(profile, jnp.int32)
        got, us[name] = timed(bounded, n)
        want, us[name + "_whole_rows"] = timed(whole, n)
        require(bool(jnp.array_equal(got, want)),
                "%s (%s lengths) differs from the whole-row stream by %.3g"
                % (what, name, float(jnp.max(jnp.abs(got - want)))))
        err = max(err, float(jnp.max(jnp.abs(got - ref(*operands, n)))))
    require(err <= tol, "%s is %.3g from its reference" % (what, err))
    return err, us


def check_decode_kernel(geometry, kv_dtype, seed):
    """decode_attention over a slot table of `geometry` = (slots, S, query
    heads, K/V heads, head size), a position one flat row of K/V heads x
    head size values as a session's table holds it, and cache dtype
    `kv_dtype` (`check_bounded_stream`)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_tuning
    from paddle_tpu.ops import pallas_kernels as pk
    n_slots, S, H, Hc, Dh = geometry
    bkv = attention_tuning.get_decode_config(S, Dh, kv_dtype)
    kq, kk, kv_, ks = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
    q = jax.random.normal(kq, (n_slots, H, Dh), jnp.float32)
    if kv_dtype == "int8":
        k = jax.random.randint(kk, (n_slots, S, Hc * Dh), -127, 128, jnp.int8)
        v = jax.random.randint(kv_, (n_slots, S, Hc * Dh), -127, 128,
                               jnp.int8)
        scales = jax.random.uniform(ks, (2, H), jnp.float32, 0.5, 1.5) / 127
    else:
        k = jax.random.normal(kk, (n_slots, S, Hc * Dh), jnp.float32)
        v = jax.random.normal(kv_, (n_slots, S, Hc * Dh), jnp.float32)
        scales = None

    def ref(q, k, v, n):
        with jax.default_matmul_precision("highest"):
            return pk.decode_attention_reference(q, k, v, n,
                                                 kv_scales=scales)

    return check_bounded_stream(
        lambda q, k, v, n: pk.decode_attention(
            q, k, v, n, kv_scales=scales,
            interpret=REQUIRED_PLATFORM != "tpu"),
        ref, (q, k, v), n_slots, S, bkv,
        "decode_attention (%s cache)" % kv_dtype, TOL_DECODE_KERNEL)


def check_latent_kernel(geometry, seed):
    """latent_decode_attention over a latent slot table of `geometry` =
    (slots, S, heads, row values, value lanes), its rows padded to the
    tile's lanes as one TPU device holds them (`check_bounded_stream`)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_tuning
    from paddle_tpu.ops import pallas_kernels as pk
    n_slots, S, H, R, V = geometry
    Rp = -(-R // 128) * 128
    scale = 1.0 / np.sqrt(192.0)
    bkv = attention_tuning.get_decode_config(S, Rp, "float32")
    kq, kt = jax.random.split(jax.random.PRNGKey(seed + 3))
    lanes = jnp.arange(Rp) < R

    def drawn(key, shape):      # bf16 numbers; the pad lanes exact zeros
        x = jax.random.normal(key, shape + (Rp,), jnp.float32)
        return jnp.where(lanes, x, 0.0).astype(jnp.bfloat16).astype(
            jnp.float32)

    def ref(q, t, n):
        with jax.default_matmul_precision("highest"):
            return pk.latent_decode_attention_reference(q, t, n, V, scale)

    return check_bounded_stream(
        lambda q, t, n: pk.latent_decode_attention(
            q, t, n, V, scale, interpret=REQUIRED_PLATFORM != "tpu"),
        ref, (drawn(kq, (n_slots, H)), drawn(kt, (n_slots, S))), n_slots, S,
        bkv, "latent_decode_attention", TOL_LATENT_KERNEL)


def check_ssm_kernel(table, seed):
    """ssm_update on layer 1 of a donated scanned-state table `table` =
    (layers, slots, heads, head size, state, groups), a quarter of the
    slots idle, against its reference: the state it writes back bit for
    bit (both compute decay * S + dt x (outer) B element by element in
    fp32), a slot that does not run and the other layer untouched, the
    read-out to TOL_SSM_READOUT_REL, one Mosaic call and the table's
    buffer given up.  Returns (relative read-out error, microseconds a
    call)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    L, N, Hs, P, Ns, G = table
    ks = jax.random.split(jax.random.PRNGKey(seed + 5), 5)
    ss = jax.random.normal(ks[0], (L, N, Hs, P, Ns), jnp.float32)
    ops = (jnp.exp(-2.0 * jax.random.uniform(ks[1], (N, Hs))),
           jax.random.normal(ks[2], (N, Hs, P)),
           jax.random.normal(ks[3], (N, G, Ns)),
           jax.random.normal(ks[4], (N, G, Ns)),
           jnp.arange(N) % 4 != 1)
    want_y, want = jax.jit(
        lambda t, *o: pk.ssm_update_reference(t, *o, 1))(ss, *ops)
    kernel = jax.jit(
        lambda t, *o: pk.ssm_update(t, *o, 1,
                                    interpret=REQUIRED_PLATFORM != "tpu"),
        donate_argnums=(0,))
    held = ss + 0.0
    compiled = kernel.lower(held, *ops).compile()
    require_mosaic(compiled.as_text(), 1, "ssm_update")
    y, got = jax.block_until_ready(compiled(held, *ops))
    require(held.is_deleted(), "ssm_update did not take the table in place")
    require(bool(jnp.all(got == want)),
            "ssm_update's state is not its reference's bit for bit")
    idle = ~np.asarray(ops[-1])
    require(bool(jnp.all(got[0] == ss[0]))
            and bool(jnp.all(got[1][idle] == ss[1][idle]))
            and not bool(jnp.any(y[idle])),
            "ssm_update touched a slot that does not run, or another layer")
    err = float(jnp.max(jnp.abs(y - want_y)) / jnp.max(jnp.abs(want_y)))
    require(err <= TOL_SSM_READOUT_REL, "ssm_update read-out error %.4g" % err)
    t0 = time.perf_counter()
    for _ in range(8):
        y, got = compiled(got, *ops)
    jax.block_until_ready(got)
    return err, round((time.perf_counter() - t0) / 8 * 1e6, 1)


def check_sparse_prefill_kernel(shape, seed):
    """sparse_prefill_attention on one chunk of a prefill at `shape`
    (SPARSE_PREFILL), every query's selection its own block, the first and
    `topk` - 2 drawn blocks in sight, against its reference (the parent's
    plain-XLA running softmax) on the prompt's positions: one Mosaic call,
    the blocks of queries wholly past the prompt zeros.  Returns (largest
    absolute error, microseconds a call, the reference's)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    C, B, H, Hc, D, block, chunk, n, topk = (shape[k] for k in (
        "C", "B", "H", "Hc", "D", "block", "chunk", "true_len", "topk"))
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 4)
    q = jax.random.normal(ks[0], (C, H, D), jnp.float32)
    live = (jnp.arange(B) < n)[:, None]
    k, v = (jax.random.normal(kk, (B, Hc * D), jnp.float32) * live
            for kk in ks[1:3])
    own = (chunk * C + jnp.arange(C)) // block                    # [C]
    drawn = jax.random.uniform(ks[3], (C, Hc, B // block))
    blocks = jnp.arange(B // block)
    drawn = jnp.where(blocks <= own[:, None, None], drawn, 2.0)
    sel = (drawn <= jnp.sort(drawn, axis=-1)[..., topk - 3:topk - 2]) & (
        blocks <= own[:, None, None])
    sel = (sel | (blocks == 0) | (blocks == own[:, None, None])).transpose(
        1, 2, 0)                                          # [Hc, blocks, C]
    kernel = jax.jit(lambda *o: pk.sparse_prefill_attention(
        *o, chunk, n, block, interpret=REQUIRED_PLATFORM != "tpu"))
    compiled = kernel.lower(q, k, v, sel).compile()
    require_mosaic(compiled.as_text(), 1, "sparse_prefill_attention")
    got = jax.block_until_ready(compiled(q, k, v, sel))
    reference = jax.jit(lambda *o: pk.sparse_prefill_attention_reference(
        *o, chunk, block)).lower(q, k, v, sel).compile()
    want = jax.block_until_ready(reference(q, k, v, sel))
    rows = n - chunk * C
    err = float(jnp.max(jnp.abs(got[:rows] - want[:rows])))
    require(err <= TOL_SPARSE_PREFILL_KERNEL and bool(
        jnp.all(jnp.isfinite(got))),
        "sparse_prefill_attention error %.4g" % err)
    Qb = pk.sparse_prefill_tiles(C, B, H // Hc, D, block, mosaic=True)[0]
    require(not bool(jnp.any(got[-(-rows // Qb) * Qb:])),
            "sparse_prefill_attention wrote past the prompt's query blocks")
    us = []
    for fn in (compiled, reference):
        t0 = time.perf_counter()
        for _ in range(4):
            out = fn(q, k, v, sel)
        jax.block_until_ready(out)
        us.append(round((time.perf_counter() - t0) / 4 * 1e6, 1))
    return err, us[0], us[1]


def check_window(pred, n_slots, prompt_list, what):
    """A window of `decode.STEP_WINDOW` trips against as many one-trip
    dispatches of the same step executable, from the same admissions: equal
    tokens, and equal tables bit for bit, before the committed length and
    past it.  A stream that meets EOS stops at its own trip and sits the
    rest of the window out (it is held, not run, in the one-trip session
    from that trip on).  Returns the trips the window ran (fewer than the
    window only if every stream met EOS)."""
    import jax.numpy as jnp
    from paddle_tpu.inference.decode import STEP_WINDOW

    def admitted():
        sess = pred.new_session(n_slots)
        for i, p in enumerate(prompt_list):
            sess.prefill(i, p)
        return sess
    live = len(prompt_list)
    win = admitted()
    toks, counts, trips = win.decode_fused(STEP_WINDOW)
    ended = [i for i in range(live) if counts[i] < STEP_WINDOW]
    require(1 <= trips == counts.max() and counts[:live].min() >= 1
            and not counts[live:].any()
            and all(toks[i, counts[i] - 1] == pred.eos_id for i in ended),
            "the %s window ran %d trips and emitted %s"
            % (what, trips, counts.tolist()))
    one = admitted()
    steps = []
    for t in range(trips):
        one.active[:live] = counts[:live] > t
        steps.append(one.decode())
    one.active[:live] = True
    steps = np.stack(steps, axis=1)
    ran = np.arange(trips)[None] < counts[:live, None]
    require(np.array_equal(toks[:live, :trips][ran], steps[:live][ran])
            and not toks[:live, :trips][~ran].any(),
            "the %s window's tokens differ from %d one-trip dispatches: "
            "%s against %s" % (what, trips, toks[:live, :trips].tolist(),
                               steps[:live].tolist()))
    require(np.array_equal(win.lengths, one.lengths)
            and np.array_equal(win.last_tokens, one.last_tokens),
            "the %s window left other lengths or last tokens" % what)
    for name, a, b in (("K", win._kc, one._kc), ("V", win._vc, one._vc)):
        require(bool(jnp.array_equal(a, b)),
                "the %s window's %s table differs from the one-trip "
                "dispatches'" % (what, name))
    return trips


def serve_one(srv, artifact, kv, seed, devs):
    """Serve the four prompts with KV-cache dtype `kv`, then hold the result
    to the reference-attention twin of the same model."""
    from paddle_tpu.inference.decode import GenerativePredictor

    name = "lm_" + kv
    t0 = time.perf_counter()
    entry = srv.registry.load_model(name, artifact, kv_cache_dtype=kv)
    load_s = time.perf_counter() - t0
    pred, n_slots = entry.predictor, entry.batcher.n_slots
    plist = prompts(seed, PROMPT_LENS)
    results, wall = stream_all(srv.endpoint, name, plist)
    check_streams(results, pred.eos_id)
    served = [toks for toks, _ in results]
    n_tok = sum(len(t) for t in served)

    _, heads, head_size, _ = pred._dims()
    kernel_err, kernel_us = check_decode_kernel(
        (n_slots, pred.max_seq_len, heads, heads, head_size),
        pred.kv_cache_dtype, seed)

    # the step executable the lane ran holds the Mosaic kernel (the lane's
    # jitted callable, lowered and compiled for the same arguments: a
    # persistent-cache hit)
    sess = pred.new_session(n_slots)
    # no slot live, budget 0, one trip: the dispatch's shape, not its work
    args = (pred._state, sess._kc, sess._vc, sess.lengths,
            sess.last_tokens, sess.active, np.zeros(n_slots, np.int32),
            np.int32(1))
    step = pred.step_fn(n_slots)
    n_calls = require_mosaic(step.lower(*args).compile().as_text(),
                             pred.meta["n_layers"],
                             "the served %s step executable" % kv)
    # the step consumes the tables it is given (donated) and hands them
    # back updated in place: take them back, as the session does
    toks_dev, sess._kc, sess._vc = step(*args)
    require_on_chip(toks_dev, "decode step output")
    require(args[1].is_deleted() and args[2].is_deleted(),
            "the %s step did not consume the slot table it was given"
            % kv)
    del sess, args
    window_trips = check_window(pred, n_slots, plist, kv)

    # same prefix, kernel step vs reference-attention step
    k_firsts, k_logits = teacher_forced_logits(pred, plist, served, n_slots)
    with reference_attention():
        ref = GenerativePredictor(artifact, kv_cache_dtype=kv)
        r_firsts, r_logits = teacher_forced_logits(ref, plist, served,
                                                   n_slots)
        ref_text = ref.step_logits_fn(n_slots).as_text()
    require("tpu_custom_call" not in ref_text,
            "the reference step still holds the Mosaic kernel")
    # prefill holds no kernel: all three programs are one and the same
    firsts = [t[0] for t in served]
    require(k_firsts == firsts and r_firsts == firsts,
            "prefill tokens differ: served %s, kernel %s, reference %s"
            % (firsts, k_firsts, r_firsts))
    max_diff, max_gap, exact, total = 0.0, 0.0, len(served), len(served)
    for t, (kl, rl) in enumerate(zip(k_logits, r_logits)):
        for i, toks in enumerate(served):
            if t + 1 >= len(toks):
                continue
            max_diff = max(max_diff, float(np.max(np.abs(kl[i] - rl[i]))))
            max_gap = max(max_gap,
                          float(rl[i].max() - rl[i, toks[t + 1]]))
            exact += int(np.argmax(rl[i]) == toks[t + 1])
            total += 1
    require(max_diff <= TOL_LOGITS,
            "kernel vs reference logits differ by %.4g > %.4g"
            % (max_diff, TOL_LOGITS))
    # a served token that is not the reference's top-1 is a near-tie
    require(max_gap <= 2 * TOL_LOGITS,
            "a served token is %.4g below the reference's top-1" % max_gap)
    require(exact >= MIN_TOP1_AGREEMENT * total,
            "reference top-1 equals the served token at only %d/%d "
            "positions" % (exact, total))
    emit("serve", kv_cache_dtype=kv, streams=len(served), tokens=n_tok,
         load_and_warm_s=round(load_s, 2), compile_cache=entry.compile_cache,
         ms_per_token=round(wall * 1e3 / n_tok, 2),
         stream_wall_s=round(wall, 2), mosaic_calls_in_step=n_calls,
         window_trips_equal_to_one_trip_dispatches=window_trips,
         decode_kernel_max_err=float("%.3g" % kernel_err),
         tol_decode_kernel=TOL_DECODE_KERNEL,
         decode_kernel_equals_whole_row_stream=True,
         decode_kernel_us=kernel_us,
         max_logit_diff_vs_reference=round(max_diff, 5),
         tol_logits=TOL_LOGITS,
         max_served_gap_below_reference_top1=round(max_gap, 5),
         reference_top1_equal="%d/%d" % (exact, total),
         peak_bytes_in_use=peak_bytes(devs[0]), device=where(toks_dev))


def phase_serve(seed, devs):
    from paddle_tpu.serving.server import InferenceServer
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    srv = None
    try:
        artifact = build_artifact(root, seed)
        srv = InferenceServer("127.0.0.1:0").start()
        for kv in ("float32", "int8"):
            serve_one(srv, artifact, kv, seed, devs)
    finally:
        if srv is not None:
            srv.shutdown(drain=False, timeout=10.0)
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# phase 2b: another block on the same serving path
# ---------------------------------------------------------------------------

def olmoe_meta():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", "olmoe_1b_7b.json")) as f:
        return dict(json.load(f)["model"], n_layers=OLMOE_LAYERS,
                    prefill_buckets=[512, 1024])


def mosaic_calls_by_kind(text):
    """(decode_attention calls, XLA grouped-matmul kernels) among the Mosaic
    custom calls of an optimized step executable: the grouped matmuls of
    `jax.lax.ragged_dot` are named `ragged-dot-*`, the Pallas kernel by its
    enclosing function."""
    calls = [ln.split(" = ", 1)[0].strip().lstrip("%")
             for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    grouped = [c for c in calls if c.startswith("ragged-dot-none")]
    other = [c for c in calls if not c.startswith("ragged-dot")]
    return len(other), len(grouped)


def phase_serve_olmoe(seed, devs):
    import jax
    import jax.numpy as jnp
    from benchmark.reference import olmoe_1b_7b as reference
    from paddle_tpu.inference.decode import save_decode_model
    from paddle_tpu.serving.server import InferenceServer
    meta = olmoe_meta()
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    srv = None
    try:
        state = reference.make_state_on_device(meta, seed)
        artifact = save_decode_model(
            os.path.join(root, "olmoe"),
            {n: np.asarray(v) for n, v in state.items()}, meta)
        srv = InferenceServer("127.0.0.1:0").start()
        t0 = time.perf_counter()
        entry = srv.registry.load_model("olmoe", artifact,
                                        decode_slots=OLMOE_SLOTS)
        load_s = time.perf_counter() - t0
        pred = entry.predictor
        plist = prompts(seed, OLMOE_PROMPT_LENS)
        results, wall = stream_all(srv.endpoint, "olmoe", plist)
        check_streams(results, pred.eos_id)
        served = [toks for toks, _ in results]
        n_tok = sum(len(t) for t in served)

        sess = pred.new_session(OLMOE_SLOTS)
        args = (pred._state, sess._kc, sess._vc, sess.lengths,
                sess.last_tokens, sess.active,
                np.zeros(OLMOE_SLOTS, np.int32), np.int32(1))
        step = pred.step_fn(OLMOE_SLOTS)
        n_att, n_grouped = mosaic_calls_by_kind(
            step.lower(*args).compile().as_text())
        require(n_att == OLMOE_LAYERS,
                "the served OLMoE step holds %d Mosaic decode_attention "
                "calls for %d layers" % (n_att, OLMOE_LAYERS))
        require(n_grouped == 3 * OLMOE_LAYERS,
                "the served OLMoE step holds %d grouped-matmul kernels, "
                "expected 3 a layer" % n_grouped)
        toks_dev, sess._kc, sess._vc = step(*args)
        require_on_chip(toks_dev, "decode step output")
        require(args[1].is_deleted() and args[2].is_deleted(),
                "the OLMoE step did not consume the slot table it was "
                "given")
        del sess, args
        window_trips = check_window(pred, OLMOE_SLOTS, plist, "OLMoE")

        firsts, logits = teacher_forced_logits(pred, plist, served,
                                               OLMOE_SLOTS)
        require(firsts == [t[0] for t in served],
                "prefill tokens differ from the served ones")
        ref = jax.jit(lambda st, t: reference.forward(st, t, meta))
        kept = flipped = 0.0
        n_near = n_flipped = n_pos = 0
        for i, (p, toks) in enumerate(zip(plist, served)):
            seq = np.zeros(1024, np.int32)
            seq[:len(p)] = p
            seq[len(p):len(p) + len(toks)] = toks
            want, gaps = (np.asarray(a) for a in ref(state,
                                                     jnp.asarray(seq)))
            for t in range(len(toks) - 1):
                pos = len(p) + t         # predicts served token t + 1
                d = float(np.max(np.abs(logits[t][i] - want[pos])))
                near_tie = gaps[pos].min() < OLMOE_ROUTER_GAP
                n_pos, n_near = n_pos + 1, n_near + int(near_tie)
                if d > TOL_LOGITS and near_tie:
                    n_flipped, flipped = n_flipped + 1, max(flipped, d)
                else:
                    kept = max(kept, d)
        require(kept <= TOL_LOGITS and flipped <= OLMOE_TOL_FLIPPED,
                "OLMoE logits differ from the reference by %.4g (bound "
                "%.4g); on router near-ties by %.4g (bound %.4g)"
                % (kept, TOL_LOGITS, flipped, OLMOE_TOL_FLIPPED))
        require(n_flipped <= OLMOE_MAX_FLIPPED_SHARE * n_pos,
                "%d of %d positions miss the bound as router near-ties"
                % (n_flipped, n_pos))
        emit("serve_olmoe", layers=OLMOE_LAYERS, streams=len(served),
             tokens=n_tok, load_and_warm_s=round(load_s, 2),
             compile_cache=entry.compile_cache, block=pred.block,
             ms_per_token=round(wall * 1e3 / n_tok, 2),
             mosaic_decode_attention_calls=n_att,
             grouped_matmul_kernels=n_grouped,
             window_trips_equal_to_one_trip_dispatches=window_trips,
             max_logit_diff_vs_reference=round(kept, 5),
             tol_logits=TOL_LOGITS,
             router_near_ties="%d/%d" % (n_near, n_pos),
             router_gap=OLMOE_ROUTER_GAP,
             near_ties_that_missed_the_bound="%d/%d" % (n_flipped, n_pos),
             their_max_logit_diff=round(flipped, 5),
             tol_flipped=OLMOE_TOL_FLIPPED,
             peak_bytes_in_use=peak_bytes(devs[0]))
    finally:
        if srv is not None:
            srv.shutdown(drain=False, timeout=10.0)
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# phase 3: the other two tiled_contraction families
# ---------------------------------------------------------------------------

def phase_kernels(seed, devs):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.parallel.ring_attention import local_attention

    # forced, not inferred from the backend: False wherever the smoke passes
    interpret = REQUIRED_PLATFORM != "tpu"
    key = jax.random.PRNGKey(seed)
    B, S, H, D = (FLASH[k] for k in "BSHD")
    q, k, v, ct = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
                   for kk in jax.random.split(key, 4))

    def fwd_bwd(attn):
        # ct is an ARGUMENT: closed over, its 33 MB would be baked into the
        # executable as a constant (and into the persistent cache entry)
        def f(q, k, v, ct):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(ct.astype(out.dtype))
        return f

    flash = jax.jit(fwd_bwd(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, interpret=interpret)))

    def ref_attn(q, k, v):
        with jax.default_matmul_precision("highest"):
            return local_attention(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=True)

    t0 = time.perf_counter()
    compiled = flash.lower(q, k, v, ct).compile()
    flash_compile_s = time.perf_counter() - t0
    # forward, dq and dkv kernels
    n_calls = require_mosaic(compiled.as_text(), 3, "flash fwd+bwd")
    got = jax.block_until_ready(compiled(q, k, v, ct))
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(q, k, v, ct))
    flash_ms = (time.perf_counter() - t0) * 1e3
    want = jax.jit(fwd_bwd(ref_attn))(q, k, v, ct)
    err_fwd = float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - want[0])))
    require(err_fwd <= TOL_FLASH_FWD, "flash fwd error %.4g" % err_fwd)
    err_bwd = 0.0
    for g, w in zip(got[1:], want[1:]):
        rel = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                    / jnp.max(jnp.abs(w)))
        err_bwd = max(err_bwd, rel)
    require(err_bwd <= TOL_FLASH_BWD_REL, "flash bwd error %.4g" % err_bwd)
    require_on_chip(got[0], "flash attention output")
    emit("kernels", kernel="flash_attention_fwd_bwd", shape=FLASH,
         dtype="bfloat16", causal=True, compile_s=round(flash_compile_s, 2),
         step_ms=round(flash_ms, 3), mosaic_calls=n_calls,
         max_abs_err_fwd=round(err_fwd, 5), tol_fwd=TOL_FLASH_FWD,
         max_rel_err_bwd=round(err_bwd, 5), tol_bwd=TOL_FLASH_BWD_REL,
         peak_bytes_in_use=peak_bytes(devs[0]), device=where(got[0]))

    for kernel, geometry in (("decode_attention_grouped_query", DECODE_GQA),
                             ("decode_attention_wide_rows", DECODE_WIDE)):
        err, us = check_decode_kernel(geometry, "float32", seed)
        emit("kernels", kernel=kernel,
             shape=dict(zip(("slots", "S", "heads", "kv_heads", "D"),
                            geometry)), dtype="float32",
             max_abs_err=float("%.3g" % err), tol=TOL_DECODE_KERNEL,
             equals_whole_row_stream=True, call_us=us,
             peak_bytes_in_use=peak_bytes(devs[0]))

    err, us = check_latent_kernel(DECODE_LATENT, seed)
    emit("kernels", kernel="latent_decode_attention",
         shape=dict(zip(("slots", "S", "heads", "row", "values"),
                        DECODE_LATENT)), dtype="float32",
         max_abs_err=float("%.3g" % err), tol=TOL_LATENT_KERNEL,
         equals_whole_row_stream=True, call_us=us,
         peak_bytes_in_use=peak_bytes(devs[0]))

    err, us = check_ssm_kernel(SSM_TABLE, seed)
    emit("kernels", kernel="ssm_update",
         shape=dict(zip(("layers", "slots", "heads", "head_dim", "state",
                         "groups"), SSM_TABLE)), dtype="float32",
         max_rel_err_readout=float("%.3g" % err), tol=TOL_SSM_READOUT_REL,
         state_equals_reference=True, call_us=us,
         peak_bytes_in_use=peak_bytes(devs[0]))

    err, us, ref_us = check_sparse_prefill_kernel(SPARSE_PREFILL, seed)
    emit("kernels", kernel="sparse_prefill_attention", shape=SPARSE_PREFILL,
         dtype="float32", max_abs_err=float("%.3g" % err),
         tol=TOL_SPARSE_PREFILL_KERNEL, call_us=us, reference_call_us=ref_us,
         peak_bytes_in_use=peak_bytes(devs[0]))

    M, K, N = (DEQUANT[k] for k in "MKN")
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    x = jax.random.normal(kx, (M, K), jnp.bfloat16)
    w_q = jax.random.randint(kw, (K, N), -127, 128, jnp.int8)
    scale = jax.random.uniform(ks, (N,), jnp.float32, 0.5, 1.5) / 127.0
    dq = jax.jit(lambda x, w, s: pk.dequant_matmul(
        x, w, s, out_dtype=jnp.float32, interpret=interpret))
    t0 = time.perf_counter()
    compiled = dq.lower(x, w_q, scale).compile()
    dq_compile_s = time.perf_counter() - t0
    n_calls = require_mosaic(compiled.as_text(), 1, "dequant_matmul")
    got = jax.block_until_ready(compiled(x, w_q, scale))
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(x, w_q, scale))
    dq_ms = (time.perf_counter() - t0) * 1e3
    want = jax.jit(lambda x, w, s: pk.dequant_matmul_reference(
        x, w, s, out_dtype=jnp.float32))(x, w_q, scale)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    require(err <= TOL_DEQUANT_REL, "dequant_matmul error %.4g" % err)
    require_on_chip(got, "dequant_matmul output")
    emit("kernels", kernel="dequant_matmul", shape=DEQUANT,
         dtype="bfloat16xint8", compile_s=round(dq_compile_s, 2),
         step_ms=round(dq_ms, 3), mosaic_calls=n_calls,
         max_rel_err=round(err, 6), tol=TOL_DEQUANT_REL,
         peak_bytes_in_use=peak_bytes(devs[0]), device=where(got))


# ---------------------------------------------------------------------------
# --chips 4: what exists only across chips
# ---------------------------------------------------------------------------

def phase_parallel_train(seed, devs):
    """fluid.ParallelExecutor over every chip against the single-device
    Executor: same program, same initial state, same five batches."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet

    fluid.set_amp(True)
    try:
        main, startup, _, loss, _, _ = resnet.get_model(lr=1e-3, **TRAIN)
        batches = train_batches(seed, 5)
        perm = np.random.RandomState(seed + 3).permutation(
            TRAIN["batch_size"])
        persist = tuple(functionalizer.persistable_names(main))
        exe = fluid.Executor(fluid.TPUPlace(0))

        def one_chip(feeds, init=None):
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                if init is None:
                    exe.run(startup)
                    init = {n: np.asarray(scope.get(n)) for n in persist
                            if scope.get(n) is not None}
                else:
                    for n, v in init.items():
                        scope.set(n, v)
                return init, [float(exe.run(main, feed=b, fetch_list=[loss])
                                    [0].reshape(-1)[0]) for b in feeds]

        init, one = one_chip(batches)
        # the same chip, the same samples in another order: what
        # re-ordering the reductions alone does to this trajectory
        _, shuffled = one_chip([{k: v[perm] for k, v in b.items()}
                                for b in batches], init)
        floor = [abs(a - b) for a, b in zip(one, shuffled)]

        scope4 = fluid.Scope()
        for n, v in init.items():
            scope4.set(n, v)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main, scope=scope4)
        require(pe.device_count == len(devs),
                "ParallelExecutor took %d of %d devices"
                % (pe.device_count, len(devs)))
        t0 = time.perf_counter()
        four, step_ms = [], []
        for b in batches:
            t1 = time.perf_counter()
            out, = pe.run([loss.name], feed=b)
            step_ms.append((time.perf_counter() - t1) * 1e3)
            four.append(float(np.asarray(out).reshape(-1)[0]))
        first_s = time.perf_counter() - t0 - sum(step_ms[1:]) / 1e3

        # the executor's own feed sharding and its own jitted step
        feeds = pe._prepare_feeds(batches[0])
        shard_devs = sorted(s.device.id
                            for s in feeds["data"].addressable_shards)
        require(len(set(shard_devs)) == len(devs),
                "feed sharded over devices %s" % shard_devs)
        state = {n: scope4.get(n) for n in persist
                 if scope4.get(n) is not None}
        text = pe._get_jitted(tuple(sorted(feeds)), (loss.name,),
                              persist).lower(
            state, feeds, np.uint32(0)).compile().as_text()
        n_allreduce = text.count("all-reduce(") + text.count(
            "all-reduce-start(")
        require(n_allreduce >= 1, "no all-reduce in the SPMD step's HLO")
        for n, v in state.items():
            require_on_chip(v, "persistable %r" % n)

        deltas = [abs(a - b) for a, b in zip(one, four)]
        require(all(np.isfinite(one + four)), "non-finite loss")
        require(deltas[0] <= TOL_PARALLEL_STEP0
                and max(deltas) <= TOL_PARALLEL,
                "sharded trajectory left the single-device one: %s vs %s"
                % (four, one))
        emit("parallel_train", devices=pe.device_count,
             batch=TRAIN["batch_size"], compile_s=round(first_s, 2),
             step_ms=round(float(np.median(step_ms[1:])), 2),
             losses_1dev=[round(x, 5) for x in one],
             losses_4dev=[round(x, 5) for x in four],
             deltas=[float("%.3g" % d) for d in deltas],
             tol_step0=TOL_PARALLEL_STEP0, tol=TOL_PARALLEL,
             reorder_floor_1dev=[float("%.3g" % d) for d in floor],
             feed_shard_devices=shard_devs, all_reduces=n_allreduce,
             peak_bytes_in_use={d.id: peak_bytes(d) for d in devs})
    finally:
        fluid.set_amp(False)


def bytes_by_device():
    import jax
    out = {}
    for a in jax.live_arrays():
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def phase_replicas(seed, devs):
    """The phase-2 artifact behind `serving_replicas="auto"`: one lane per
    chip, eight requests spread by the router, tokens equal to one lane's."""
    from paddle_tpu.serving.server import InferenceServer
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    srv = None
    try:
        artifact = build_artifact(root, seed)
        plist = prompts(seed, PROMPT_LENS + PROMPT_LENS[::-1])
        srv = InferenceServer("127.0.0.1:0").start()
        entry = srv.registry.load_model("lm_one", artifact)
        results, _ = stream_all(srv.endpoint, "lm_one", plist)
        check_streams(results, entry.predictor.eos_id)
        one = [toks for toks, _ in results]
        srv.registry.unload_model("lm_one")
        del entry, results
        gc.collect()        # the one lane's params and cache leave device 0

        t0 = time.perf_counter()
        entry = srv.registry.load_model("lm_auto", artifact, replicas="auto")
        load_s = time.perf_counter() - t0
        require(len(entry.replicas) == len(devs)
                and sorted(p.device.id for p in entry.replicas)
                == sorted(d.id for d in devs),
                "replicas='auto' placed %s" % entry.device_labels())
        held = bytes_by_device()
        print(json.dumps({"bytes_by_device": held}), flush=True)
        require(all(held.get(d.id, 0) > 0 for d in devs),
                "a device holds nothing: %s" % held)
        require(held[devs[0].id] < 0.5 * sum(held.values()),
                "device 0 holds most of the bytes: %s" % held)
        results, wall = stream_all(srv.endpoint, "lm_auto", plist)
        check_streams(results, entry.predictor.eos_id)
        lanes = entry.batcher.replica_stats()
        require(all(l["rows"] > 0 for l in lanes),
                "the router left a lane idle: %s" % lanes)
        auto = [toks for toks, _ in results]
        require(auto == one, "replica streams differ from the one-lane "
                "run: %s vs %s" % (auto, one))
        n_tok = sum(len(t) for t in auto)
        emit("replicas", lanes=len(lanes), requests=len(plist),
             tokens=n_tok, load_and_warm_s=round(load_s, 2),
             compile_cache=entry.compile_cache,
             ms_per_token=round(wall * 1e3 / n_tok, 2),
             tokens_by_lane={l["device"]: l["rows"] for l in lanes},
             bytes_by_device=held, equal_to_one_lane=True,
             peak_bytes_in_use={d.id: peak_bytes(d) for d in devs})
    finally:
        if srv is not None:
            srv.shutdown(drain=False, timeout=10.0)
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths (needs >= 4 "
                         "chips); default 1: train, serve, kernels")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, batches and prompts")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        print("chip_smoke: jax found %s devices, no %s — not falling back"
              % (devs[0].platform, REQUIRED_PLATFORM), file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print("chip_smoke: --chips %d but jax found %d device(s)"
              % (args.chips, len(devs)), file=sys.stderr)
        return 3

    from paddle_tpu import compile_cache, native
    jax_cache = compile_cache.ensure_jax_cache()
    aot_dir = os.path.join(compile_cache.cache_root(),
                           compile_cache.AOT_SUBDIR)
    # [files, bytes]; jax's own LRU (JAX_COMPILATION_CACHE_MAX_SIZE, where
    # the machine sets it) may evict this run's first entries by its end
    before = dir_entries(jax_cache)
    emit("setup", jax=jax.__version__, device_kind=devs[0].device_kind,
         devices=len(devs), jax_cache_dir=jax_cache,
         jax_cache_entries_before=before,
         jax_cache_max_size=jax.config.jax_compilation_cache_max_size,
         aot_store=aot_dir, aot_entries_before=dir_entries(aot_dir)[0],
         native="library" if native.lib is not None else "python-fallback")

    t0 = time.perf_counter()
    phases = (phase_parallel_train, phase_replicas) if args.chips == 4 \
        else (phase_train, phase_serve, phase_serve_olmoe, phase_kernels)
    for phase in phases:
        phase(args.seed, devs)
        gc.collect()        # drop the phase's device arrays before the next
    emit("done", wall_s=round(time.perf_counter() - t0, 1),
         jax_cache_entries_before=before,
         jax_cache_entries_after=dir_entries(jax_cache),
         aot_entries_after=dir_entries(aot_dir)[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
