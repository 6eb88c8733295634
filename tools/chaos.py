"""Chaos harness: fault injection for the fault-tolerant runtime.

Scenarios (each is library API + CLI; the CLI prints PASS/FAIL lines and
exits nonzero on failure):

  crash-save   spawn a training child that checkpoints every step with a
               chaos pause inside the commit protocol, `kill -9` it mid-
               save for real, then prove the vault still serves a fully-
               committed last-good checkpoint (latest pointer intact,
               every CRC verifies, meta step == last committed step).
  bit-flip     commit a checkpoint, flip one bit in an array shard, and
               prove the load is REJECTED with an error naming exactly
               that array.
  nan-poison   train with the anomaly sentinel on and a poisoned batch
               (NaN features) injected mid-epoch; prove the bad steps
               are skipped (params revert) and the K-th consecutive bad
               step rolls back to the last-good checkpoint.
  drop-rpc     run a MasterClient conversation through a TCP proxy that
               kills the first connection mid-flight; prove the jittered
               retry re-dials and the lease protocol's resend/req_id
               dedup hands back exactly-once work.
  serving-overload
               flood an inference server (paddle_tpu/serving) through a
               FlakyProxy with slow-worker injection and a tiny
               admission queue; prove overflow is shed with an explicit
               ServerOverloaded and EVERY request resolves — shed, not
               hang (SERVING.md overload semantics).
  cache-commit kill -9 a child mid-commit of a persistent compile-cache
               entry (COMPILE_CACHE.md): the first bucket's entry
               commits cleanly, the second is interrupted at a named
               commit point.  Prove the store is left with the clean
               entry + only a stale _tmp dir, and that the next boot
               serves correctly, recompiles ONLY the interrupted entry
               (hit=1 miss=1), and sweeps the stale tmp.
  quantize-commit
               SIGKILL a child mid-PTQ-write of a quantized artifact
               (QUANTIZE.md): commit #1 lands cleanly, commit #2 is
               interrupted at a named point.  Prove the fp32 source AND
               the prior quantized artifact survive intact (every
               payload CRC verifies, probe replies bit-identical) and a
               recovery run re-commits and sweeps the stale tmp.
  decode-disconnect
               streaming-generation chaos (SERVING.md continuous
               batching): a client disconnect mid-stream and a deadline
               expiring MID-DECODE must each free the decode slot
               within a few steps (typed error frame + deadline_expired
               event for the latter), with zero wedged lanes and zero
               cross-request KV leakage — reused slots serve bit-exact
               greedy streams because freed slots are zeroed.
  decode-disconnect-int8
               the same scenario under the QUANTIZED slot table
               (QUANTIZE.md "Quantized KV cache", kv_cache_dtype=int8):
               freed slots hold exact int8 zeros, replays compare
               against a direct int8-cache session — zero leakage and
               bit-stability survive quantization.
  decode-disconnect-fused
               fused-decode boundary chaos (SERVING.md "Fused
               multi-step decode", fuse_steps=4): a disconnect
               MID-FUSED-WINDOW frees the slot at the next dispatch
               boundary (<= 3·N steps, zero wedged lanes), a deadline
               expiry overshoots by at most ~one fused dispatch (the
               EWMA trip clamp) with overshoot_ms stamped on the
               deadline_expired event, and boundary-freed slots serve
               bit-exact streams on reuse.
  backend-kill federated-serving chaos (SERVING.md "Federated
               serving"): N backend subprocesses behind an in-process
               front-door router, concurrent decode streams pinned
               across them by session affinity, then SIGKILL one
               backend mid-stream.  Prove ONLY the victim backend's
               in-flight streams fail — each with a typed StreamBroken
               naming the backend and the committed token count, zero
               hangs — survivors complete bit-exact, the lost lease is
               evicted within one heartbeat TTL, and a re-placed
               session lands on a survivor bit-exact with zero sheds.
  spec-fallback
               speculative-decoding chaos (SERVING.md): poison the
               draft predictor MID-STREAM (set_draft_poison) — the
               serving lane must degrade to target-only decode within
               that same round, the victim stream completes its full
               budget bit-identical to the fp32-only greedy decode,
               a spec_degraded event + counter fire, and post-degrade
               traffic keeps serving with zero wedged lanes.
  mesh-member-loss
               mesh-replica chaos (SERVING.md "Mesh replicas"): poison
               one member chip of a 2-chip sharded replica mesh
               mid-stream.  The victim lane must DIE, not wedge —
               in-flight streams on it fail typed (naming the lost
               member), the lane is marked dead (stats/health +
               mesh_lane_dead event) and skipped by admission, sibling
               mesh lanes stay bit-exact, and page + fault-in rebuilds
               the full mesh lane set from the persisted load spec.
               Runs twice: gather lanes, then FLAGS.mesh_tp lanes
               (loss lands mid-psum in the partitioned program; the
               rebuild must come back tensor-parallel).

  --smoke      crash-save (deterministic `exit` fault at every commit
               point) + bit-flip, fast enough for tier-1.

The injection points live in paddle_tpu/fluid/checkpoint.py (`_chaos`,
env `PADDLE_TPU_CHAOS="<point>=<action>[@<n>]"`); this tool is the
driver.  Reference motivation: the Go pserver/master survived worker
churn and crash-mid-checkpoint by construction (go/pserver/service.go
temp+fsync+rename, go/master lease recovery); these scenarios are the
repro's proof of the same properties.
"""

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CHAOS_POINTS = ("array_written", "arrays_written", "manifest_written",
                "committed", "latest_updated")
# compile-cache store commit points (paddle_tpu/compile_cache.py)
CACHE_POINTS = ("cc_exec_written", "cc_committed")
# PTQ artifact commit points (paddle_tpu/inference/quantize.py)
QUANT_POINTS = ("quant_arrays_written", "quant_committed")
# flight-recorder bundle commit point (paddle_tpu/obs/flightrec.py)
FLIGHT_POINTS = ("flight_committed",)


# ---------------------------------------------------------------------------
# shard corruption
# ---------------------------------------------------------------------------

def bit_flip(path, offset=None, bit=3):
    """Flip one bit of the file at `path` (default: middle byte) —
    the minimal corruption a CRC32 manifest must catch."""
    with open(path, "rb") as f:
        raw = bytearray(f.read())
    if not raw:
        raise ValueError("cannot bit-flip empty file %s" % path)
    if offset is None:
        offset = len(raw) // 2
    raw[offset] ^= (1 << bit)
    with open(path, "wb") as f:
        f.write(raw)
    return offset


def corrupt_array(ckpt_dir, array_name):
    """Bit-flip the named array's shard inside a committed checkpoint."""
    from paddle_tpu.fluid import checkpoint as ckpt
    manifest = ckpt.read_manifest(ckpt_dir)
    ent = manifest["arrays"][array_name]
    path = os.path.join(ckpt_dir, ent["file"])
    bit_flip(path)
    return path


# ---------------------------------------------------------------------------
# NaN poisoning
# ---------------------------------------------------------------------------

def nan_poison_reader(reader, poison_steps, nan_value=float("nan")):
    """Wrap a reader creator: batches whose index is in `poison_steps`
    have every float array replaced by NaN — the data-side gradient
    poisoning fault (a flaky preprocessing job, a corrupt shard read)."""
    import numpy as np
    poison_steps = frozenset(poison_steps)

    def _poison(sample):
        out = []
        for part in sample:
            arr = np.asarray(part)
            if arr.dtype.kind == "f":
                arr = np.full_like(arr, nan_value)
            out.append(arr)
        return tuple(out)

    def poisoned():
        for i, batch in enumerate(reader()):
            if i in poison_steps:
                yield [_poison(s) for s in batch]
            else:
                yield batch

    return poisoned


def slow_host_reader(reader, stall_ms):
    """Slow-host injection: every batch costs `stall_ms` of host wall
    clock before it is yielded — the training-side analogue of
    bench_serving's --chaos_slow_ms knob, a deterministic stand-in for
    expensive host preprocessing (decode, augment, a slow shard read).
    Feeding a trainer through this wrapped reader WITHOUT prefetch
    serializes the stall with every step; through
    reader.prefetch_to_device the stall lands on the prefetch thread
    and the pipeline hides it (tests/test_pipeline.py pins the delta)."""
    def slowed():
        for item in reader():
            time.sleep(stall_ms / 1000.0)
            yield item
    return slowed


# ---------------------------------------------------------------------------
# RPC drop: a TCP proxy that kills connections on demand
# ---------------------------------------------------------------------------

class FlakyProxy:
    """Forward TCP to `target`, killing the first `drop_first`
    connections after `drop_after_bytes` of server->client traffic —
    the client sees a mid-conversation connection reset, exactly what a
    master/pserver crash looks like from the wire."""

    def __init__(self, target, drop_first=1, drop_after_bytes=0):
        self.target = target
        self.drop_first = drop_first
        self.drop_after_bytes = drop_after_bytes
        self.dropped = 0
        self._lsock = socket.socket()
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(8)
        self._stop = False
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True)

    @property
    def endpoint(self):
        host, port = self._lsock.getsockname()
        return "%s:%d" % (host, port)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop = True
        try:
            self._lsock.close()
        except OSError:
            pass

    def _accept_loop(self):
        while not self._stop:
            try:
                cli, _ = self._lsock.accept()
            except OSError:
                return
            drop_this = self.dropped < self.drop_first
            if drop_this:
                self.dropped += 1
            threading.Thread(target=self._pump, args=(cli, drop_this),
                             daemon=True).start()

    def _pump(self, cli, drop_this):
        host, port = self.target.rsplit(":", 1)
        try:
            srv = socket.create_connection((host, int(port)), timeout=10)
        except OSError:
            cli.close()
            return
        seen = [0]

        def one_way(src, dst, count_down):
            try:
                while True:
                    data = src.recv(1 << 16)
                    if not data:
                        break
                    if count_down and drop_this:
                        seen[0] += len(data)
                        if seen[0] > self.drop_after_bytes:
                            # kill BOTH sides mid-flight; shutdown (not
                            # just close) so the victim's blocked recv
                            # wakes on FIN now, not at its socket timeout
                            for s in (cli, srv):
                                try:
                                    s.shutdown(socket.SHUT_RDWR)
                                except OSError:
                                    pass
                                s.close()
                            return
                    dst.sendall(data)
            except OSError:
                pass
            finally:
                try:
                    dst.shutdown(socket.SHUT_WR)
                except OSError:
                    pass

        t = threading.Thread(target=one_way, args=(srv, cli, True),
                             daemon=True)
        t.start()
        one_way(cli, srv, False)


# ---------------------------------------------------------------------------
# the training child (subprocess target for crash-save)
# ---------------------------------------------------------------------------

def _child_train(workdir, steps, chaos_spec=None, chaos_at_save=0):
    """Tiny deterministic fc-regression that checkpoints EVERY step into
    `workdir` — the victim process for kill-mid-save scenarios.  The
    chaos spec is armed only for save number `chaos_at_save` (1-based),
    so earlier saves commit cleanly and there IS a last-good to
    recover."""
    import numpy as np
    import paddle_tpu.fluid as fluid

    rng = np.random.RandomState(0)
    xs = rng.randn(8, 4).astype(np.float32)
    ys = xs.sum(axis=1, keepdims=True)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    for step in range(1, steps + 1):
        exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss])
        if chaos_spec and step == chaos_at_save:
            os.environ["PADDLE_TPU_CHAOS"] = chaos_spec
        fluid.io.save_checkpoint(exe, workdir, main_program=main,
                                 step=step, epoch=0,
                                 max_num_checkpoints=3)
        os.environ.pop("PADDLE_TPU_CHAOS", None)
        print("SAVED %d" % step, flush=True)
    print("DONE", flush=True)


def _spawn_child(workdir, steps, chaos_spec, chaos_at_save,
                 extra_env=None):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_CHAOS", None)  # armed by the child at the step
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child-train",
         workdir, "--steps", str(steps), "--chaos-spec", chaos_spec,
         "--chaos-at-save", str(chaos_at_save)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)


def _verify_last_good(workdir, min_step=None, max_step=None):
    """The recovery invariant: whatever the crash point, the vault must
    resolve to a FULLY-COMMITTED checkpoint whose every CRC verifies."""
    from paddle_tpu.fluid import checkpoint as ckpt
    latest = ckpt.latest_checkpoint(workdir)
    assert latest is not None, "no loadable checkpoint under %s" % workdir
    manifest = ckpt.verify_checkpoint_dir(latest)
    meta = ckpt.normalize_meta(manifest["meta"])
    if min_step is not None:
        assert meta["step"] >= min_step, \
            "last-good step %d < expected %d" % (meta["step"], min_step)
    if max_step is not None:
        assert meta["step"] <= max_step, \
            "last-good step %d > committed %d" % (meta["step"], max_step)
    return meta


# ---------------------------------------------------------------------------
# the compile-cache child (subprocess target for cache-commit)
# ---------------------------------------------------------------------------

def _child_cache(store_dir):
    """Compile-cache victim: a tiny fc Predictor with two batch buckets
    whose executables commit to the store at `store_dir` one after the
    other — the parent arms PADDLE_TPU_CHAOS with `@2` so commit #1 is
    clean and commit #2 is interrupted at the named point."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import compile_cache as cc
    from paddle_tpu.inference import AnalysisConfig, Predictor

    fluid.set_flags({"compile_cache_dir": store_dir,
                     "compile_cache": True})
    md = os.path.join(store_dir, "model")
    if not os.path.isdir(md):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            pred = fluid.layers.fc(input=x, size=4, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.save_inference_model(md, ["x"], [pred], exe,
                                       main_program=main)
    cfg = AnalysisConfig(model_dir=md)
    cfg.batch_size_buckets = (2, 4)
    p = Predictor(cfg)
    rng = np.random.RandomState(0)
    for i, b in enumerate((2, 4)):
        out, = p.run({"x": rng.randn(b, 8).astype(np.float32)})
        print("COMMITTED %d sum=%.6f" % (i + 1, float(out.sum())),
              flush=True)
    print("STATS %s" % json.dumps(cc.stats()), flush=True)
    print("DONE", flush=True)


def _spawn_cache_child(store_dir, chaos_spec=None):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_CHAOS", None)
    if chaos_spec:
        env["PADDLE_TPU_CHAOS"] = chaos_spec
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child-cache",
         store_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)


def scenario_cache_commit(workdir, point="cc_exec_written",
                          real_kill=True, verbose=True):
    """Kill a child mid-commit of compile-cache entry #2 at `point`,
    then prove the store invariants: (1) the interrupted commit left
    only a stale _tmp dir next to the intact entry #1, (2) a fresh boot
    serves bit-identical replies, recompiles ONLY the interrupted entry
    (hits=1, misses=1), and sweeps the stale tmp."""
    import json as _json
    from paddle_tpu import compile_cache as cc
    store = os.path.join(workdir, "cc_store")
    os.makedirs(store, exist_ok=True)
    action = "pause:120" if real_kill else "exit"
    spec = "%s=%s@2" % (point, action)
    proc = _spawn_cache_child(store, chaos_spec=spec)
    committed, sums = 0, []
    try:
        if real_kill:
            for line in proc.stdout:
                line = line.strip()
                if line.startswith("COMMITTED"):
                    committed = int(line.split()[1])
                    sums.append(line.split("sum=")[1])
                if line.startswith("CHAOS_PAUSE"):
                    os.kill(proc.pid, signal.SIGKILL)
                    break
            proc.wait(timeout=30)
        else:
            out, _ = proc.communicate(timeout=240)
            for line in out.splitlines():
                if line.startswith("COMMITTED"):
                    committed = int(line.split()[1])
                    sums.append(line.split("sum=")[1])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode != 0, \
        "child survived the kill (rc=0) — no fault injected"
    assert committed == 1, \
        "expected the crash during commit #2 (after 1 clean commit), " \
        "child reported %d" % committed
    store_cc = cc.CompileCache(root=store, xla_cache=False)
    entries = store_cc.entries()
    tmps = store_cc.stale_tmp_dirs()
    committed_ok = point == "cc_committed"
    want_entries = 2 if committed_ok else 1
    assert len(entries) == want_entries, \
        "store has %d committed entries after kill at %s, want %d" \
        % (len(entries), point, want_entries)
    assert committed_ok or len(tmps) >= 1, \
        "no stale _tmp dir left by the interrupted commit"
    bad = [k for k, err, _ in store_cc.verify() if err]
    assert not bad, "kill corrupted committed entries: %s" % bad
    # recovery boot: same store, no chaos — serves, recompiles only the
    # interrupted entry, sweeps the tmp
    proc2 = _spawn_cache_child(store)
    out2, _ = proc2.communicate(timeout=240)
    assert proc2.returncode == 0, out2[-2000:]
    assert "DONE" in out2, out2[-2000:]
    stats_line = [ln for ln in out2.splitlines()
                  if ln.startswith("STATS ")]
    st = _json.loads(stats_line[0][len("STATS "):])
    want_miss = 0 if committed_ok else 1
    assert st["hits"] == 2 - want_miss and st["misses"] == want_miss, \
        "recovery boot should recompile only the interrupted entry " \
        "(want hits=%d misses=%d), got %s" \
        % (2 - want_miss, want_miss, st)
    sums2 = [line.split("sum=")[1] for line in out2.splitlines()
             if line.startswith("COMMITTED")]
    assert sums and sums2[0] == sums[0], \
        "recovery reply differs from pre-kill reply: %s vs %s" \
        % (sums2[0], sums[0])
    assert len(store_cc.entries()) == 2, "entry not recompiled"
    assert not store_cc.stale_tmp_dirs(), \
        "stale tmp dirs not swept on recovery: %s" \
        % store_cc.stale_tmp_dirs()
    bad = [k for k, err, _ in store_cc.verify() if err]
    assert not bad, "recovered store fails verification: %s" % bad
    if verbose:
        print("PASS cache-commit point=%s kill=%s: 1 clean entry kept, "
              "recovery hits=%d misses=%d, tmp swept, store verifies"
              % (point, real_kill, st["hits"], st["misses"]))
    return st


# ---------------------------------------------------------------------------
# PTQ commit chaos (QUANTIZE.md)
# ---------------------------------------------------------------------------

_QUANT_PROBE = None  # lazy: the fixed reply probe batch


def _quant_probe_batch():
    import numpy as np
    return np.arange(32, dtype=np.float32).reshape(4, 8) / 32.0


def _child_quant(workdir):
    """Subprocess target (--child-quant): build (or reuse) a tiny fp32
    fc artifact, quantize it TWICE into the same sibling dir (commit #1
    clean, commit #2 is where the parent injects the fault), then serve
    one probe batch from the quantized artifact and print its sum."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.flags import set_flags
    set_flags({"compile_cache": False})
    src = os.path.join(workdir, "fc")
    if not os.path.exists(os.path.join(src, "__model__")):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(input=x, size=32, act="relu")
            pred = fluid.layers.fc(input=h, size=10, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.save_inference_model(src, ["x"], [pred], exe,
                                       main_program=main)
    from paddle_tpu.inference import (AnalysisConfig, Predictor,
                                      quantize_inference_model)
    s = None
    for i in range(2):
        s = quantize_inference_model(src, min_weight_elems=64)
        print("QUANTIZED %d ratio=%.4f" % (i + 1, s["bytes"]["ratio"]),
              flush=True)
    cfg = AnalysisConfig(model_dir=s["dst"])
    cfg.batch_size_buckets = (4,)
    out, = Predictor(cfg).run({"x": _quant_probe_batch()})
    print("REPLY sum=%.6f" % float(np.asarray(out, np.float64).sum()),
          flush=True)
    print("DONE", flush=True)


def _spawn_quant_child(workdir, chaos_spec=None):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_CHAOS", None)
    if chaos_spec:
        env["PADDLE_TPU_CHAOS"] = chaos_spec
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child-quant",
         workdir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)


def scenario_quantize_commit(workdir, point="quant_arrays_written",
                             real_kill=True, verbose=True):
    """SIGKILL a child mid-PTQ-write at `point` during quantized-commit
    #2, then prove: (1) the fp32 source artifact still loads and
    serves, (2) the PRIOR quantized artifact is intact (every payload
    CRC verifies, the probe reply is bit-identical to commit #1's), and
    (3) a recovery run re-quantizes cleanly, sweeps the stale tmp, and
    serves the same reply."""
    import glob as _glob
    import numpy as np
    os.makedirs(workdir, exist_ok=True)
    action = "pause:120" if real_kill else "exit"
    spec = "%s=%s@2" % (point, action)
    proc = _spawn_quant_child(workdir, chaos_spec=spec)
    committed = 0
    try:
        if real_kill:
            for line in proc.stdout:
                line = line.strip()
                if line.startswith("QUANTIZED"):
                    committed = int(line.split()[1])
                if line.startswith("CHAOS_PAUSE"):
                    os.kill(proc.pid, signal.SIGKILL)
                    break
            proc.wait(timeout=30)
        else:
            out, _ = proc.communicate(timeout=240)
            for line in out.splitlines():
                if line.startswith("QUANTIZED"):
                    committed = int(line.split()[1])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode != 0, \
        "child survived the kill (rc=0) — no fault injected"
    assert committed == 1, \
        "expected the crash during quantized commit #2 (after 1 clean " \
        "commit), child reported %d" % committed
    src = os.path.join(workdir, "fc")
    dst = src + "_int8"
    # (1) the fp32 source never moved — it still loads and serves
    from paddle_tpu.inference import AnalysisConfig, Predictor
    from paddle_tpu.inference import quantize as q
    cfg = AnalysisConfig(model_dir=src)
    cfg.batch_size_buckets = (4,)
    Predictor(cfg).run({"x": _quant_probe_batch()})
    # (2) the prior quantized artifact is intact, whatever the point
    bad = [(f, e) for f, e in q.verify_quantized_dir(dst) if e]
    assert not bad, "kill corrupted the quantized artifact: %s" % bad
    committed_ok = point == "quant_committed"
    tmps = _glob.glob(dst + ".tmp.*")
    assert committed_ok or tmps, \
        "no stale tmp dir left by the interrupted commit"
    cfgq = AnalysisConfig(model_dir=dst)
    cfgq.batch_size_buckets = (4,)
    out, = Predictor(cfgq).run({"x": _quant_probe_batch()})
    prior_sum = "%.6f" % float(np.asarray(out, np.float64).sum())
    # (3) recovery: re-quantize cleanly, sweep the tmp, same reply
    proc2 = _spawn_quant_child(workdir)
    out2, _ = proc2.communicate(timeout=240)
    assert proc2.returncode == 0, out2[-2000:]
    assert "DONE" in out2, out2[-2000:]
    reply = [ln for ln in out2.splitlines() if ln.startswith("REPLY ")]
    assert reply and reply[0].split("sum=")[1] == prior_sum, \
        "recovery reply differs from the intact artifact: %s vs %s" \
        % (reply, prior_sum)
    assert not _glob.glob(dst + ".tmp.*"), \
        "stale tmp dirs not swept on recovery"
    bad = [(f, e) for f, e in q.verify_quantized_dir(dst) if e]
    assert not bad, "recovered artifact fails verification: %s" % bad
    if verbose:
        print("PASS quantize-commit point=%s kill=%s: fp32 + prior "
              "quantized artifact intact, recovery reply bit-identical, "
              "tmp swept" % (point, real_kill))
    return {"committed": committed, "reply_sum": prior_sum}


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def scenario_crash_save(workdir, point="manifest_written",
                        crash_at_save=2, real_kill=True, steps=6,
                        verbose=True):
    """kill -9 a child mid-save at `point` during save number
    `crash_at_save`, then verify the vault.  With real_kill the child
    pauses at the point and the parent delivers SIGKILL; otherwise the
    child os._exit(137)s itself at the point (deterministic, no
    timing)."""
    os.makedirs(workdir, exist_ok=True)
    action = "pause:120" if real_kill else "exit"
    spec = "%s=%s" % (point, action)
    proc = _spawn_child(workdir, steps, spec, crash_at_save)
    saved = 0
    try:
        if real_kill:
            for line in proc.stdout:
                line = line.strip()
                if line.startswith("SAVED"):
                    saved = int(line.split()[1])
                if line.startswith("CHAOS_PAUSE"):
                    os.kill(proc.pid, signal.SIGKILL)
                    break
            proc.wait(timeout=30)
        else:
            out, _ = proc.communicate(timeout=120)
            for line in out.splitlines():
                if line.startswith("SAVED"):
                    saved = int(line.split()[1])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    rc = proc.returncode
    assert rc != 0, "child survived the kill (rc=0) — no fault injected"
    assert saved == crash_at_save - 1, \
        "expected the crash during save %d (after %d clean saves), " \
        "child reported %d" % (crash_at_save, crash_at_save - 1, saved)
    # after a crash at any pre-commit point, last-good == last SAVED line;
    # a crash after commit-but-before-latest may legitimately expose the
    # newer committed step (both are fully-verified checkpoints)
    meta = _verify_last_good(
        workdir, min_step=saved if saved else None,
        max_step=saved + 1 if point in ("committed", "latest_updated")
        else saved)
    if verbose:
        print("PASS crash-save point=%s save#%d kill=%s: child rc=%s, "
              "last SAVED=%d, recovered last-good step=%d"
              % (point, crash_at_save, real_kill, rc, saved,
                 meta["step"]))
    return meta


def scenario_bit_flip(workdir, verbose=True):
    """Commit a checkpoint, flip one bit in one shard, and require the
    load to fail NAMING that array (and verify_checkpoint to exit 2)."""
    import numpy as np
    from paddle_tpu.fluid import checkpoint as ckpt
    root = os.path.join(workdir, "bitflip")
    arrays = {"fc_w": np.arange(24, dtype=np.float32).reshape(4, 6),
              "fc_b": np.ones(6, np.float32)}
    path = ckpt.save_checkpoint_dir(root, arrays, {"epoch": 0, "step": 1})
    corrupt_array(path, "fc_w")
    try:
        ckpt.load_checkpoint_dir(path)
    except ckpt.CheckpointCorruptionError as e:
        assert "fc_w" in str(e), \
            "corruption error does not name the array: %s" % e
    else:
        raise AssertionError("bit-flipped shard loaded without error")
    if verbose:
        print("PASS bit-flip: load rejected, error names fc_w")
    return True


def scenario_nan_poison(verbose=True):
    """Sentinel end-to-end: poisoned batches are skipped (params revert)
    and K consecutive poisoned steps roll back to last-good."""
    import tempfile
    import warnings
    import numpy as np
    import paddle_tpu.fluid as fluid

    rng = np.random.RandomState(0)
    data = [(x, np.array([x.sum()], np.float32))
            for x in [rng.randn(4).astype(np.float32) for _ in range(10)]]

    def train_func():
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        return fluid.layers.mean(fluid.layers.square_error_cost(pred, y))

    def optimizer_func():
        return fluid.optimizer.SGD(learning_rate=0.05)

    def reader():
        for x, y in data:
            yield [(x, y)]

    workdir = tempfile.mkdtemp(prefix="chaos_nan_")
    fluid.set_flags({"sentinel_nan_check": True,
                     "sentinel_policy": "rollback",
                     "sentinel_max_bad_steps": 2})
    try:
        with fluid.scope_guard(fluid.Scope()):
            cfg = fluid.contrib.CheckpointConfig(
                checkpoint_dir=workdir, step_interval=3)
            trainer = fluid.contrib.Trainer(
                train_func, optimizer_func, place=fluid.CPUPlace(),
                checkpoint_config=cfg)
            poisoned = nan_poison_reader(reader, poison_steps={5, 6})
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                trainer.train(num_epochs=1, event_handler=lambda ev: None,
                              reader=poisoned, feed_order=["x", "y"])
        msgs = [str(w.message) for w in caught]
        assert any("reverted" in m for m in msgs), \
            "no skip-step warning: %s" % msgs
        assert any("rolled back" in m for m in msgs), \
            "no rollback warning: %s" % msgs
    finally:
        fluid.set_flags({"sentinel_nan_check": False,
                         "sentinel_policy": "skip",
                         "sentinel_max_bad_steps": 3})
    if verbose:
        print("PASS nan-poison: skip then rollback observed")
    return True


def scenario_drop_rpc(verbose=True):
    """MasterClient through a connection-killing proxy: the retry
    wrapper re-dials and the lease req_id dedup keeps work exactly-once.
    """
    from paddle_tpu.distributed.elastic import MasterService, MasterClient
    master = MasterService("127.0.0.1:0").start()
    proxy = FlakyProxy(master.endpoint, drop_first=1).start()
    try:
        cli = MasterClient(proxy.endpoint, worker="w0", dial_timeout=20.0)
        cli.set_dataset(["task-%d" % i for i in range(4)])
        got = []
        while True:
            t = cli.get_task(block=True, timeout=20.0)
            if t is None or master.num_passes > 0:
                break
            got.append(t[1])
            cli.task_finished(t[0])
            if len(got) >= 4:
                break
        assert sorted(got) == ["task-%d" % i for i in range(4)], \
            "leases not exactly-once through the drop: %s" % got
        assert proxy.dropped >= 1, "proxy never injected a drop"
        cli.close()
    finally:
        proxy.stop()
        master.stop()
    if verbose:
        print("PASS drop-rpc: %d connection(s) killed, 4 tasks "
              "exactly-once" % proxy.dropped)
    return True


def scenario_serving_overload(verbose=True):
    """Serving shed-not-hang: an in-process inference server behind a
    connection-killing FlakyProxy, with slow-worker injection and a tiny
    admission queue, takes a burst far past capacity.  Required
    invariants: (1) some requests succeed, (2) overflow is shed with an
    explicit ServerOverloaded, (3) EVERY request resolves — success,
    shed, or deadline — within a bound; nothing hangs."""
    import tempfile
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.serving import (DeadlineExceeded, InferenceServer,
                                    ServerOverloaded, ServingClient,
                                    set_dispatch_delay)

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 5
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        pred = fluid.layers.fc(input=x, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = os.path.join(tempfile.mkdtemp(prefix="chaos_srv_"), "m")
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main_p)

    server = InferenceServer(max_queue=4, buckets=(2, 4)).start()
    proxy = FlakyProxy(server.endpoint, drop_first=2,
                       drop_after_bytes=64).start()
    x_req = np.zeros((1, 8), np.float32)
    outcomes = {"ok": 0, "shed": 0, "deadline": 0, "conn": 0}
    lock = threading.Lock()

    def one_request(i):
        cli = ServingClient(proxy.endpoint)
        try:
            cli.infer("m", {"x": x_req}, deadline_ms=500.0,
                      retry_sheds=False)
            key = "ok"
        except ServerOverloaded:
            key = "shed"
        except DeadlineExceeded:
            key = "deadline"
        except (ConnectionError, OSError, EOFError, RuntimeError):
            key = "conn"
        finally:
            cli.close()
        with lock:
            outcomes[key] += 1

    try:
        boot = ServingClient(server.endpoint)  # not via the proxy
        boot.load_model("m", md, buckets=[2, 4])
        boot.infer("m", {"x": x_req})  # warm through the real endpoint
        set_dispatch_delay(0.15)       # slow worker: force a backlog
        threads = [threading.Thread(target=one_request, args=(i,))
                   for i in range(32)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        wall = time.time() - t0
        alive = [t for t in threads if t.is_alive()]
        assert not alive, "%d requests HUNG under overload" % len(alive)
        total = sum(outcomes.values())
        assert total == 32, "lost requests: %s" % outcomes
        assert outcomes["ok"] >= 1, "nothing succeeded: %s" % outcomes
        assert outcomes["shed"] >= 1, \
            "queue never shed (admission control dead): %s" % outcomes
        assert proxy.dropped >= 1, "proxy never injected a drop"
    finally:
        set_dispatch_delay(0.0)
        proxy.stop()
        server.shutdown(drain=False, timeout=5.0)
    if verbose:
        print("PASS serving-overload: %d ok / %d shed / %d deadline / "
              "%d conn-killed in %.1fs, %d proxy drops, zero hangs"
              % (outcomes["ok"], outcomes["shed"], outcomes["deadline"],
                 outcomes["conn"], wall, proxy.dropped))
    return outcomes


def scenario_decode_disconnect(verbose=True, kv_dtype=None):
    """Continuous-batching decode chaos (SERVING.md "Continuous
    batching & streaming"): streaming requests that die mid-generation
    must not wedge the slot table.

    `kv_dtype="int8"` re-runs the whole scenario under the QUANTIZED
    slot table (QUANTIZE.md "Quantized KV cache"): the invariants are
    identical — freed slots must hold exact int8 zeros before reuse,
    and phase C's replay (vs a direct int8-cache session) proves zero
    cross-request leakage survives quantization.

    Phase A — client disconnect mid-stream: a victim opens an
    `infer_stream`, reads a few chunks, and drops the connection.  The
    server's flush failure cancels the stream; required invariants:
    (1) the slot frees within a handful of decode steps (the flush of
    the NEXT token notices the dead socket, the step after that
    reclaims the slot), (2) zero wedged lanes — later traffic on the
    same (tiny) slot table completes.

    Phase B — deadline expiry mid-decode: a stream whose deadline
    expires while GENERATING (the PR 8 fix: deadlines cover in-decode
    time, not just queue+reply wait) is evicted from its slot with a
    typed error frame on the stream and a `deadline_expired` event
    carrying its trace_id.

    Phase C — no cross-request KV leakage: the victims' slots are
    reused by fresh requests whose greedy token streams must be
    IDENTICAL to a direct single-slot DecodeSession on the same
    artifact — possible only if freed slots were zeroed before reuse.
    """
    import tempfile
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             build_tiny_decode_model,
                                             greedy_decode)
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import (DeadlineExceeded, InferenceServer,
                                    ServingClient, set_dispatch_delay)

    md = build_tiny_decode_model(
        os.path.join(tempfile.mkdtemp(prefix="chaos_decode_"), "lm"),
        vocab_size=64, d_model=32, n_heads=4, n_layers=2,
        max_seq_len=64, eos_id=-1, seed=21)
    # the reference session runs the SAME cache dtype as the server:
    # int8 streams are bit-exact against int8 sessions (self-stable),
    # not against fp32 ones
    pred = GenerativePredictor(md, kv_cache_dtype=kv_dtype)
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    step_ms = 20.0

    def occupancy():
        snap = boot.stats()["stats"]["models"]["lm"]
        return snap.get("decode_slots_busy", 0), snap.get(
            "decode_steps", 0)

    try:
        # the classic loop, one step a dispatch: windows have their own
        # scenario (decode-disconnect-fused)
        boot.load_model("lm", md, decode_slots=2, kv_cache_dtype=kv_dtype,
                        fuse_steps=1)
        # slow, deterministic steps so "mid-stream" is unambiguous
        set_dispatch_delay(step_ms / 1000.0)

        # ---- phase A: disconnect mid-stream ------------------------
        victim = ServingClient(server.endpoint)
        it = victim.infer_stream("lm", [3, 5, 7], max_new_tokens=48)
        got = [t for _, t in zip(range(3), it)]
        assert len(got) == 3, "victim stream never started"
        busy_before, steps_at_drop = occupancy()
        assert busy_before >= 1, "victim not occupying a slot"
        it.close()       # drops the connection mid-stream
        victim.close()
        t0 = time.time()
        freed_steps = None
        while time.time() - t0 < 10.0:
            busy, steps = occupancy()
            if busy == 0:
                freed_steps = steps - steps_at_drop
                break
            time.sleep(0.01)
        assert freed_steps is not None, \
            "slot still occupied 10s after client disconnect (wedged)"
        # flush-of-next-token notices the dead socket, the step after
        # reclaims; polling adds slack — a small step bound still
        # proves the slot freed promptly, not at max_new_tokens
        assert freed_steps <= 6, \
            "slot took %d decode steps to free after disconnect" \
            % freed_steps

        # ---- phase B: deadline expires mid-decode ------------------
        cli = ServingClient(server.endpoint)
        tokens_before_expiry = 0
        expired = False
        try:
            for chunk in cli.infer_stream("lm", [9, 4], deadline_ms=200.0,
                                          max_new_tokens=60,
                                          trace_id="chaosdl"):
                tokens_before_expiry += len(chunk)
        except DeadlineExceeded:
            expired = True
        finally:
            cli.close()
        assert expired, "deadline never expired mid-stream"
        assert tokens_before_expiry >= 1, \
            "stream expired before generating (not an IN-DECODE expiry)"
        ev = [e for e in obs_events.recent_events(kind="deadline_expired")
              if e.get("trace_id") == "chaosdl"]
        assert ev, "no deadline_expired event with the stream's trace_id"
        assert ev[-1].get("tokens", 0) >= 1, \
            "deadline_expired event missing in-decode token count"

        # ---- phase C: slot reuse, zero leakage, zero wedged lanes --
        set_dispatch_delay(0.0)
        prompts = [[3, 5, 7], [9, 4], [11, 12, 13, 14], [2]]
        refs = [greedy_decode(pred, p, 12)[0] for p in prompts]
        outs = [None] * len(prompts)
        errs = []

        def rerun(i):
            c = ServingClient(server.endpoint)
            try:
                outs[i] = [t for ch in c.infer_stream(
                    "lm", prompts[i], max_new_tokens=12,
                    deadline_ms=60000.0) for t in ch]
            except Exception as e:
                errs.append(e)
            finally:
                c.close()

        threads = [threading.Thread(target=rerun, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), \
            "post-chaos traffic hung (wedged lane)"
        assert not errs, "post-chaos traffic failed: %r" % errs[:2]
        for i, (out, ref) in enumerate(zip(outs, refs)):
            assert out == ref, \
                ("KV leakage: reused slot changed request %d's tokens "
                 "(%s vs %s)" % (i, out, ref))
        busy, _ = occupancy()
        assert busy == 0, "slots still occupied after drain"
    finally:
        set_dispatch_delay(0.0)
        boot.close()
        server.shutdown(drain=False, timeout=10.0)
    if verbose:
        print("PASS decode-disconnect%s: slot freed in %d step(s) "
              "after disconnect, deadline evicted mid-decode after %d "
              "token(s) with event, %d post-chaos streams bit-exact "
              "on reused slots"
              % (" (kv=%s)" % kv_dtype if kv_dtype else "",
                 freed_steps, tokens_before_expiry, len(prompts)))
    return {"freed_steps": freed_steps,
            "expired_tokens": tokens_before_expiry,
            "kv_dtype": kv_dtype or "float32"}


def scenario_decode_disconnect_fused(verbose=True, fuse_steps=4):
    """Fused-decode boundary chaos (SERVING.md "Fused multi-step
    decode"): with N steps compiled into one dispatch, slot joins,
    leaves and deadline evictions only land at DISPATCH BOUNDARIES —
    chaos mid-window must resolve at the next boundary, never wedge.

    Phase A — disconnect mid-fused-window: a victim drops its
    connection while a fused dispatch is in flight.  The flush of the
    window's token block notices the dead socket; the NEXT boundary's
    housekeeping frees the slot.  Invariants: the slot frees within a
    couple of windows (<= 3·N decode steps), and later traffic on the
    same slot table completes — zero wedged lanes.

    Phase B — deadline expiry under fusion (the satellite bugfix):
    deadline checks only fire between dispatches, so the per-dispatch
    trip count is CLAMPED by the lane's step-EWMA and no stream may
    overshoot its deadline by more than about one fused dispatch.  The
    `deadline_expired` event must stamp `overshoot_ms`, and the
    overshoot must be bounded — not the unclamped N-window tail.

    Phase C — boundary-freed slots are clean: fresh requests reusing
    the victims' slots stream bit-identical to a direct single-slot
    session — the fused path zeroes freed rows exactly like N=1."""
    import tempfile
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             build_tiny_decode_model,
                                             greedy_decode)
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import (DeadlineExceeded, InferenceServer,
                                    ServingClient, set_dispatch_delay)

    fuse = max(int(fuse_steps), 2)
    md = build_tiny_decode_model(
        os.path.join(tempfile.mkdtemp(prefix="chaos_fused_"), "lm"),
        vocab_size=64, d_model=32, n_heads=4, n_layers=2,
        max_seq_len=64, eos_id=-1, seed=21)
    pred = GenerativePredictor(md)
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    step_ms = 20.0

    def occupancy():
        snap = boot.stats()["stats"]["models"]["lm"]
        return snap.get("decode_slots_busy", 0), snap.get(
            "decode_steps", 0)

    try:
        boot.load_model("lm", md, decode_slots=2, fuse_steps=fuse)
        # per-STEP stand-in: a full window stalls fuse*step_ms, so
        # "mid-window" is unambiguous
        set_dispatch_delay(step_ms / 1000.0)

        # ---- phase A: disconnect mid-fused-window ------------------
        victim = ServingClient(server.endpoint)
        it = victim.infer_stream("lm", [3, 5, 7], max_new_tokens=48)
        got = [t for _, t in zip(range(3), it)]
        assert len(got) == 3, "victim stream never started"
        busy_before, steps_at_drop = occupancy()
        assert busy_before >= 1, "victim not occupying a slot"
        it.close()       # drops the connection mid-window
        victim.close()
        t0 = time.time()
        freed_steps = None
        while time.time() - t0 < 10.0:
            busy, steps = occupancy()
            if busy == 0:
                freed_steps = steps - steps_at_drop
                break
            time.sleep(0.01)
        assert freed_steps is not None, \
            "slot still occupied 10s after mid-window disconnect"
        # the in-flight window finishes, its flush fails, the NEXT
        # boundary's housekeeping frees the slot: a couple of windows
        # of steps, never the stream's max_new tail
        assert freed_steps <= 3 * fuse, \
            ("slot took %d decode steps to free after mid-window "
             "disconnect (fuse=%d — not boundary-freed)"
             % (freed_steps, fuse))

        # ---- phase B: deadline expiry at the boundary --------------
        cli = ServingClient(server.endpoint)
        tokens_before_expiry = 0
        expired = False
        try:
            for chunk in cli.infer_stream("lm", [9, 4],
                                          deadline_ms=200.0,
                                          max_new_tokens=60,
                                          trace_id="chaosfdl"):
                tokens_before_expiry += len(chunk)
        except DeadlineExceeded:
            expired = True
        finally:
            cli.close()
        assert expired, "deadline never expired mid-stream"
        assert tokens_before_expiry >= 1, \
            "stream expired before generating (not an IN-DECODE expiry)"
        ev = [e for e in
              obs_events.recent_events(kind="deadline_expired")
              if e.get("trace_id") == "chaosfdl"]
        assert ev, "no deadline_expired event with the stream's trace_id"
        over = ev[-1].get("overshoot_ms")
        assert over is not None, \
            "deadline_expired event missing overshoot_ms"
        # EWMA trip clamp: the overshoot is about ONE fused dispatch
        # (+ host scheduling slack), not an unclamped fuse-step tail
        assert over <= fuse * step_ms + 500.0, \
            ("deadline overshoot %.1fms exceeds one fused dispatch "
             "(fuse=%d x %.0fms) — trip clamp not engaged"
             % (over, fuse, step_ms))

        # ---- phase C: boundary-freed slots are clean ---------------
        set_dispatch_delay(0.0)
        prompts = [[3, 5, 7], [9, 4], [11, 12, 13, 14], [2]]
        refs = [greedy_decode(pred, p, 12)[0] for p in prompts]
        outs = [None] * len(prompts)
        errs = []

        def rerun(i):
            c = ServingClient(server.endpoint)
            try:
                outs[i] = [t for ch in c.infer_stream(
                    "lm", prompts[i], max_new_tokens=12,
                    deadline_ms=60000.0) for t in ch]
            except Exception as e:
                errs.append(e)
            finally:
                c.close()

        threads = [threading.Thread(target=rerun, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), \
            "post-chaos traffic hung (wedged lane)"
        assert not errs, "post-chaos traffic failed: %r" % errs[:2]
        for i, (out, ref) in enumerate(zip(outs, refs)):
            assert out == ref, \
                ("KV leakage: reused slot changed request %d's tokens "
                 "(%s vs %s)" % (i, out, ref))
        busy, _ = occupancy()
        assert busy == 0, "slots still occupied after drain"
    finally:
        set_dispatch_delay(0.0)
        boot.close()
        server.shutdown(drain=False, timeout=10.0)
    if verbose:
        print("PASS decode-disconnect-fused (N=%d): slot freed in %d "
              "step(s) after mid-window disconnect, deadline evicted "
              "with overshoot %.1fms (<= one dispatch), %d post-chaos "
              "streams bit-exact on reused slots"
              % (fuse, freed_steps, over, len(prompts)))
    return {"freed_steps": freed_steps, "fuse_steps": fuse,
            "overshoot_ms": over,
            "expired_tokens": tokens_before_expiry}


def scenario_spec_fallback(verbose=True):
    """Speculative-decoding chaos (SERVING.md "Speculative decoding"):
    the draft predictor dies MID-STREAM and the serving lane must
    degrade to target-only decode without dropping or corrupting one
    token.

    A server loads a decode model with a same-weights draft (spec_k=4,
    accept ~1.0).  A victim stream starts, reads a few chunks riding
    speculative rounds, then `set_draft_poison(0)` kills every further
    draft step.  Required invariants: (1) the victim stream completes
    to its full token budget — the poisoned round itself falls back to
    a plain target step, so the stream never stalls; (2) every token of
    the victim AND of fresh post-degrade streams is bit-identical to a
    direct fp32-only greedy decode (degradation must not touch the
    committed KV state); (3) a `spec_degraded` obs event fires and the
    `spec_degraded` stats counter reads >= 1; (4) zero wedged lanes —
    the slot table drains clean."""
    import tempfile
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             build_tiny_decode_model,
                                             greedy_decode,
                                             set_draft_poison)
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)

    md = build_tiny_decode_model(
        os.path.join(tempfile.mkdtemp(prefix="chaos_spec_"), "lm"),
        vocab_size=64, d_model=32, n_heads=4, n_layers=2,
        max_seq_len=64, eos_id=-1, seed=23)
    pred = GenerativePredictor(md)
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    set_draft_poison(None)
    try:
        boot.load_model("lm", md, decode_slots=2, draft=md, spec_k=4)
        # slow, deterministic steps so "mid-stream" is unambiguous
        set_dispatch_delay(0.01)
        victim = ServingClient(server.endpoint)
        prompt, budget = [3, 5, 7], 32
        ref, _ = greedy_decode(pred, prompt, budget)
        it = victim.infer_stream("lm", prompt, max_new_tokens=budget,
                                 deadline_ms=60000.0)
        got = []
        poisoned = False
        for chunk in it:
            got.extend(chunk)
            if not poisoned and len(got) >= 6:
                # a few speculative rounds in: kill the draft
                set_draft_poison(0)
                poisoned = True
        victim.close()
        assert poisoned, "stream finished before the poison armed"
        assert len(got) == budget, \
            "victim stream stalled/truncated after draft death: " \
            "%d of %d tokens" % (len(got), budget)
        assert got == ref, \
            "draft death corrupted the victim stream (%s vs %s)" \
            % (got[:8], ref[:8])
        ev = [e for e in obs_events.recent_events(kind="spec_degraded")]
        assert ev, "no spec_degraded event after draft poison"
        assert "poison" in str(ev[-1].get("error", "")), ev[-1]
        snap = boot.stats()["stats"]["models"]["lm"]
        assert snap.get("spec_degraded", 0) >= 1, snap
        accept = snap.get("spec_accept_rate")
        # fresh post-degrade traffic: target-only, still bit-exact
        set_dispatch_delay(0.0)
        prompts = [[9, 4], [11, 12, 13, 14], [2]]
        for p in prompts:
            cli = ServingClient(server.endpoint)
            try:
                out = [t for ch in cli.infer_stream(
                    "lm", p, max_new_tokens=12, deadline_ms=60000.0)
                    for t in ch]
            finally:
                cli.close()
            assert out == greedy_decode(pred, p, 12)[0], \
                "post-degrade stream not bit-exact for %s" % (p,)
        t0 = time.time()
        while time.time() - t0 < 10.0:
            if boot.stats()["stats"]["models"]["lm"].get(
                    "decode_slots_busy", 0) == 0:
                break
            time.sleep(0.01)
        busy = boot.stats()["stats"]["models"]["lm"].get(
            "decode_slots_busy", 0)
        assert busy == 0, "slots still occupied after drain (wedged)"
    finally:
        set_draft_poison(None)
        set_dispatch_delay(0.0)
        boot.close()
        server.shutdown(drain=False, timeout=10.0)
    if verbose:
        print("PASS spec-fallback: draft poisoned mid-stream after 6+ "
              "tokens, victim completed all %d tokens bit-exact, "
              "spec_degraded event + counter fired (accept rate before "
              "death %s), %d post-degrade streams bit-exact, slots "
              "drained" % (budget, accept, len(prompts)))
    return {"victim_tokens": len(got), "accept_rate": accept}


def scenario_mesh_member_loss(verbose=True):
    """Mesh-replica chaos (SERVING.md "Mesh replicas"): one member chip
    of a sharded replica mesh dies mid-stream.  A mesh lane cannot
    degrade to fewer chips — its params and KV slot table are sharded
    across the members — so the required failure shape is lane DEATH,
    not a wedge:

    (1) every in-flight stream on the victim mesh fails with a TYPED
        error naming the lost member (zero hangs);
    (2) the lane is marked dead — stats/health carry the mesh size and
        the death reason, a `mesh_lane_dead` event fires, and admission
        skips the corpse;
    (3) sibling mesh lanes are untouched: their in-flight streams
        complete BIT-EXACT vs the single-device greedy oracle, and
        fresh post-loss traffic keeps serving bit-exact on survivors;
    (4) the persisted load spec replays: page + fault-in rebuilds the
        FULL mesh lane set (the fleet controller's fault path), and the
        rebuilt lanes serve bit-exact again.

    The drill runs TWICE: once with shard-at-rest (gather) lanes and
    once with FLAGS.mesh_tp on (SERVING.md "Tensor-parallel compute"),
    where the member dies while the partitioned program is executing —
    mid-psum, not between gathers.  The TP pass additionally asserts
    that the lanes really are tensor-parallel (stats rows carry
    tp=True) and that the fault-in rebuild comes back as TP lanes,
    not silently degraded to gather lanes.
    """
    # the mesh needs >= 4 host devices; when the backend is already up
    # with fewer (e.g. `--scenario all` after another scenario touched
    # jax), re-exec as a subprocess with the forced device count
    import jax
    if jax.device_count() < 4:
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + ["--xla_force_host_platform_device_count=8"])
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--scenario", "mesh-member-loss"],
            env=env, cwd=REPO, timeout=900)
        assert proc.returncode == 0, \
            "mesh-member-loss subprocess failed (rc=%d)" % proc.returncode
        return {"reexec": True}

    from paddle_tpu.flags import get_flags, set_flags
    saved = get_flags(["mesh_tp"])
    out = {}
    try:
        for tp in (False, True):
            set_flags({"mesh_tp": tp})
            out["tp" if tp else "gather"] = \
                _mesh_member_loss_drill(tp, verbose)
    finally:
        set_flags(saved)
    return out


def _mesh_member_loss_drill(tp, verbose=True):
    import tempfile
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             build_tiny_decode_model,
                                             greedy_decode)
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.parallel.mesh import set_member_poison
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)

    md = build_tiny_decode_model(
        os.path.join(tempfile.mkdtemp(prefix="chaos_mesh_"), "lm"),
        vocab_size=64, d_model=32, n_heads=4, n_layers=2,
        max_seq_len=64, eos_id=-1, seed=29)
    pred = GenerativePredictor(md)
    budget = 24
    prompts = [[3, 5, 7], [9, 4], [11, 12, 13, 14], [2, 6]]
    refs = [greedy_decode(pred, p, budget)[0] for p in prompts]
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    set_member_poison(None)
    try:
        # two replica lanes, each a 2-chip mesh (params + KV sharded)
        rep = boot.load_model("lm", md, decode_slots=4,
                              replicas="cpu:0+cpu:1,cpu:2+cpu:3")
        assert rep.get("mesh") == [2, 2], rep
        rows = boot.stats()["stats"]["models"]["lm"].get("replicas") or []
        assert all(bool(r.get("tp")) == tp for r in rows), \
            "lanes not in the requested compute mode (tp=%s): %s" \
            % (tp, rows)
        set_dispatch_delay(0.02)  # slow steps: "mid-stream" for real

        outs = [None] * len(prompts)
        errs = [None] * len(prompts)
        counts = [0] * len(prompts)

        def run(i):
            c = ServingClient(server.endpoint)
            try:
                buf = []
                for ch in c.infer_stream("lm", prompts[i],
                                         max_new_tokens=budget,
                                         deadline_ms=60000.0):
                    buf.extend(ch)
                    counts[i] = len(buf)
                outs[i] = buf
            except Exception as e:
                errs[i] = e
            finally:
                c.close()

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        t0 = time.time()
        while time.time() - t0 < 30.0:
            if all(c >= 2 for c in counts):
                break
            time.sleep(0.01)
        assert all(c >= 2 for c in counts), \
            "streams never got going: %s" % (counts,)
        # ---- kill one member of the first mesh mid-generation ------
        set_member_poison("cpu:1")
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), \
            "stream hung after mesh member loss (wedged lane)"
        victims = [i for i in range(len(prompts)) if errs[i] is not None]
        survivors = [i for i in range(len(prompts)) if errs[i] is None]
        assert victims, "no stream was riding the poisoned mesh"
        assert survivors, "member loss killed streams on sibling lanes"
        for i in victims:
            assert "mesh member" in str(errs[i]), \
                "victim error not typed: %r" % (errs[i],)
        for i in survivors:
            assert outs[i] == refs[i], \
                ("member loss corrupted a SIBLING lane's stream %d "
                 "(%s vs %s)" % (i, outs[i][:8], refs[i][:8]))

        # ---- the corpse is marked, observable, and skipped ---------
        snap = boot.stats()["stats"]["models"]["lm"]
        rows = snap.get("replicas") or []
        dead = [r for r in rows if r.get("dead")]
        live = [r for r in rows if not r.get("dead")]
        assert len(dead) == 1 and len(live) == 1, rows
        assert dead[0]["mesh"] == 2 and "cpu:1" in dead[0]["device"], \
            dead[0]
        ev = [e for e in obs_events.recent_events(kind="mesh_lane_dead")
              if e.get("model") == "lm"]
        assert ev, "no mesh_lane_dead event after member loss"
        assert "cpu:1" in str(ev[-1].get("error", "")), ev[-1]
        set_dispatch_delay(0.0)
        for i, p in enumerate(prompts[:2]):
            cli = ServingClient(server.endpoint)
            try:
                out = [t for ch in cli.infer_stream(
                    "lm", p, max_new_tokens=budget,
                    deadline_ms=60000.0) for t in ch]
            finally:
                cli.close()
            assert out == refs[i], \
                "post-loss stream on survivor not bit-exact for %s" % (p,)

        # ---- rebuild from the persisted spec (fleet fault path) ----
        set_member_poison(None)  # the "chip" comes back
        boot.page_model("lm")
        boot.fault_model("lm", trigger="chaos")
        rows = boot.stats()["stats"]["models"]["lm"].get("replicas") or []
        assert len(rows) == 2 and not any(r.get("dead") for r in rows), \
            rows
        assert all(r.get("mesh") == 2 for r in rows), rows
        assert all(bool(r.get("tp")) == tp for r in rows), \
            "fault-in rebuilt lanes in the wrong compute mode " \
            "(want tp=%s): %s" % (tp, rows)
        for i, p in enumerate(prompts):
            cli = ServingClient(server.endpoint)
            try:
                out = [t for ch in cli.infer_stream(
                    "lm", p, max_new_tokens=budget,
                    deadline_ms=60000.0) for t in ch]
            finally:
                cli.close()
            assert out == refs[i], \
                "rebuilt mesh lane not bit-exact for %s" % (p,)
    finally:
        set_member_poison(None)
        set_dispatch_delay(0.0)
        boot.close()
        server.shutdown(drain=False, timeout=10.0)
    if verbose:
        print("PASS mesh-member-loss[%s]: %d victim stream(s) failed "
              "typed, %d sibling stream(s) bit-exact, dead lane marked "
              "+ mesh_lane_dead event, survivors served post-loss, "
              "page/fault-in rebuilt both 2-chip mesh lanes bit-exact"
              % ("tensor-parallel" if tp else "gather",
                 len(victims), len(survivors)))
    return {"victims": len(victims), "survivors": len(survivors)}


def scenario_trace_overflow(workdir, verbose=True):
    """Observability hot-path safety (OBSERVABILITY.md): the span ring
    wraps under concurrent load and the event log rotates mid-write —
    tracing must never block, never raise into the instrumented code,
    and every log generation must stay valid JSONL.

    Phase A — overflow: 4 threads hammer spans + events through a tiny
    ring (64) and a ~2 KiB rotation threshold; asserts (1) zero emitter
    exceptions, (2) the ring wrapped (dropped > 0) and holds exactly
    its capacity, (3) every line of every log generation parses as
    JSON, (4) at least one rotation happened, (5) no single emit took
    >250 ms (the never-blocks bound, generous for CI).

    Phase B — fault mid-rotation: the vault chaos hook raises at the
    `obs_rotated` point (between the fsync and the atomic rename);
    emitters must swallow it (warn-once, drop to memory-only), the
    pre-rotation file must survive intact, and the memory ring must
    keep recording."""
    import glob
    import json as _json
    import warnings
    from paddle_tpu.flags import set_flags, get_flags
    from paddle_tpu.fluid.checkpoint import set_chaos_hook
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.obs import tracing as obs_tracing

    os.makedirs(workdir, exist_ok=True)
    log_path = os.path.join(workdir, "events.jsonl")
    saved = get_flags(["trace", "trace_buffer_events", "event_log",
                       "event_log_max_kb"])
    errors = []
    slow = [0.0]

    def hammer(tid, n=400):
        try:
            for i in range(n):
                t0 = time.time()
                with obs_tracing.trace("chaos/span", kind="serving",
                                       trace_id="t%d" % tid, i=i):
                    pass
                obs_events.emit("chaos", thread=tid, i=i)
                dt = time.time() - t0
                if dt > slow[0]:
                    slow[0] = dt
        except BaseException as e:   # emitters must never raise
            errors.append(e)

    try:
        set_flags({"trace": True, "trace_buffer_events": 64,
                   "event_log_max_kb": 2, "event_log": log_path})
        obs_tracing.clear()
        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), \
            "emitter thread hung — tracing blocked the hot path"
        assert not errors, "emitter raised: %r" % errors[0]
        st = obs_tracing.stats()
        assert st["buffered"] == 64, \
            "ring holds %d spans, capacity 64" % st["buffered"]
        assert st["dropped"] > 0, "ring never wrapped: %s" % st
        assert slow[0] < 0.25, \
            "an emit blocked for %.0f ms" % (slow[0] * 1e3)
        obs_events.get_log().flush()
        gens = sorted(glob.glob(log_path + "*"))
        assert os.path.exists(log_path + ".1"), \
            "no rotation happened: %s" % gens
        n_lines = 0
        for g in gens:
            with open(g) as f:
                for line in f:
                    rec = _json.loads(line)   # raises = corrupt log
                    assert rec.get("kind") == "chaos"
                    n_lines += 1
        assert n_lines > 0

        # phase B: rotation faults mid-commit
        fault_log = os.path.join(workdir, "fault.jsonl")
        set_flags({"event_log": fault_log})

        def _boom(point):
            if point == "obs_rotated":
                raise RuntimeError("chaos: fault mid-rotation")

        set_chaos_hook(_boom)
        before = obs_events.events_total()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for i in range(4000):   # enough to cross 2 KiB
                obs_events.emit("chaos_b", i=i)
        set_chaos_hook(None)
        assert obs_events.events_total() - before == 4000, \
            "events lost across the rotation fault"
        assert any("memory-only" in str(w.message) for w in caught), \
            "sink death was silent"
        assert os.path.exists(fault_log), \
            "pre-rotation log vanished (rotation not atomic)"
        with open(fault_log) as f:
            for line in f:
                _json.loads(line)
        assert obs_events.recent_events(1, kind="chaos_b"), \
            "memory ring stopped recording after sink death"
    finally:
        set_chaos_hook(None)
        set_flags(saved)
    if verbose:
        print("PASS trace-overflow: ring wrapped (%d dropped), %d "
              "rotated JSONL lines valid, max emit %.1f ms, "
              "mid-rotation fault absorbed memory-only"
              % (st["dropped"], n_lines, slow[0] * 1e3))
    return {"dropped": st["dropped"], "lines": n_lines,
            "max_emit_ms": slow[0] * 1e3}


def _child_flight(workdir):
    """Subprocess target for the SIGKILL-mid-dump half of the
    slo-breach scenario: commit one clean bundle, then trigger a
    second — PADDLE_TPU_CHAOS='flight_committed=exit@2' kills this
    process between the tmp fsync and the publishing rename, so the
    parent must find bundle #1 intact + at most a stale _tmp dir."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.obs import flightrec
    set_flags({"flight_dir": workdir, "flight_cooldown_s": 0.0,
               "flight_keep": 8})
    rec = flightrec.get_recorder()
    rec.add_provider("probe", lambda: {"child": os.getpid()})
    p1 = rec.trigger("chaos_a", force=True)
    print("CHILD_BUNDLE_1 %s" % p1, flush=True)
    rec.trigger("chaos_b", force=True)  # chaos point fires here
    print("CHILD_BUNDLE_2_COMMITTED", flush=True)


def scenario_slo_breach(workdir, verbose=True, kill_phase=True):
    """The SLO engine + flight recorder, end to end (OBSERVABILITY.md
    "SLOs & burn rates" / "Flight recorder"):

    1. an in-process server with a declared p95 SLO serves clean
       traffic (state ok; replies captured for the bit-exactness
       check);
    2. injected dispatch latency (set_dispatch_delay) pushes every
       interval past the target: the breach must be DETECTED within 2
       fast-burn evaluation windows, flip the health state machine to
       'breach', and fire the flight recorder exactly once (cooldown
       absorbs the storm);
    3. the produced bundle must be complete and valid
       (flight_inspect's deep validation: manifest CRC walk, required
       files, JSONL parse);
    4. clearing the latency must recover the state machine with
       exactly ONE slo_recovered event, and replies must be
       bit-identical to the pre-chaos captures — monitoring never
       touches the bits;
    5. a REAL kill mid-dump (subprocess at the flight_committed chaos
       point) leaves prior bundles intact + only a stale tmp dir,
       and the next dump sweeps it."""
    import glob
    import numpy as np
    import tempfile
    import paddle_tpu.fluid as fluid
    from paddle_tpu.flags import set_flags, get_flags
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.obs import flightrec
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)
    sys.path.insert(0, HERE)
    import flight_inspect

    os.makedirs(workdir, exist_ok=True)
    flight_dir = os.path.join(workdir, "flight")
    interval_ms = 100.0
    fast_window = 3
    saved = get_flags(["serving_slo", "slo_eval_interval_ms",
                       "slo_monitor", "flight_dir", "flight_keep",
                       "flight_cooldown_s"])
    set_flags({
        "slo_monitor": True,
        "slo_eval_interval_ms": interval_ms,
        # p95 target far under the injected 60 ms stall; budget 0.2
        # means a fully-bad fast window burns at 5x (>= the scaled
        # fast_burn threshold below) — trips in 2 evaluations
        "serving_slo": ("m:p95_ms=25,budget=0.2,fast_window=%d,"
                        "slow_window=10,fast_burn=5,breach_evals=2,"
                        "recover_evals=2" % fast_window),
        "flight_dir": flight_dir,
        "flight_keep": 8,
        "flight_cooldown_s": 30.0,
    })

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 5
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        pred = fluid.layers.fc(input=x, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = os.path.join(tempfile.mkdtemp(prefix="chaos_slo_"), "m")
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main_p)

    server = InferenceServer(max_queue=64).start()
    cli = ServingClient(server.endpoint)
    x_req = np.linspace(-1, 1, 8, dtype=np.float32).reshape(1, 8)
    try:
        cli.load_model("m", md, buckets=[2, 4])
        ref = cli.infer("m", {"x": x_req}, deadline_ms=10000)
        # let a couple of clean evaluations land: state must be ok
        time.sleep(3 * interval_ms / 1000.0)
        h = cli.health()
        assert h["slo"]["m"]["state"] == "ok", \
            "clean traffic reads %r" % h["slo"]["m"]
        assert h["models"]["m"]["lanes"]["fp32"]["liveness"][
            "router_alive"], "router not alive in health readout"

        # phase 2: inject latency, drive traffic, require detection
        # within 2 evaluation windows (2 * fast_window ticks) + one
        # interval of sampling slack
        set_dispatch_delay(0.06)
        detect_budget = (2 * fast_window + 1) * interval_ms / 1000.0
        t0 = time.monotonic()
        breach_at = None
        while time.monotonic() - t0 < detect_budget + 2.0:
            cli.infer("m", {"x": x_req}, deadline_ms=10000)
            if obs_events.recent_events(kind="slo_breach"):
                breach_at = time.monotonic() - t0
                break
        assert breach_at is not None, \
            "no slo_breach within %.1fs" % (detect_budget + 2.0)
        assert breach_at <= detect_budget, \
            "breach detected after %.2fs — budget is 2 evaluation " \
            "windows (%.2fs)" % (breach_at, detect_budget)
        assert cli.health()["slo"]["m"]["state"] == "breach"

        # phase 3: exactly one bundle (cooldown absorbs the storm),
        # complete and valid
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            bundles = flightrec.list_bundles(flight_dir)
            if bundles:
                break
            time.sleep(0.05)
        assert bundles, "breach never produced a flight bundle"
        # keep breaching a while longer: still one bundle
        for _ in range(10):
            cli.infer("m", {"x": x_req}, deadline_ms=10000)
        assert len(flightrec.list_bundles(flight_dir)) == 1, \
            "cooldown failed: breach storm wrote %d bundles" \
            % len(flightrec.list_bundles(flight_dir))
        problems = flightrec.validate_bundle(bundles[0])
        assert not problems, "bundle invalid: %s" % problems
        assert flight_inspect.main([flight_dir, "--validate"]) == 0, \
            "flight_inspect --validate rejected a fresh bundle"
        manifest = flightrec.read_manifest(bundles[0])
        assert manifest["reason"] == "slo_breach"
        # the bundle must carry the server snapshot + SLO timeline
        server_files = [n for n in manifest["files"]
                        if n.startswith("serving_")]
        assert server_files, "bundle missing the server snapshot"
        with open(os.path.join(bundles[0], server_files[0])) as f:
            snap = json.load(f)
        assert snap.get("slo_timeline", {}).get("m"), \
            "bundle missing the SLO metrics timeline"

        # phase 4: recovery — exactly one slo_recovered, bits intact
        set_dispatch_delay(0.0)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0:
            cli.infer("m", {"x": x_req}, deadline_ms=10000)
            if obs_events.recent_events(kind="slo_recovered"):
                break
            time.sleep(0.05)
        recovered = obs_events.recent_events(kind="slo_recovered")
        assert len(recovered) == 1, \
            "expected exactly one slo_recovered, got %d" % len(recovered)
        assert cli.health()["slo"]["m"]["state"] == "ok"
        out = cli.infer("m", {"x": x_req}, deadline_ms=10000)
        assert np.array_equal(out[0], ref[0]), \
            "SLO monitoring changed reply bits"
    finally:
        set_dispatch_delay(0.0)
        try:
            cli.close()
        finally:
            server.shutdown(drain=False, timeout=5.0)
            set_flags(saved)

    # phase 5: REAL kill mid-dump — prior bundles survive intact
    # (kill_phase=False = the tier-1 in-process subset; the ci_checks
    # `slo` gate always runs the kill)
    if not kill_phase:
        if verbose:
            print("PASS slo-breach (no-kill subset): detected in "
                  "%.2fs (budget %.2fs)" % (breach_at, detect_budget))
        return {"breach_s": breach_at, "budget_s": detect_budget}
    kill_dir = os.path.join(workdir, "flight_kill")
    env = dict(os.environ)
    env["PADDLE_TPU_CHAOS"] = "flight_committed=exit@2"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--child-flight", kill_dir],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 137, \
        "child should die at flight_committed@2 (rc=%d, out=%s)" \
        % (proc.returncode, proc.stdout + proc.stderr)
    assert "CHILD_BUNDLE_1" in proc.stdout
    assert "CHILD_BUNDLE_2_COMMITTED" not in proc.stdout
    survivors = flightrec.list_bundles(kill_dir)
    assert len(survivors) == 1, \
        "kill mid-dump should leave exactly the prior bundle: %s" \
        % survivors
    assert not flightrec.validate_bundle(survivors[0]), \
        "prior bundle corrupted by the mid-dump kill"
    stale = glob.glob(os.path.join(kill_dir, "_tmp.flight_*"))
    assert len(stale) == 1, "expected one stale tmp dir, got %s" % stale
    # recovery: a fresh dump sweeps the stale tmp and commits
    env.pop("PADDLE_TPU_CHAOS")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--child-flight", kill_dir],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert not glob.glob(os.path.join(kill_dir, "_tmp.flight_*")), \
        "recovery dump did not sweep the stale tmp dir"
    survivors = flightrec.list_bundles(kill_dir)
    assert len(survivors) == 3, \
        "recovery should add 2 bundles to the survivor: %s" % survivors
    for b in survivors:
        assert not flightrec.validate_bundle(b)

    if verbose:
        print("PASS slo-breach: detected in %.2fs (budget %.2fs), "
              "state ok->breach->ok, 1 bundle under cooldown "
              "(valid, with server snapshot + SLO timeline), exactly "
              "1 slo_recovered, replies bit-exact, kill@"
              "flight_committed left prior bundle intact + tmp swept"
              % (breach_at, detect_budget))
    return {"breach_s": breach_at, "budget_s": detect_budget}


def scenario_flash_crowd(verbose=True):
    """The fleet controller, end to end (SERVING.md "Fleet
    controller"): diurnal two-model traffic, then a flash crowd on the
    COLD model — a pattern a static single-replica placement provably
    sheds on, which the controller must hold the SLO across.

    1. two models serve (hot + cold, distinct weights); the cold model
       declares an SLO + a fleet policy ([1,3] replicas, ~1s page
       TTL); reference replies are captured for the bit-exactness
       check;
    2. diurnal phase: traffic stays on the hot model — the idle cold
       model must PAGE OUT (fleet_paged_out event, load spec
       persisted, hot traffic untouched);
    3. flash crowd: an open-loop burst on the cold model at ~3x one
       lane's capacity.  The first request FAULTS the model back in
       (fleet_fault_in event, measured fault_in_ms, warm compile
       cache), queue pressure + the SLO breach drive scale-up within
       the [min,max] policy, and EVERY request must be answered
       exactly once, bit-identical to the pre-page captures — zero
       dropped, zero double-answered;
    4. the breach must RECOVER (slo_recovered) once the crowd drains —
       breach-without-recovery fails the scenario;
    5. the STATIC control: the same burst against the same serving
       shape without the controller (one pinned replica, no paging)
       must drop requests — proving the traffic pattern actually
       exceeds a static placement, so the hold in (3) is the
       controller's doing."""
    import tempfile
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.flags import set_flags, get_flags
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import (DeadlineExceeded, InferenceServer,
                                    ServerOverloaded, ServingClient,
                                    ServingError, set_dispatch_delay)

    def build(seed, tag):
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = startup.random_seed = seed
        with fluid.program_guard(main_p, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            pred = fluid.layers.fc(input=x, size=4, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            md = os.path.join(tempfile.mkdtemp(prefix="chaos_fleet_"),
                              tag)
            fluid.save_inference_model(md, ["x"], [pred], exe,
                                       main_program=main_p)
        return md

    md_hot, md_cold = build(5, "hot"), build(11, "cold")
    x_req = np.linspace(-1, 1, 8, dtype=np.float32).reshape(1, 8)
    STEP_S = 0.1          # injected per-dispatch cost: 10 rps per lane
    FLASH_K = 60          # burst size
    FLASH_QPS = 30.0      # ~3x one lane, <= the 3-replica policy cap
    DEADLINE_MS = 2500.0

    def open_loop(endpoint, model, k, qps, deadline_ms):
        """Fire k requests on an open-loop schedule; every request is
        accounted exactly once: (ok latencies in fire order, failures).
        Clients retry sheds under their deadline — a DROP is a request
        that never got an answer."""
        results = [None] * k
        threads = []

        def fire(i):
            cli = ServingClient(endpoint)
            delay = i / qps
            time.sleep(delay)
            t0 = time.monotonic()
            try:
                out = cli.infer(model, {"x": x_req},
                                deadline_ms=deadline_ms)
                results[i] = ("ok", (time.monotonic() - t0) * 1e3,
                              out[0])
            except (ServerOverloaded, DeadlineExceeded, ServingError,
                    ConnectionError, OSError, EOFError) as e:
                results[i] = ("fail", type(e).__name__, None)
            finally:
                cli.close()

        for i in range(k):
            t = threading.Thread(target=fire, args=(i,), daemon=True)
            threads.append(t)
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), \
            "flash requests HUNG"
        assert all(r is not None for r in results), "lost accounting"
        return results

    saved = get_flags(["serving_slo", "slo_eval_interval_ms",
                       "slo_monitor", "fleet_controller",
                       "fleet_eval_interval_ms", "fleet_policy",
                       "fleet_dry_run", "flight_dir"])
    set_flags({
        "slo_monitor": True,
        "slo_eval_interval_ms": 100.0,
        # p95 far under the queue wait a backlog builds; budget 0.2
        # makes a fully-bad fast window burn at 5x (= fast_burn)
        "serving_slo": ("cold:p95_ms=200,budget=0.2,fast_window=3,"
                        "slow_window=10,fast_burn=5,breach_evals=2,"
                        "recover_evals=2"),
        "fleet_controller": True,
        "fleet_eval_interval_ms": 100.0,
        "fleet_dry_run": False,
        "flight_dir": "",
    })

    # ---- the controller run -------------------------------------------
    server = InferenceServer(max_queue=24).start()
    cli = ServingClient(server.endpoint)
    flash = None
    try:
        cli.load_model("hot", md_hot, buckets=[1])
        cli.load_model(
            "cold", md_cold, buckets=[1],
            fleet_policy=("min_replicas=1,max_replicas=3,"
                          "page_ttl_s=1.0,page_cooldown_s=0.5,"
                          "scale_up_queue=3,scale_cooldown_s=0.4,"
                          "scale_down_idle_s=60"))
        ref_hot = cli.infer("hot", {"x": x_req}, deadline_ms=10000)
        ref_cold = cli.infer("cold", {"x": x_req}, deadline_ms=10000)
        assert not np.array_equal(ref_hot[0], ref_cold[0]), \
            "hot/cold fixtures degenerate (same weights)"
        set_dispatch_delay(STEP_S)

        # phase 2: diurnal — hot-only traffic; the idle cold model
        # must page out within its TTL (+ a couple of ticks of slack)
        t0 = time.monotonic()
        paged = False
        while time.monotonic() - t0 < 8.0:
            cli.infer("hot", {"x": x_req}, deadline_ms=10000)
            if server.registry.paged_models().get("cold"):
                paged = True
                break
            time.sleep(0.05)
        assert paged, "idle cold model never paged out"
        assert obs_events.recent_events(kind="fleet_paged_out"), \
            "page-out not evented"
        desc = server.registry.describe().get("cold") or {}
        assert desc.get("paged") and desc.get("lanes") == ["fp32"], \
            "paged record lost the lane set: %r" % (desc,)
        # hot is untouched by the page
        out = cli.infer("hot", {"x": x_req}, deadline_ms=10000)
        assert np.array_equal(out[0], ref_hot[0])

        # phase 3: flash crowd on the paged cold model
        results = open_loop(server.endpoint, "cold", FLASH_K,
                            FLASH_QPS, DEADLINE_MS)
        oks = [r for r in results if r[0] == "ok"]
        fails = [r for r in results if r[0] == "fail"]
        assert not fails, \
            "controller run DROPPED %d/%d requests: %s" \
            % (len(fails), FLASH_K,
               sorted(set(f[1] for f in fails)))
        assert len(oks) == FLASH_K, "request accounting broke"
        for r in oks:  # answered once, bit-exact vs pre-page captures
            assert np.array_equal(r[2], ref_cold[0]), \
                "flash reply diverged from the pre-page reference"
        flash = {"ttfr_ms": round(oks[0][1], 1),
                 "p95_ms": round(sorted(r[1] for r in oks)[
                     int(0.95 * (len(oks) - 1))], 1)}
        fi = obs_events.recent_events(kind="fleet_fault_in")
        assert fi, "flash crowd never faulted the cold model in"
        assert fi[-1].get("fault_in_ms") is not None
        flash["fault_in_ms"] = fi[-1]["fault_in_ms"]
        ups = obs_events.recent_events(kind="fleet_scale_up")
        assert ups, "controller never scaled the cold model up"
        assert all(u.get("to_replicas", 0) <= 3 for u in ups), \
            "scale-up escaped the max_replicas policy"
        breaches = obs_events.recent_events(kind="slo_breach")
        assert any(b.get("model") == "cold" for b in breaches), \
            "flash crowd never breached the declared SLO"

        # phase 4: recovery — light traffic until the state machine
        # returns to ok; breach-without-recovery is the failure mode
        set_dispatch_delay(0.0)
        t0 = time.monotonic()
        recovered = False
        while time.monotonic() - t0 < 12.0:
            cli.infer("cold", {"x": x_req}, deadline_ms=10000)
            if any(e.get("model") == "cold" for e in
                   obs_events.recent_events(kind="slo_recovered")):
                recovered = True
                break
            time.sleep(0.1)
        assert recovered, "SLO breached and never recovered"
        out = cli.infer("cold", {"x": x_req}, deadline_ms=10000)
        assert np.array_equal(out[0], ref_cold[0]), \
            "post-recovery reply bits diverged"
        fleet_status = cli.fleet()
        assert fleet_status.get("enabled") and fleet_status["models"]
    finally:
        set_dispatch_delay(0.0)
        try:
            cli.close()
        finally:
            server.shutdown(drain=False, timeout=5.0)

    # ---- the static control -------------------------------------------
    # same serving shape, no controller: one pinned replica, no paging.
    # The same burst must DROP requests — the pattern really does
    # exceed a static placement.
    set_flags({"fleet_controller": False, "serving_slo": ""})
    server2 = InferenceServer(max_queue=24).start()
    cli2 = ServingClient(server2.endpoint)
    try:
        cli2.load_model("cold", md_cold, buckets=[1])
        cli2.infer("cold", {"x": x_req}, deadline_ms=10000)  # warm
        set_dispatch_delay(STEP_S)
        results = open_loop(server2.endpoint, "cold", FLASH_K,
                            FLASH_QPS, DEADLINE_MS)
        static_fails = [r for r in results if r[0] == "fail"]
        assert static_fails, \
            "static placement survived the flash crowd — the scenario " \
            "no longer proves anything; raise the burst"
    finally:
        set_dispatch_delay(0.0)
        try:
            cli2.close()
        finally:
            server2.shutdown(drain=False, timeout=5.0)
            set_flags(saved)

    if verbose:
        print("PASS flash-crowd: paged out on TTL, fault-in %.0fms, "
              "flash %d/%d answered bit-exact (TTFR %.0fms, p95 "
              "%.0fms), breach -> recovered, scale-up within [1,3]; "
              "static control dropped %d/%d"
              % (flash["fault_in_ms"], FLASH_K, FLASH_K,
                 flash["ttfr_ms"], flash["p95_ms"],
                 len(static_fails), FLASH_K))
    return {"fault_in_ms": flash["fault_in_ms"],
            "flash_ttfr_ms": flash["ttfr_ms"],
            "flash_p95_ms": flash["p95_ms"],
            "static_dropped": len(static_fails),
            "flash_k": FLASH_K}


def _child_backend(frontend, backend_id, slow_ms=0.0):
    """Subprocess target (--child-backend): one federated backend — an
    InferenceServer that registers with the front-door `frontend` and
    heartbeats until the parent kills it.  Models arrive via the
    frontend's load_model fan-out; `slow_ms` stretches every dispatch
    so "mid-stream" is unambiguous when the parent delivers SIGKILL."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.serving import InferenceServer, set_dispatch_delay
    set_flags({"federation_heartbeat_ms": 200.0,
               "compile_cache": False})
    srv = InferenceServer(federation=frontend,
                          backend_id=backend_id).start()
    if slow_ms:
        set_dispatch_delay(slow_ms / 1000.0)
    print("BACKEND_READY %s %s" % (backend_id, srv.endpoint),
          flush=True)
    while True:
        time.sleep(3600)


def _spawn_backend_child(frontend, backend_id, slow_ms):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child-backend",
         frontend, "--backend-id", backend_id,
         "--slow-ms", str(slow_ms)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)


def scenario_backend_kill(workdir, verbose=True):
    """Federated serving under backend loss (SERVING.md "Federated
    serving"): two backend SUBPROCESSES register with an in-process
    FrontendServer, concurrent decode streams ride the router's
    session affinity across both, and one backend takes a real SIGKILL
    mid-stream.  Required invariants:

    1. blast radius — ONLY streams pinned to the killed backend fail,
       each with a typed StreamBroken naming that backend and the
       token count already committed (the relayed chunks are a prefix
       of the reference, never garbage); streams on the survivor
       complete bit-identical to a direct greedy decode; NOTHING
       hangs;
    2. membership — the lost lease leaves the accepting set within one
       heartbeat TTL of the kill (transport evidence beats the TTL:
       the relay's failed read suspects it immediately) and lands in
       the lost list with a backend_lost event;
    3. re-placement — a new stream for a broken session re-places on
       the survivor and answers its FIRST token within one TTL,
       bit-exact from token 0 (the dead backend's KV is gone; the
       stream restarts, never resumes);
    4. accounting — streams_broken == the victim's in-flight streams,
       shed == 0 (loss must not masquerade as overload)."""
    import tempfile
    from paddle_tpu.federation import FrontendServer
    from paddle_tpu.flags import set_flags, get_flags
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             build_tiny_decode_model,
                                             greedy_decode)
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import ServingClient, StreamBroken

    TTL = 2.0        # lease TTL; children beat at 200 ms
    K = 4            # concurrent streams (affinity spreads them 2+2)
    BUDGET = 48      # tokens per stream
    STEP_MS = 60.0   # child-side per-dispatch stall
    os.makedirs(workdir, exist_ok=True)
    md = build_tiny_decode_model(
        os.path.join(workdir, "lm"), vocab_size=64, d_model=32,
        n_heads=4, n_layers=2, max_seq_len=64, eos_id=-1, seed=21)
    pred = GenerativePredictor(md)
    prompts = [[3, 5, 7], [9, 4], [11, 12, 13], [2, 6]]
    refs = [greedy_decode(pred, p, BUDGET)[0] for p in prompts]

    saved = get_flags(["federation_heartbeat_ms"])
    set_flags({"federation_heartbeat_ms": 200.0})
    fe = FrontendServer(ttl_s=TTL).start()
    boot = ServingClient(fe.endpoint)
    procs = {}
    try:
        for bid in ("be0", "be1"):
            procs[bid] = _spawn_backend_child(fe.endpoint, bid, STEP_MS)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 90.0:
            if len(fe.membership.backends(accepting_only=True)) == 2:
                break
            time.sleep(0.05)
        live = fe.membership.backends(accepting_only=True)
        assert len(live) == 2, \
            "backends never registered with the frontend: %s" \
            % sorted(live)
        boot.load_model("lm", md, decode_slots=4)  # fan-out to both

        toks = [[] for _ in range(K)]
        errors = [None] * K

        def stream(i):
            c = ServingClient(fe.endpoint)
            try:
                for ch in c.infer_stream("lm", prompts[i],
                                         max_new_tokens=BUDGET,
                                         deadline_ms=120000.0,
                                         trace_id="bk%d" % i):
                    toks[i].extend(ch)
            except StreamBroken as e:
                errors[i] = e
            except Exception as e:   # anything untyped fails the run
                errors[i] = e
            finally:
                c.close()

        threads = []
        for i in range(K):
            t = threading.Thread(target=stream, args=(i,), daemon=True)
            threads.append(t)
            t.start()
            time.sleep(0.15)   # let inflight counts settle placement
        t0 = time.monotonic()
        while time.monotonic() - t0 < 30.0:
            if all(len(ts) >= 2 for ts in toks):
                break
            time.sleep(0.02)
        assert all(len(ts) >= 2 for ts in toks), \
            "streams never got going: %s" % [len(ts) for ts in toks]
        pins = {i: fe._affinity.get("bk%d" % i) for i in range(K)}
        by_bid = {}
        for i, b in pins.items():
            by_bid.setdefault(b, []).append(i)
        assert len(by_bid) == 2 and None not in by_bid, \
            "placement did not spread the streams: %r" % pins
        victim_bid = min(by_bid, key=lambda b: (len(by_bid[b]), b))
        survivor_bid = next(b for b in by_bid if b != victim_bid)
        victims = by_bid[victim_bid]
        survivors = by_bid[survivor_bid]

        # ---- the kill: a real SIGKILL mid-stream -------------------
        kill_t = time.monotonic()
        os.kill(procs[victim_bid].pid, signal.SIGKILL)
        procs[victim_bid].wait(timeout=10)
        evicted_s = None
        while time.monotonic() - kill_t < TTL + 2.0:
            if victim_bid not in fe.membership.backends(
                    accepting_only=True):
                evicted_s = time.monotonic() - kill_t
                break
            time.sleep(0.02)
        assert evicted_s is not None and evicted_s <= TTL + 0.5, \
            "lost backend still accepting %.2fs after SIGKILL " \
            "(TTL %.1fs)" % (evicted_s or -1.0, TTL)
        for t in threads:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in threads), \
            "streams HUNG after the backend kill"

        # (1) blast radius: typed loss for victims, bit-exact survivors
        for i in victims:
            e = errors[i]
            assert isinstance(e, StreamBroken), \
                "victim stream %d surfaced %r, want StreamBroken" \
                % (i, e)
            assert e.backend == victim_bid, \
                "StreamBroken names %r, want %r" % (e.backend,
                                                    victim_bid)
            assert e.received == len(toks[i]) >= 2, \
                "committed-token accounting broke: received=%d, " \
                "yielded=%d" % (e.received, len(toks[i]))
            assert toks[i] == refs[i][:len(toks[i])], \
                "victim %d's committed chunks are not a reference " \
                "prefix" % i
        for i in survivors:
            assert errors[i] is None, \
                "survivor stream %d failed: %r" % (i, errors[i])
            assert toks[i] == refs[i], \
                "survivor stream %d not bit-exact" % i

        # (2) membership: lost list + event
        assert victim_bid in fe.membership.lost(), \
            "killed backend missing from the lost list"
        assert any(e.get("backend") == victim_bid for e in
                   obs_events.recent_events(kind="backend_lost")), \
            "no backend_lost event for the killed backend"

        # (3) re-placement: the broken session restarts on the
        # survivor, first token within one TTL, bit-exact from 0
        rv = victims[0]
        c = ServingClient(fe.endpoint)
        try:
            t0 = time.monotonic()
            out, first_tok_s = [], None
            for ch in c.infer_stream("lm", prompts[rv],
                                     max_new_tokens=BUDGET,
                                     deadline_ms=120000.0,
                                     trace_id="bk%d" % rv):
                if first_tok_s is None:
                    first_tok_s = time.monotonic() - t0
                out.extend(ch)
        finally:
            c.close()
        assert first_tok_s is not None and first_tok_s <= TTL, \
            "re-placed stream's first token took %.2fs (TTL %.1fs)" \
            % (first_tok_s or -1.0, TTL)
        assert out == refs[rv], "re-placed stream not bit-exact"
        assert fe._affinity.get("bk%d" % rv) == survivor_bid, \
            "re-placed session not pinned to the survivor"

        # (4) accounting: loss is loss, not overload
        assert fe._counters["streams_broken"] == len(victims), \
            "streams_broken=%d, want %d" \
            % (fe._counters["streams_broken"], len(victims))
        assert fe._counters["shed"] == 0, \
            "backend loss was shed as overload (%d sheds)" \
            % fe._counters["shed"]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        boot.close()
        fe.shutdown()
        set_flags(saved)
    if verbose:
        print("PASS backend-kill: %d/%d streams on the victim broke "
              "typed (committed prefixes intact), %d survivor "
              "stream(s) bit-exact, lease evicted %.2fs after SIGKILL "
              "(TTL %.1fs), re-placed session first token %.2fs on "
              "the survivor, shed=0, zero hangs"
              % (len(victims), K, len(survivors), evicted_s, TTL,
                 first_tok_s))
    return {"victims": len(victims), "survivors": len(survivors),
            "evicted_s": round(evicted_s, 3),
            "replace_first_token_s": round(first_tok_s, 3)}


def run_smoke(workdir):
    """Tier-1 smoke: deterministic crash at every commit point + the
    bit-flip rejection — no timing races, CPU-only, a few seconds."""
    ok = True
    for point in CHAOS_POINTS:
        d = os.path.join(workdir, "crash_%s" % point)
        try:
            scenario_crash_save(d, point=point, crash_at_save=2,
                                real_kill=False, steps=4)
        except AssertionError as e:
            ok = False
            print("FAIL crash-save %s: %s" % (point, e))
    try:
        scenario_bit_flip(workdir)
    except AssertionError as e:
        ok = False
        print("FAIL bit-flip: %s" % e)
    print("CHAOS SMOKE %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", choices=["crash-save", "bit-flip",
                                           "nan-poison", "drop-rpc",
                                           "serving-overload",
                                           "cache-commit",
                                           "quantize-commit",
                                           "trace-overflow",
                                           "decode-disconnect",
                                           "decode-disconnect-int8",
                                           "decode-disconnect-fused",
                                           "spec-fallback",
                                           "mesh-member-loss",
                                           "slo-breach",
                                           "flash-crowd",
                                           "backend-kill", "all"])
    ap.add_argument("--smoke", action="store_true",
                    help="fast deterministic subset for CI")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--point", default="manifest_written",
                    choices=CHAOS_POINTS + CACHE_POINTS + QUANT_POINTS
                    + FLIGHT_POINTS)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--no-real-kill", action="store_true",
                    help="child os._exit(137)s at the point instead of "
                         "being SIGKILLed while paused there")
    ap.add_argument("--child-train", metavar="DIR",
                    help=argparse.SUPPRESS)  # internal subprocess target
    ap.add_argument("--child-cache", metavar="DIR",
                    help=argparse.SUPPRESS)  # internal subprocess target
    ap.add_argument("--child-quant", metavar="DIR",
                    help=argparse.SUPPRESS)  # internal subprocess target
    ap.add_argument("--child-flight", metavar="DIR",
                    help=argparse.SUPPRESS)  # internal subprocess target
    ap.add_argument("--child-backend", metavar="ENDPOINT",
                    help=argparse.SUPPRESS)  # internal subprocess target
    ap.add_argument("--backend-id", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--slow-ms", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--chaos-spec", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--chaos-at-save", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child_train:
        _child_train(args.child_train, args.steps, args.chaos_spec,
                     args.chaos_at_save)
        return 0
    if args.child_cache:
        _child_cache(args.child_cache)
        return 0
    if args.child_quant:
        _child_quant(args.child_quant)
        return 0
    if args.child_flight:
        _child_flight(args.child_flight)
        return 0
    if args.child_backend:
        _child_backend(args.child_backend, args.backend_id,
                       slow_ms=args.slow_ms)
        return 0

    import tempfile
    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_")
    if args.smoke:
        return run_smoke(workdir)
    if args.scenario in (None, "all"):
        scenarios = ["crash-save", "bit-flip", "nan-poison", "drop-rpc",
                     "serving-overload", "cache-commit",
                     "quantize-commit", "trace-overflow",
                     "decode-disconnect", "decode-disconnect-int8",
                     "decode-disconnect-fused",
                     "spec-fallback", "mesh-member-loss",
                     "slo-breach", "flash-crowd",
                     "backend-kill"]
    else:
        scenarios = [args.scenario]
    rc = 0
    for s in scenarios:
        try:
            if s == "crash-save":
                point = args.point if args.point in CHAOS_POINTS \
                    else "manifest_written"
                scenario_crash_save(
                    os.path.join(workdir, "crash"), point=point,
                    real_kill=not args.no_real_kill, steps=args.steps)
            elif s == "cache-commit":
                point = args.point if args.point in CACHE_POINTS \
                    else "cc_exec_written"
                scenario_cache_commit(
                    os.path.join(workdir, "cache"), point=point,
                    real_kill=not args.no_real_kill)
            elif s == "quantize-commit":
                point = args.point if args.point in QUANT_POINTS \
                    else "quant_arrays_written"
                scenario_quantize_commit(
                    os.path.join(workdir, "quant"), point=point,
                    real_kill=not args.no_real_kill)
            elif s == "bit-flip":
                scenario_bit_flip(workdir)
            elif s == "nan-poison":
                scenario_nan_poison()
            elif s == "drop-rpc":
                scenario_drop_rpc()
            elif s == "serving-overload":
                scenario_serving_overload()
            elif s == "trace-overflow":
                scenario_trace_overflow(
                    os.path.join(workdir, "trace_overflow"))
            elif s == "decode-disconnect":
                scenario_decode_disconnect()
            elif s == "decode-disconnect-int8":
                # the same invariants under the QUANTIZED slot table
                scenario_decode_disconnect(kv_dtype="int8")
            elif s == "decode-disconnect-fused":
                scenario_decode_disconnect_fused()
            elif s == "spec-fallback":
                scenario_spec_fallback()
            elif s == "mesh-member-loss":
                scenario_mesh_member_loss()
            elif s == "slo-breach":
                scenario_slo_breach(os.path.join(workdir, "slo_breach"))
            elif s == "flash-crowd":
                scenario_flash_crowd()
            elif s == "backend-kill":
                scenario_backend_kill(
                    os.path.join(workdir, "backend_kill"))
        except AssertionError as e:
            rc = 1
            print("FAIL %s: %s" % (s, e))
    return rc


if __name__ == "__main__":
    sys.exit(main())
