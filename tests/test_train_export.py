"""AOT training export: train from a saved artifact with no Program and
no trace — in Python (AotTrainer) and from pure C (capi_train_demo).

Reference analogue: the C++ train/demo
(paddle/fluid/train/demo/demo_trainer.cc, train/test_train_recognize_
digits.cc) — training driven from a saved program by a non-Python host.
Here the artifact is a versioned StableHLO module of the WHOLE optimizer
step plus wire-encoded state; parity is exact against the live Executor.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.train_export import save_aot_trainer, load_aot_trainer

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
NATIVE = os.path.join(REPO, "native")


def _build():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(fluid.layers.square(pred - y))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
    return main, startup, loss


def _feeds(n, batch=4, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    return [{"x": rng.randn(batch, 8).astype(np.float32),
             "y": rng.randn(batch, 1).astype(np.float32)}
            for _ in range(n)]


def test_aot_trainer_matches_executor(tmp_path):
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feeds = _feeds(6)
    art = str(tmp_path / "art")
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_aot_trainer(art, main, ["x", "y"], [loss], scope=scope,
                         batch_size=4)
        ref = [float(np.asarray(exe.run(main, feed=f,
                                        fetch_list=[loss])[0]).ravel()[0])
               for f in feeds]

    t = load_aot_trainer(art)
    got = [float(t.step(f)[0].ravel()[0]) for f in feeds[:3]]
    np.testing.assert_allclose(ref[:3], got, rtol=1e-5)

    # checkpoint mid-trajectory, resume in a new handle: exact continuation
    ck = str(tmp_path / "ck")
    t.save(ck)
    t2 = load_aot_trainer(ck)
    assert t2.step_count == 3
    got2 = [float(t2.step(f)[0].ravel()[0]) for f in feeds[3:]]
    np.testing.assert_allclose(ref[3:], got2, rtol=1e-5)


def test_aot_trainer_fresh_process_no_trace(tmp_path):
    """A new process must train from the artifact WITHOUT tracing: jit
    compilation of new computations is poisoned in the child."""
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feeds = _feeds(3)
    art = str(tmp_path / "art")
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_aot_trainer(art, main, ["x", "y"], [loss], scope=scope,
                         batch_size=4)
        ref = [float(np.asarray(exe.run(main, feed=f,
                                        fetch_list=[loss])[0]).ravel()[0])
               for f in feeds]

    child = r"""
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
# poison tracing: deserialized-module calls must not build new jaxprs
import jax._src.interpreters.partial_eval as pe
def _no_trace(*a, **k):
    raise AssertionError("tracing happened in the AOT child")
pe.trace_to_jaxpr_dynamic = _no_trace
from paddle_tpu.fluid.train_export import load_aot_trainer
t = load_aot_trainer(sys.argv[1])
rng = np.random.RandomState(0)
for _ in range(3):
    f = {"x": rng.randn(4, 8).astype(np.float32),
         "y": rng.randn(4, 1).astype(np.float32)}
    print("%.6f" % float(t.step(f)[0].ravel()[0]))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", child, art],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = [float(v) for v in proc.stdout.strip().splitlines()]
    np.testing.assert_allclose(ref, got, rtol=1e-5)


def test_feed_validation(tmp_path):
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    art = str(tmp_path / "art")
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_aot_trainer(art, main, ["x", "y"], [loss], scope=scope,
                         batch_size=4)
    t = load_aot_trainer(art)
    with pytest.raises(ValueError):
        t.step({"x": np.zeros((2, 8), np.float32),
                "y": np.zeros((2, 1), np.float32)})   # wrong batch
    with pytest.raises(KeyError):
        t.step({"x": np.zeros((4, 8), np.float32)})   # missing feed


@pytest.fixture(scope="module")
def train_demo_bin():
    if not os.path.exists("/usr/bin/gcc") and not os.path.exists(
            "/usr/bin/cc") and not os.path.exists("/usr/local/bin/gcc"):
        pytest.skip("no C toolchain")
    subprocess.run(["make", "libpaddle_tpu_capi.so", "capi_train_demo"],
                   cwd=NATIVE, check=True, capture_output=True,
                   timeout=600)
    return os.path.join(NATIVE, "capi_train_demo")


def test_c_trainer_matches_python(train_demo_bin, tmp_path):
    """The pure-C client trains the artifact, checkpoints halfway,
    resumes from the checkpoint, and every loss matches an in-process
    AotTrainer driven with the same deterministic feeds."""
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    art = str(tmp_path / "art")
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_aot_trainer(art, main, ["x", "y"], [loss], scope=scope,
                         batch_size=4)

    steps, batch, feat = 6, 4, 8

    def c_batch(step):
        # mirrors fill_batch() in capi_train_demo.c
        x = np.array([((i + 13 * step) * 37 % 65) - 32.0
                      for i in range(batch * feat)],
                     np.float32).reshape(batch, feat) / 32.0
        y = np.array([((i + 7 * step) * 29 % 33) - 16.0
                      for i in range(batch)],
                     np.float32).reshape(batch, 1) / 16.0
        return {"x": x, "y": y}

    t = load_aot_trainer(art)
    ref = [float(t.step(c_batch(s))[0].ravel()[0]) for s in range(steps)]

    ck = str(tmp_path / "ck")
    env = dict(os.environ)
    env["PD_CAPI_PLATFORM"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [train_demo_bin, art, str(steps), str(batch), str(feat), ck],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, (proc.stdout[-500:], proc.stderr[-2000:])
    assert "CAPI-TRAIN-OK" in proc.stdout
    assert "resumed" in proc.stdout

    got = {}
    for line in proc.stdout.splitlines():
        if line.startswith("loss "):
            _, s, v = line.split()
            got[int(s)] = float(v)
    assert sorted(got) == list(range(steps))
    np.testing.assert_allclose(ref, [got[s] for s in range(steps)],
                               rtol=1e-4, atol=1e-6)


def test_multi_platform_artifact_serves_on_cpu(tmp_path):
    """platforms=("cpu","tpu") embeds both lowerings in ONE artifact:
    exported on this CPU host it must still train here, and the stored
    module must declare both platforms (so a TPU host accepts it)."""
    from jax import export as jax_export
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feeds = _feeds(2)
    art = str(tmp_path / "art")
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_aot_trainer(art, main, ["x", "y"], [loss], scope=scope,
                         batch_size=4, platforms=("cpu", "tpu"))
        ref = [float(np.asarray(exe.run(main, feed=f,
                                        fetch_list=[loss])[0]).ravel()[0])
               for f in feeds]
    with open(os.path.join(art, "train_step.bin"), "rb") as f:
        exp = jax_export.deserialize(f.read())
    assert set(p.lower() for p in exp.platforms) == {"cpu", "tpu"}
    t = load_aot_trainer(art)
    got = [float(t.step(f)[0].ravel()[0]) for f in feeds]
    np.testing.assert_allclose(ref, got, rtol=1e-5)
