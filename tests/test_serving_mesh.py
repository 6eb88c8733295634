"""Mesh-replica tests (SERVING.md "Mesh replicas").

One serving replica = a device mesh: params and the decode KV slot
table live SHARDED across the member chips (NamedSharding over the 1-D
"model" axis), compute runs replicated, so a mesh replica's replies
are bit-exact vs a single-device replica by construction.  Pins:

* placement grammar — 'mesh:N' / 'mesh:RxC' host packing, explicit
  'a+b' member lists, the 1-member/mesh:1 collapse to the legacy plain
  -device path, duplicate/unknown-member rejection, and the
  device_labels() -> resolve_placement round trip the fleet replay
  rides;
* params actually sharded — per-member addressable bytes strictly
  below the whole model, KV slot-table shards exactly 1/mesh;
* per-member fit pricing — analyze_artifact(mesh_size=m) /
  ResourceReport.per_device_bytes: a model whose static estimate
  exceeds one device's budget is REJECTED single-device and ADMITTED
  + served on a 2-chip mesh, stream bit-exact vs direct
  single-process execution (the ISSUE 19 acceptance pin);
* sharded int8 KV decode parity + the spec-twin accept==1.0 invariant
  riding the sharded program unchanged;
* mesh lanes in the serving stack — registry streams bit-exact, lane
  death on member loss is typed (sibling lanes unaffected), stats
  carry mesh shape, hot swap of a whole mesh lane set under hammer
  keeps every reply exactly one version's output.

Everything CPU-safe under JAX_PLATFORMS=cpu + the conftest's 8 forced
host devices.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.analysis.resources import (ResourceFitError,
                                           analyze_artifact)
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.inference.decode import (GenerativePredictor,
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.parallel.mesh import (MeshGroup, MeshMemberLost,
                                      as_mesh_group, set_member_poison)
from paddle_tpu.serving import ModelRegistry, resolve_placement

import jax


@pytest.fixture(autouse=True)
def _clear_poison():
    yield
    set_member_poison(None)


def _lm(tmp_path, name="lm", seed=7, **kw):
    kw.setdefault("vocab_size", 32)
    kw.setdefault("d_model", 16)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("eos_id", -1)
    return build_tiny_decode_model(str(tmp_path / name), seed=seed, **kw)


def _export_fc(tmp_path, seed, name="m"):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(input=x, size=6, act="relu")
        pred = fluid.layers.fc(input=h, size=6, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / name)
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main)
    return md


def _flat(stream_result):
    """DecodeStream.result() returns token-chunk arrays; flatten to a
    plain int list for comparison against greedy_decode."""
    chunks = [np.atleast_1d(np.asarray(c)) for c in stream_result]
    if not chunks:
        return []
    return [int(t) for t in np.concatenate(chunks)]


# ---------------------------------------------------------------------------
# placement grammar
# ---------------------------------------------------------------------------

class TestMeshPlacement:
    def test_mesh_string_packs_whole_host(self):
        groups = resolve_placement("mesh:2")
        assert len(groups) == jax.device_count() // 2
        assert all(isinstance(g, MeshGroup) and g.mesh_size == 2
                   for g in groups)
        # members partition the host: no chip serves two replicas
        labels = [l for g in groups for l in g.member_labels()]
        assert len(labels) == len(set(labels)) == jax.device_count()

    def test_mesh_rxc_dims(self):
        groups = resolve_placement("mesh:2x2")
        assert len(groups) == jax.device_count() // 4
        assert all(g.mesh_size == 4 for g in groups)

    def test_mesh_1_is_the_legacy_plain_path(self):
        # a 1-device mesh IS the pre-mesh behavior: plain jax.Device
        # replicas, no MeshGroup wrapper anywhere
        groups = resolve_placement("mesh:1")
        assert groups == list(jax.local_devices())
        assert all(as_mesh_group(d) is None for d in groups)

    def test_explicit_member_list(self):
        groups = resolve_placement("cpu:0+cpu:1,cpu:2+cpu:3")
        assert [g.mesh_size for g in groups] == [2, 2]
        assert groups[0].member_labels() == ["cpu:0", "cpu:1"]
        assert groups[1].member_labels() == ["cpu:2", "cpu:3"]
        # the mesh label is the "+"-joined member list — what
        # device_labels()/load specs persist
        assert groups[0].label() == "cpu:0+cpu:1"

    def test_single_member_collapses_to_plain_device(self):
        groups = resolve_placement("cpu:0,cpu:1+cpu:2")
        assert as_mesh_group(groups[0]) is None  # plain jax.Device
        assert groups[0].platform == "cpu" and groups[0].id == 0
        assert as_mesh_group(groups[1]).mesh_size == 2

    def test_label_round_trips_through_resolve(self):
        # the fleet fault-in/resize replay path: persisted labels must
        # rebuild the SAME mesh shape
        first = resolve_placement("cpu:0+cpu:1,cpu:2+cpu:3")
        labels = ",".join(g.label() for g in first)
        again = resolve_placement(labels)
        assert [g.member_labels() for g in again] \
            == [g.member_labels() for g in first]

    def test_rejects_overlapping_members(self):
        with pytest.raises(ValueError):
            resolve_placement("cpu:0+cpu:1,cpu:1+cpu:2")

    def test_rejects_member_doubling_as_plain_replica(self):
        with pytest.raises(ValueError):
            resolve_placement("cpu:0+cpu:1,cpu:1")

    def test_rejects_unknown_member_device(self):
        with pytest.raises(ValueError):
            resolve_placement("cpu:0+nope:7")

    def test_rejects_mesh_wider_than_host(self):
        with pytest.raises(ValueError):
            resolve_placement("mesh:%d" % (jax.device_count() * 2))

    def test_rejects_mesh_token_inside_a_list(self):
        with pytest.raises(ValueError):
            resolve_placement("mesh:2,cpu:0")


# ---------------------------------------------------------------------------
# params + KV actually sharded (not replicated) across members
# ---------------------------------------------------------------------------

class TestActuallySharded:
    def test_param_bytes_per_member_below_whole_model(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        pm = GenerativePredictor(md, device=MeshGroup(devs[:2]))
        total = sum(int(np.asarray(v).nbytes)
                    for v in pm._state_host.values())
        per = sum(int(s.data.nbytes) for v in pm._state.values()
                  for s in v.addressable_shards if s.device == devs[0])
        assert per < total, \
            "mesh member holds the WHOLE model (%d of %d bytes) — " \
            "params are replicated, not sharded" % (per, total)

    def test_kv_slot_table_shards_exactly_1_over_mesh(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        pm = GenerativePredictor(md, device=MeshGroup(devs[:2]))
        sess = pm.new_session(4)
        per = sum(int(s.data.nbytes) for s in sess._kc.addressable_shards
                  if s.device == devs[0])
        assert per * 2 == int(sess._kc.nbytes)


# ---------------------------------------------------------------------------
# per-member fit pricing (the ISSUE 19 acceptance pin)
# ---------------------------------------------------------------------------

class TestMeshFitCheck:
    # big enough that the estimate straddles an MB-granular budget:
    # ~5.7 MiB whole, ~2.9 MiB per 2-mesh member
    BIG = dict(vocab_size=64, d_model=128, n_heads=4, n_layers=2,
               max_seq_len=256)
    SLOTS = 8
    BUDGET_MB = 4

    def test_static_per_device_pricing(self, tmp_path):
        md = _lm(tmp_path, name="big", **self.BIG)
        rep = analyze_artifact(md, decode_slots=self.SLOTS)
        # mesh_size=1 is EXACTLY the legacy estimate
        assert rep.per_device_bytes(1) == rep.peak_bytes
        # sharded-at-rest bytes (params + KV slot table) price at
        # ceil(1/m); the replicated-compute activation peak does not
        sharded = rep.param_bytes + rep.kv_cache_bytes
        for m in (2, 4):
            assert rep.per_device_bytes(m) \
                == -(-sharded // m) + rep.activation_peak_bytes
        # analyze_artifact(mesh_size=) stamps the report: to_dict and
        # downstream consumers read per-device numbers directly
        rep2 = analyze_artifact(md, decode_slots=self.SLOTS,
                                mesh_size=2)
        d = rep2.to_dict()
        assert d["mesh_size"] == 2
        assert d["per_device_bytes"] == rep.per_device_bytes(2)
        # per-member KV bytes ~1/mesh statically
        assert rep.kv_cache_bytes // 2 \
            <= rep2.per_device_bytes() - rep2.activation_peak_bytes \
            - rep2.param_bytes // 2 + 1

    def test_rejected_single_device_admitted_on_2_mesh_bit_exact(
            self, tmp_path):
        md = _lm(tmp_path, name="big", **self.BIG)
        old = get_flags(["serving_device_mem_mb"])
        set_flags({"serving_device_mem_mb": self.BUDGET_MB})
        reg = ModelRegistry()
        try:
            with pytest.raises(ResourceFitError):
                reg.load_model("big", md, devices=["cpu:0"],
                               decode_slots=self.SLOTS)
            # the SAME model admits when the replica is a 2-chip mesh:
            # each member is priced at ~half the sharded bytes
            reg.load_model("big", md, devices=["cpu:0+cpu:1"],
                           decode_slots=self.SLOTS)
            info = reg.describe()["big"]
            assert info["mesh"] == [2]
            assert info["est_per_device_mb"] < self.BUDGET_MB \
                < info["est_peak_mb"]
            # ...and SERVES bit-exact vs direct single-process
            # execution on the unsharded artifact
            prompt = [3, 5, 7]
            ref, _ = greedy_decode(GenerativePredictor(md), prompt, 8,
                                   n_slots=self.SLOTS, slot=0)
            out = _flat(reg.submit_stream("big", prompt,
                                          max_new_tokens=8).result(
                                              timeout=300))
            assert out == ref
        finally:
            reg.close_all()
            set_flags(old)

    def test_draft_twin_priced_per_member_too(self, tmp_path):
        md = _lm(tmp_path, name="big", **self.BIG)
        old = get_flags(["serving_device_mem_mb"])
        # both target and twin draft shard across the mesh: 2x the
        # per-member bytes must still overflow a budget sized for one
        set_flags({"serving_device_mem_mb": self.BUDGET_MB})
        reg = ModelRegistry()
        try:
            with pytest.raises(ResourceFitError):
                reg.load_model("big", md, devices=["cpu:0+cpu:1"],
                               decode_slots=self.SLOTS, draft=md,
                               spec_k=2)
        finally:
            reg.close_all()
            set_flags(old)


# ---------------------------------------------------------------------------
# sharded decode parity: int8 KV + speculative twin ride unchanged
# ---------------------------------------------------------------------------

class TestShardedDecodeParity:
    def test_int8_kv_mesh_stream_bit_exact(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        prompt = [3, 5, 7, 9, 11]
        ref, _ = greedy_decode(
            GenerativePredictor(md, device=devs[0],
                                kv_cache_dtype="int8"),
            prompt, 12, n_slots=4, slot=1)
        out, _ = greedy_decode(
            GenerativePredictor(md, device=MeshGroup(devs[:2]),
                                kv_cache_dtype="int8"),
            prompt, 12, n_slots=4, slot=1)
        assert out == ref

    def test_spec_twin_on_mesh_accepts_exactly_all(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        group = MeshGroup(devs[:2])
        pm = GenerativePredictor(md, device=group)
        p8 = GenerativePredictor(md, device=group,
                                 kv_cache_dtype="int8")
        prompt = [3, 5, 7, 9, 11]
        ref, _ = greedy_decode(GenerativePredictor(md, device=devs[0]),
                               prompt, 12, n_slots=4, slot=1)
        spec = SpeculativeDecodeSession(pm, p8, 4, 2)
        got = [spec.prefill(1, prompt)]
        while len(got) < 12 and got[-1] != pm.eos_id:
            toks, counts = spec.step()
            got.extend(int(t) for t in toks[1][:counts[1]])
        assert got[:12] == ref
        # int8-twin drafting for the fp32 target on the SAME mesh:
        # accept rate must be exactly 1.0
        assert spec.proposed > 0 and spec.accepted == spec.proposed


# ---------------------------------------------------------------------------
# mesh lanes in the serving stack
# ---------------------------------------------------------------------------

class TestMeshServing:
    def test_streams_bit_exact_and_stats_carry_mesh(self, tmp_path):
        md = _lm(tmp_path)
        reg = ModelRegistry()
        try:
            reg.load_model("lm", md, devices=["cpu:0+cpu:1",
                                              "cpu:2+cpu:3"],
                           decode_slots=2)
            entry = reg._models["lm"]["versions"][1]
            assert entry.mesh_sizes() == [2, 2]
            assert entry.device_labels() == ["cpu:0+cpu:1",
                                             "cpu:2+cpu:3"]
            pred = GenerativePredictor(md)
            prompts = [[3, 5, 7], [9, 4], [11, 12, 13, 14], [2, 6]]
            refs = [greedy_decode(pred, p, 10)[0] for p in prompts]
            streams = [reg.submit_stream("lm", p, max_new_tokens=10)
                       for p in prompts]
            for s, ref in zip(streams, refs):
                assert _flat(s.result(timeout=300)) == ref
            rows = entry.batcher.replica_stats()
            assert [r["mesh"] for r in rows] == [2, 2]
            assert all(r["dead"] is None for r in rows)
            assert reg.describe()["lm"]["mesh"] == [2, 2]
        finally:
            reg.close_all()

    def test_member_loss_kills_lane_typed_sibling_survives(
            self, tmp_path):
        md = _lm(tmp_path)
        reg = ModelRegistry()
        try:
            reg.load_model("lm", md, devices=["cpu:0+cpu:1",
                                              "cpu:2+cpu:3"],
                           decode_slots=2)
            pred = GenerativePredictor(md)
            prompt = [3, 5, 7]
            ref, _ = greedy_decode(pred, prompt, 10)
            set_member_poison("cpu:3")
            # drive until each lane has eaten a stream: the lanes pull
            # from one queue and whichever wakes first takes a request,
            # so four streams all went to one lane in a run of ten
            outcomes = []
            kinds = []
            while len(kinds) < 32 and not {"ok", "dead"} <= set(kinds):
                s = reg.submit_stream("lm", prompt, max_new_tokens=10)
                try:
                    outcomes.append(("ok", _flat(s.result(timeout=300))))
                except MeshMemberLost as e:
                    outcomes.append(("dead", str(e)))
                kinds = [k for k, _ in outcomes]
            assert "dead" in kinds, \
                "poisoned lane never took a stream: %s" % (outcomes,)
            assert "ok" in kinds, \
                "member loss killed the SIBLING lane too"
            for k, v in outcomes:
                if k == "ok":
                    assert v == ref
                else:
                    assert "cpu:3" in v  # typed, naming the member
            entry = reg._models["lm"]["versions"][1]
            rows = entry.batcher.replica_stats()
            dead = [r for r in rows if r["dead"]]
            assert len(dead) == 1 and "cpu:3" in dead[0]["device"]
            # post-loss traffic rides the survivor, still bit-exact
            out = _flat(reg.submit_stream(
                "lm", prompt, max_new_tokens=10).result(timeout=300))
            assert out == ref
        finally:
            reg.close_all()

    def test_resize_grows_mesh_lanes(self, tmp_path):
        md = _lm(tmp_path)
        reg = ModelRegistry()
        try:
            reg.load_model("lm", md, devices=["cpu:0+cpu:1",
                                              "cpu:2+cpu:3"],
                           decode_slots=2)
            reg.resize_model("lm", 3)
            entry = reg._models["lm"]["versions"][2]
            assert entry.mesh_sizes() == [2, 2, 2]
            assert entry.device_labels()[2] == "cpu:4+cpu:5"
            pred = GenerativePredictor(md)
            prompt = [5, 9, 2]
            ref, _ = greedy_decode(pred, prompt, 8)
            out = _flat(reg.submit_stream(
                "lm", prompt, max_new_tokens=8).result(timeout=300))
            assert out == ref
        finally:
            reg.close_all()


class TestMeshHotSwap:
    def test_swap_mesh_lane_set_under_hammer(self, tmp_path):
        """Hammer one model from 4 threads while hot-swapping a
        2x2-chip mesh lane set for another: every request resolves
        exactly once, every answer is exactly v1's or v2's output, and
        post-swap traffic serves v2 from mesh lanes."""
        md1 = _export_fc(tmp_path, seed=31, name="v1")
        md2 = _export_fc(tmp_path, seed=32, name="v2")
        x = np.random.RandomState(6).randn(2, 4).astype(np.float32)
        from paddle_tpu.inference import AnalysisConfig, Predictor
        cfg = AnalysisConfig(model_dir=md1)
        cfg.batch_size_buckets = (2, 4)
        r1 = Predictor(cfg).run({"x": x})[0]
        cfg2 = AnalysisConfig(model_dir=md2)
        cfg2.batch_size_buckets = (2, 4)
        r2 = Predictor(cfg2).run({"x": x})[0]
        placement = "cpu:0+cpu:1,cpu:2+cpu:3"
        reg = ModelRegistry(deadline_ms=2)
        reg.load_model("m", md1, buckets=(2, 4), replicas=placement)
        stop = threading.Event()
        wrong, errors, answered = [], [], [0]
        lock = threading.Lock()

        def hammer():
            while not stop.is_set():
                try:
                    out = reg.infer("m", {"x": x}, timeout=60)[0]
                except Exception as e:
                    errors.append(e)
                    return
                with lock:
                    answered[0] += 1
                    if not (np.array_equal(out, r1)
                            or np.array_equal(out, r2)):
                        wrong.append(out)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.25)
            # the swap builds + warms the WHOLE mesh set before the flip
            reg.load_model("m", md2, buckets=(2, 4), replicas=placement)
            time.sleep(0.25)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
        assert not errors, errors[:3]
        assert not wrong, "%d responses matched neither version" \
            % len(wrong)
        assert answered[0] > 10
        out_after = reg.infer("m", {"x": x}, timeout=60)[0]
        assert np.array_equal(out_after, r2)
        entry = reg._models["m"]["versions"][2]
        assert entry.mesh_sizes() == [2, 2]
        reg.close_all()
