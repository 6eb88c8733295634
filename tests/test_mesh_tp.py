"""Tensor-parallel mesh compute tests (SERVING.md "Tensor-parallel
compute").

With FLAGS.mesh_tp, a mesh replica stops gathering its sharded params
per step and runs ONE partitioned executable over the member mesh:
fc/mul column->row-parallel pairs closed by a single psum, attention
head-parallel on each member's resident KV shard, long-prompt prefill
sequence-parallel (parallel/ulysses.py).  Pins:

* head-parallel decode attention is EXACT: per-member
  `decode_attention_head_slice` on the resident head block equals the
  full-table kernel, per mesh size 1/2/4, fp32 and int8 (the [2, H]
  scale table windows per member, dequant stays local);
* decode streams are top-1 identical to the single-device oracle AND
  to the gather-mesh lane, across fp32, int8 KV, sequence-parallel
  prefill, fused multi-step, and the speculative twin;
* the documented tolerance point — the psum closing a column->row
  pair reorders one reduction — stays within the pinned bound and
  never moves top-1 on the pinned logits;
* per-member roofline: per_device_step_bytes is total/m only under
  tp (the gather lane still moves every byte through each member);
* the partitioned executable rides the persistent compile cache —
  warm process-equivalent reload is hits:N misses:0, and the mesh
  shape is a fingerprint field (a (2,)-mesh blob never serves a
  (4,) mesh);
* unsupported geometry falls back to the gather lane with a
  RuntimeWarning (never silently wrong), and member loss under TP
  still raises the TYPED MeshMemberLost naming the member.

Everything CPU-safe under JAX_PLATFORMS=cpu + the conftest's 8 forced
host devices.
"""

import numpy as np
import pytest

from paddle_tpu import compile_cache as cc
from paddle_tpu.analysis.resources import analyze_artifact
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.inference.decode import (GenerativePredictor,
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.ops.pallas_kernels import (decode_attention,
                                           decode_attention_head_slice)
from paddle_tpu.parallel.mesh import (MeshGroup, MeshMemberLost,
                                      set_member_poison, tp_supported)

import jax

PROMPT = [3, 5, 7, 9, 11]
BUDGET = 12

_FLAGS = ["mesh_tp", "mesh_tp_prefill_seq", "compile_cache_dir"]


@pytest.fixture(autouse=True)
def _tp_flags():
    saved = get_flags(_FLAGS)
    set_flags({"mesh_tp": True})
    yield
    set_flags(saved)
    set_member_poison(None)


def _lm(tmp_path, name="lm", seed=7, **kw):
    """TP-able geometry: every partitioned dim divides by 4, so the
    same artifact exercises m=2 and m=4."""
    kw.setdefault("vocab_size", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("eos_id", -1)
    return build_tiny_decode_model(str(tmp_path / name), seed=seed, **kw)


def _stream(md, device, budget=BUDGET, **kw):
    pred = GenerativePredictor(md, device=device, **kw)
    out, _ = greedy_decode(pred, PROMPT, budget, n_slots=4, slot=1)
    return out, pred


# ---------------------------------------------------------------------------
# head-parallel decode attention: exact per member, per mesh size
# ---------------------------------------------------------------------------

class TestHeadSliceParity:
    N, S, H, D = 3, 16, 4, 8

    def _case(self, rng, dtype=np.float32):
        """q [N, H, D] and caches as a slot table holds them, [N, S,
        H * D]: a member's heads are contiguous lanes of the row."""
        q = rng.standard_normal((self.N, self.H, self.D)).astype(
            np.float32)
        k = rng.standard_normal((self.N, self.S, self.H * self.D))
        v = rng.standard_normal((self.N, self.S, self.H * self.D))
        if dtype == np.int8:
            k = np.clip(k * 40, -127, 127).astype(np.int8)
            v = np.clip(v * 40, -127, 127).astype(np.int8)
        else:
            k, v = k.astype(dtype), v.astype(dtype)
        lengths = np.array([16, 9, 1], np.int32)
        return q, k, v, lengths

    @staticmethod
    def _pin(got, full):
        """Heads are independent, so the per-head math is identical —
        but XLA schedules the narrower [N, Hl, ...] contraction of a
        head block differently (at m = 2 already: 0.23925762 against
        0.23925759), so what is pinned is the ULP-level bound;
        bit-exactness across compiled shapes is no contract (PERF.md,
        PR 21)."""
        np.testing.assert_allclose(got.astype(np.float64),
                                   full.astype(np.float64),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("m", [1, 2, 4])
    def test_matches_full_kernel(self, m):
        q, k, v, lengths = self._case(np.random.default_rng(3))
        full = np.asarray(decode_attention(q, k, v, lengths))
        hl = self.H // m
        parts = []
        for i in range(m):
            sl = slice(i * hl, (i + 1) * hl)
            lanes = slice(i * hl * self.D, (i + 1) * hl * self.D)
            parts.append(np.asarray(decode_attention_head_slice(
                q[:, sl], k[..., lanes], v[..., lanes], lengths,
                head_offset=i * hl, n_local_heads=hl)))
        self._pin(np.concatenate(parts, axis=1), full)

    @pytest.mark.parametrize("m", [2, 4])
    def test_int8_scale_window_per_member(self, m):
        q, k, v, lengths = self._case(np.random.default_rng(5),
                                      dtype=np.int8)
        scales = np.linspace(0.01, 0.08, 2 * self.H).reshape(
            2, self.H).astype(np.float32)
        full = np.asarray(decode_attention(q, k, v, lengths,
                                           kv_scales=scales))
        hl = self.H // m
        parts = []
        for i in range(m):
            sl = slice(i * hl, (i + 1) * hl)
            # each member receives the FULL [2, H] table and slices
            # its own window at the traced head offset
            lanes = slice(i * hl * self.D, (i + 1) * hl * self.D)
            parts.append(np.asarray(decode_attention_head_slice(
                q[:, sl], k[..., lanes], v[..., lanes], lengths,
                head_offset=i * hl, n_local_heads=hl,
                kv_scales=scales)))
        self._pin(np.concatenate(parts, axis=1), full)


# ---------------------------------------------------------------------------
# partitioned decode vs the single-device oracle and the gather lane
# ---------------------------------------------------------------------------

class TestTPDecodeParity:
    def test_tp_stream_top1_identical(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        ref, _ = _stream(md, devs[0])
        set_flags({"mesh_tp": False})
        gather, pg = _stream(md, MeshGroup(devs[:2]))
        assert not pg.tp_active
        assert gather == ref
        set_flags({"mesh_tp": True})
        for m in (2, 4):
            out, pm = _stream(md, MeshGroup(devs[:m]))
            assert pm.tp_active and pm.tp_size == m
            assert out == ref, \
                "TP m=%d diverged from single-device top-1" % m

    def test_int8_kv_tp_parity(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        ref, _ = _stream(md, devs[0], kv_cache_dtype="int8")
        out, pm = _stream(md, MeshGroup(devs[:2]),
                          kv_cache_dtype="int8")
        assert pm.tp_active
        assert out == ref

    def test_seqpar_prefill_bit_exact(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        ref, _ = _stream(md, devs[0])
        # drop the activation threshold so the bucket-8 prefill takes
        # the sequence-parallel (ulysses) path
        set_flags({"mesh_tp_prefill_seq": 8})
        out, pm = _stream(md, MeshGroup(devs[:2]))
        assert pm.tp_active and pm._tp_prefill_seq == 8
        assert out == ref

    def test_fused_multistep_tp(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        ref, _ = _stream(md, devs[0])
        pm = GenerativePredictor(md, device=MeshGroup(devs[:2]))
        assert pm.tp_active
        # the partitioned step executable, windows of 4 trips and a tail
        sess = pm.new_session(4)
        out = [sess.prefill(1, PROMPT)]
        while len(out) < BUDGET:
            toks, counts, trips = sess.decode_fused(
                4, max_trips=min(4, BUDGET - len(out)))
            assert trips == counts[1] >= 1
            out.extend(int(t) for t in toks[1, :counts[1]])
        assert out == ref

    def test_spec_twin_accepts_everything_under_tp(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        ref, _ = _stream(md, devs[0])
        group = MeshGroup(devs[:2])
        target = GenerativePredictor(md, device=group)
        draft = GenerativePredictor(md, device=group,
                                    kv_cache_dtype="int8")
        assert target.tp_active and draft.tp_active
        spec = SpeculativeDecodeSession(target, draft, 4, 2)
        got = [spec.prefill(1, PROMPT)]
        while len(got) < BUDGET and got[-1] != target.eos_id:
            toks, counts = spec.step()
            got.extend(int(t) for t in toks[1][:counts[1]])
        assert got[:BUDGET] == ref
        assert spec.proposed > 0 and spec.accepted == spec.proposed

    def test_unsupported_geometry_falls_back_with_warning(self,
                                                          tmp_path):
        # n_heads=2 does not divide by 4 -> tp_supported is False and
        # the predictor must drop to the gather lane, loudly
        md = _lm(tmp_path, name="small", n_heads=2, d_model=16,
                 vocab_size=32)
        devs = jax.devices()
        assert not tp_supported(4, 2, 16, 32)
        with pytest.warns(RuntimeWarning, match="mesh_tp"):
            pred = GenerativePredictor(md, device=MeshGroup(devs[:4]))
        assert not pred.tp_active
        ref, _ = _stream(md, devs[0])
        out, _ = greedy_decode(pred, PROMPT, BUDGET, n_slots=4, slot=1)
        assert out == ref


# ---------------------------------------------------------------------------
# the tolerance point: one psum closes each column->row pair
# ---------------------------------------------------------------------------

class TestTolerancePin:
    def test_psum_reorder_stays_in_bound_and_top1_stable(self):
        """The ONLY inexact point of the TP lowering: the row-parallel
        matmul contracts [in/m] per member and psum adds m partials,
        reordering one fp32 reduction.  Pin the documented bound
        (SERVING.md "Tensor-parallel compute": rtol 1e-5 / atol 1e-6
        on fp32 activations) and that top-1 never moves on a
        logits-shaped output."""
        rng = np.random.default_rng(11)
        x = rng.standard_normal((4, 32)).astype(np.float32)
        w1 = rng.standard_normal((32, 64)).astype(np.float32)  # column
        w2 = rng.standard_normal((64, 64)).astype(np.float32)  # row
        ref = np.maximum(x @ w1, 0.0) @ w2
        for m in (2, 4):
            cols = np.split(w1, m, axis=1)   # [in, out/m] per member
            rows = np.split(w2, m, axis=0)   # [in/m, out] per member
            partial = [np.maximum(x @ cols[i], 0.0) @ rows[i]
                       for i in range(m)]
            got = np.sum(np.stack(partial), axis=0)  # the psum
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
            assert np.array_equal(got.argmax(-1), ref.argmax(-1)), \
                "psum reorder moved top-1 at m=%d" % m


# ---------------------------------------------------------------------------
# per-member roofline
# ---------------------------------------------------------------------------

class TestPerMemberBytes:
    def test_per_device_step_bytes_scales_only_under_tp(self,
                                                        tmp_path):
        md = _lm(tmp_path)
        base = analyze_artifact(md, decode_slots=8)
        total = base.per_device_step_bytes()
        assert total == base.total_bytes
        for m, bound in ((2, 0.6), (4, 0.35)):
            tp = analyze_artifact(md, decode_slots=8, mesh_size=m,
                                  tp=True)
            gather = analyze_artifact(md, decode_slots=8, mesh_size=m,
                                      tp=False)
            # the gather lane still moves EVERY param byte through
            # every member each step; only tp divides the roofline
            assert gather.per_device_step_bytes() == total
            ratio = tp.per_device_step_bytes() / float(total)
            assert ratio <= bound, \
                "per-member bytes at m=%d: %.3f > %.2f" % (m, ratio,
                                                           bound)
            assert tp.per_device_step_bytes() == -(-total // m)
        assert "per member" in analyze_artifact(
            md, decode_slots=8, mesh_size=2, tp=True).render()


# ---------------------------------------------------------------------------
# compile cache: warm reload of the partitioned executable
# ---------------------------------------------------------------------------

class TestTPCompileCache:
    def test_warm_reload_and_mesh_shape_fingerprint(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        set_flags({"compile_cache_dir": str(tmp_path / "cache")})

        before = cc.stats()
        ref, _ = _stream(md, MeshGroup(devs[:2]), budget=6)
        cold = cc.stats_delta(before)
        assert cold["puts"] >= 2 and cold["misses"] >= 2, cold

        # a FRESH predictor instance is the in-process stand-in for a
        # process restart: its export memo starts empty, so every
        # phase must come back from the persisted blobs
        before = cc.stats()
        warm, _ = _stream(md, MeshGroup(devs[:2]), budget=6)
        d = cc.stats_delta(before)
        assert d["hits"] >= 2 and d["misses"] == 0, d
        assert warm == ref

        # mesh shape is a fingerprint field: the (2,)-mesh blobs must
        # NOT serve a (4,) mesh
        before = cc.stats()
        out4, _ = _stream(md, MeshGroup(devs[:4]), budget=6)
        d4 = cc.stats_delta(before)
        assert d4["hits"] == 0 and d4["misses"] >= 2, d4
        assert out4 == ref


# ---------------------------------------------------------------------------
# member loss under TP stays typed
# ---------------------------------------------------------------------------

class TestTPMemberLoss:
    def test_member_loss_typed_mid_decode(self, tmp_path):
        md = _lm(tmp_path)
        devs = jax.devices()
        pred = GenerativePredictor(md, device=MeshGroup(devs[:2]))
        assert pred.tp_active
        session = pred.new_session(4)
        session.prefill(1, PROMPT)
        session.decode()
        set_member_poison("cpu:1")
        with pytest.raises(MeshMemberLost, match="cpu:1"):
            session.decode()
