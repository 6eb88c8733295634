"""A decode artifact whose layers are LATENT attention (openPangu-Ultra-MoE's:
one row of kv_lora_rank + qk_rope_head_dim values a position, an expanded
prefill and an absorbed decode) with sandwich norms, a leading dense SwiGLU
layer, and a shared expert beside a chip's SHARE of sigmoid-routed experts,
its matmul weights bfloat16 at rest, through the serving path, against the
plain reference `benchmark/reference/openpangu_ultra_moe_718b.py`, at a tiny
size on the CPU.

A slot of such a session holds ONE kind of state, held once: its rows of the
latent table.  What these tests pin: prefill (which expands the rows) and
the step (which attends over them as they are) are one function; the kernel
is its reference at ragged lengths; rows are written where the slot runs,
zeroed by `free`, never leak into a neighbour; the held experts' part plus
the shared expert once add up to the uncut layer over the shares; bf16
storage computes what fp32 storage of the same numbers computes; what cannot
take the stack is refused by a typed error that names the meta key; and an
artifact that names none of the new keys is the program it was.

TOL_LOGITS as in test_olmoe_decode.py: both sides compute in float32 here,
in another order of operations; measured differences are a few 1e-6.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import openpangu_ultra_moe_718b as reference  # noqa: E402,E501
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model,
                                         save_decode_model)
from paddle_tpu.obs import tracing as obs_tracing  # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402
from paddle_tpu.serving import (InferenceServer,  # noqa: E402
                                ServingClient)

TOL_LOGITS = 1e-4
E, HELD = 16, (4, 4)
PANGU_BLOCK = {"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
               "rope_theta": 25.6e6, "layer_types": ["mla"] * 3,
               "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
               "qk_rope_head_dim": 4, "v_head_dim": 8, "sandwich_norm": True,
               "n_dense_layers": 1, "dense_width": 96, "ffn": "moe_swiglu",
               "n_experts": E, "experts_per_token": 4, "expert_width": 32,
               "norm_topk_prob": True, "router": "sigmoid",
               "routed_scaling": 2.5, "n_shared_experts": 1,
               "experts_held": list(HELD), "weight_dtype": "bfloat16"}
TINY = dict(vocab_size=97, d_model=60, n_heads=4, n_layers=3, max_seq_len=64,
            eos_id=0, prefill_buckets=[16, 32, 64])
META = dict(PANGU_BLOCK, **TINY)
ROW = 16 + 4
SEED = 2 ** 31 + 35


def _drawn(meta, seed=SEED):
    """The artifact's state as the benchmark's driver makes it: each tensor
    from (seed, name), in the dtype it has at rest."""
    return {n: np.asarray(reference.draw_tensor(n, s, seed))
            for n, s in reference.tensor_shapes(meta).items()}


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("pangu") / "lm")
    return save_decode_model(d, _drawn(META), META)


@pytest.fixture(scope="module")
def opened(artifact):
    pred = GenerativePredictor(artifact)
    return pred, {n: jnp.asarray(v) for n, v in pred._state_host.items()}


_REF = {}


def _ref(state, seq, meta):
    """(logits, router gaps) of the reference for `seq`, through ONE jitted
    program: the sequence padded to max_seq_len (causal)."""
    fn = _REF.get("fn")
    if fn is None:
        model = {k: meta[k] for k in sorted(meta)}
        fn = _REF["fn"] = jax.jit(
            lambda st, t: reference.forward(st, t, model))
    tokens = np.zeros(TINY["max_seq_len"], np.int32)
    tokens[:len(seq)] = seq
    logits, gaps = fn(state, jnp.asarray(tokens))
    return np.asarray(logits)[:len(seq)], np.asarray(gaps)[:len(seq)]


def _prompt(n, seed=3):
    return np.random.default_rng([seed, n]).integers(
        1, TINY["vocab_size"], n, dtype=np.int32)


def test_the_meta_describes_the_stack(opened):
    pred, _ = opened
    assert pred.layer_kinds == [("mla", "dense_swiglu"),
                                ("mla", "moe_swiglu"), ("mla", "moe_swiglu")]
    assert (pred.latent, pred.conv_layers, pred.routed_layers,
            pred._n_tables) == (True, 0, 2, 1)
    # ONE table, one row of rank + rope values a position, held once
    assert pred.table_shape(3) == (3, 3, 64, ROW)
    assert pred.conv_state_shape(3) is None
    assert pred.kv_cache_bytes(3) == 3 * 3 * 64 * ROW * 4
    sess = pred.new_session(3)
    assert sess._vc is None and sess._cs is None
    assert sess.cache_bytes() == pred.kv_cache_bytes(3)
    assert len(pred._step_specs(3)) == 6
    # bf16 at rest: every matrix but the routers', and `param_bytes` says so
    host = pred._state_host
    two = {n for n, v in host.items() if v.dtype.itemsize == 2}
    assert two == {n for n, v in host.items()
                   if v.ndim >= 2 and not n.endswith("_router")}
    assert pred.param_bytes() == sum(
        (2 if n in two else 4) * int(np.prod(s))
        for n, s in dec.decode_state_shapes(pred.meta).items())
    # the held experts' stacks hold the held experts alone
    assert host["l1_w_gate"].shape == (HELD[1], 60, 32)
    assert host["l1_router"].shape == (60, E)


def test_the_static_report_prices_the_same_slot_state(opened, artifact):
    from paddle_tpu.analysis.resources import _decode_report
    pred, _ = opened
    rep = _decode_report(artifact, pred.meta, 3, None, "pangu")
    assert rep.kv_cache_bytes == pred.kv_cache_bytes(3)
    assert rep.param_bytes == pred.param_bytes()


@pytest.mark.parametrize("path", ["session", "window"])
@pytest.mark.parametrize("n", [1, 2, 15, 20])
def test_prefill_and_32_steps_match_the_reference_by_logits(opened, path, n):
    """A prefill (the EXPANDED path) and 32 decode steps through the latent
    table (the ABSORBED path, the kernel in interpret mode) against the
    reference's expanded forward: by logits through
    `DecodeSession.decode_logits` and token for token through the fused
    window.  Prompts of 1, 2 and bucket - 1 tokens and one in the next
    bucket."""
    pred, state = opened
    prompt = _prompt(n)
    sess = pred.new_session(3)
    seq = list(prompt) + [sess.prefill(1, prompt)]
    got = []
    if path == "session":
        for _ in range(32):
            toks, logits = sess.decode_logits()
            got.append(logits[1])
            seq.append(int(toks[1]))
    else:
        while len(seq) < n + 33:
            toks, counts, trips = sess.decode_fused(dec.STEP_WINDOW)
            assert counts[1] == trips and counts[0] == counts[2] == 0
            seq += [int(t) for t in toks[1, :counts[1]]]
        seq = seq[:n + 33]
    want, _ = _ref(state, seq, pred.meta)
    for t in range(33):
        row = want[n - 1 + t]
        assert row.max() - row[seq[n + t]] <= 2 * TOL_LOGITS, t
    for t, logits in enumerate(got):
        assert np.max(np.abs(logits - want[n + t])) <= TOL_LOGITS, t


def test_absorbed_attention_is_expanded_attention_on_the_same_rows(opened):
    """One set of weights, two paths: every position of a prompt attended
    the expanded way (per-head keys and values from the rows) equals the
    absorbed way over a table that holds those rows (the up-projection
    folded into the query and the output)."""
    pred, _ = opened
    rng = np.random.default_rng(7)
    T, H, dn, dr, dv, rank = 12, 4, 8, 4, 8, 16
    qn = jnp.asarray(rng.standard_normal((1, T, H, dn)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((1, T, H, dr)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((1, T, ROW)), jnp.float32)
    wkv_b = jnp.asarray(rng.standard_normal((rank, H, dn + dv)) / 4.0,
                        jnp.float32)
    want = np.asarray(pred._mla_expanded(qn, qr, rows, wkv_b))[0]
    table = jnp.zeros((2, T, 64, ROW)).at[1, :, :T].set(
        jnp.broadcast_to(rows, (T, T, ROW)))
    # slot t holds the prompt's rows and attends under its first t + 1
    got = pred._mla_absorbed(qn[0], qr[0], table, jnp.arange(T) + 1, 1,
                             wkv_b)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


RAGGED = [(1, 17, 64, 33), (16, 32, 48, 64), (0, 5, 63, 15)]


@pytest.mark.parametrize("lengths", RAGGED, ids=["ragged", "edges", "zero"])
@pytest.mark.parametrize("block", [8, 16, 64])
def test_latent_kernel_matches_its_reference(lengths, block):
    """`latent_decode_attention` in interpret mode, a single layer and the
    stacked table, at ragged lengths on and around the block edges: 8 heads
    on ONE row a position whose first 16 lanes are the values."""
    rng = np.random.default_rng(5)
    N, S, H, R, V = 4, 64, 8, 24, 16
    q = jnp.asarray(rng.standard_normal((N, H, R)), jnp.float32)
    table = jnp.asarray(rng.standard_normal((2, N, S, R)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    want = np.asarray(pk.latent_decode_attention_reference(
        q, table[1], lens, V, 0.2))
    # the oracle, spelled out for one head of one slot
    n, a, ln = 1, 3, lengths[1]
    s = np.asarray(table[1, n, :ln] @ q[n, a]) * 0.2
    p = np.exp(s - s.max())
    np.testing.assert_allclose(
        want[n, a], (p / p.sum()) @ np.asarray(table[1, n, :ln, :V]),
        atol=1e-5)
    live = np.asarray(lengths) > 0          # length 0: well-defined garbage
    for got in (pk.latent_decode_attention(q, table[1], lens, V, 0.2,
                                           block_kv=block, interpret=True),
                pk.latent_decode_attention(q, table, lens, V, 0.2,
                                           block_kv=block, interpret=True,
                                           layer=1)):
        assert got.shape == (N, H, V) and np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got)[live], want[live],
                                   atol=2e-5)


def test_latent_kernel_refuses_a_table_it_cannot_read():
    q, table = jnp.zeros((2, 4, 24)), jnp.zeros((3, 2, 16, 24))
    with pytest.raises(ValueError, match="stacked table"):
        pk.latent_decode_attention(q, table, jnp.ones(2, jnp.int32), 16, 1.0)
    with pytest.raises(ValueError, match="24 lanes"):
        pk.latent_decode_attention(q, table[0, :, :, :20],
                                   jnp.ones(2, jnp.int32), 16, 1.0)


def test_slots_joining_and_leaving_a_window_keep_every_stream(opened):
    """Per-slot math is independent: streams that join and leave a session
    running full windows are, token for token, those of one-trip dispatches
    in a session of their own."""
    pred, _ = opened
    prompts = [_prompt(9), _prompt(13, seed=5), _prompt(3, seed=8)]
    alone = []
    for p in prompts:
        one = pred.new_session(1)
        seq = [one.prefill(0, p)]
        for _ in range(31):
            seq.append(int(one.decode()[0]))
        alone.append(seq)
    sess = pred.new_session(3)
    got = [[sess.prefill(0, prompts[0])], [], []]

    def window(live):
        toks, counts, trips = sess.decode_fused(dec.STEP_WINDOW)
        for s in live:
            assert counts[s] == trips
            got[s] += [int(t) for t in toks[s, :counts[s]]]
    window([0])                                    # 0 alone
    got[1].append(sess.prefill(1, prompts[1]))     # 1 joins
    window([0, 1])
    sess.free(0)                                   # 0 leaves
    got[2].append(sess.prefill(2, prompts[2]))     # 2 joins, 0's row empty
    window([1, 2])
    got[0].append(sess.prefill(0, prompts[0]))     # the freed slot again
    window([0, 1, 2])
    assert got[0][:17] == alone[0][:17] and got[0][17:] == alone[0][:9]
    assert got[1] == alone[1][:len(got[1])] and len(got[1]) == 25
    assert got[2] == alone[2][:len(got[2])] and len(got[2]) == 17


def test_a_slot_that_stops_mid_window_keeps_its_latent_rows(opened):
    """Its latent rows after the window are those of its own stop, bit for
    bit (no row landed in the trips it sat out); the neighbour's stream, the
    routing facts and the blocks counted are those of the trips each slot
    ran (`tests/test_decode_window.py`)."""
    from tests.test_decode_window import a_slot_that_stops_sits_out_the_window
    a_slot_that_stops_sits_out_the_window(
        opened[0], [_prompt(9), _prompt(13, seed=5)], 3)


def test_a_freed_slot_is_zero_and_its_neighbour_unmoved(opened):
    pred, _ = opened
    sess = pred.new_session(3)
    quiet = pred.new_session(3)          # the neighbour, alone
    p1, p2 = _prompt(9), _prompt(13, seed=5)
    sess.prefill(0, p1)
    sess.prefill(2, p2)
    quiet.prefill(2, p2)
    for _ in range(3):
        sess.decode_fused(4)
        quiet.decode_fused(4)
    assert not sess.slot_is_zero(0) and sess.slot_is_zero(1)
    # the rows past a slot's length are zeros, those under it are not
    rows = np.asarray(sess._kc[:, 0])
    assert rows[:, :9 + 12].any(axis=-1).all() and not rows[:, 21:].any()
    sess.free(0)
    assert sess.slot_is_zero(0) and not np.asarray(sess._kc[:, 0]).any()
    _, l_sess = sess.decode_logits()
    _, l_quiet = quiet.decode_logits()
    assert sess.slot_is_zero(0) and sess.slot_is_zero(1)
    assert (l_sess[2] == l_quiet[2]).all()
    fresh = pred.new_session(1)
    assert sess.prefill(0, p1) == fresh.prefill(0, p1)
    a, _ = sess.decode_logits()
    b, _ = fresh.decode_logits()
    assert a[0] == b[0]


def test_rollback_is_rows_under_a_length(opened):
    """The state is rows under a length, so a rollback works: the slot is
    bit for bit one that never advanced."""
    pred, _ = opened
    sess, twin = pred.new_session(2), pred.new_session(2)
    p = _prompt(11)
    first = sess.prefill(1, p)
    twin.prefill(1, p)
    sess.decode_fused(5)
    sess.rollback(1, 5, last_token=first)
    assert (np.asarray(sess._kc) == np.asarray(twin._kc)).all()
    assert int(sess.lengths[1]) == 11
    a, _ = sess.decode_logits()
    b, _ = twin.decode_logits()
    assert a[1] == b[1]


def test_padded_rows_serve_the_same_streams(artifact, opened, monkeypatch):
    """What one TPU device holds: rows padded to the tile's 128 lanes.  The
    pad is exact zeros and stays so, the tokens are the plain table's."""
    plain, _ = opened
    monkeypatch.setattr(dec.slot_state, "_rows_are_tiles",
                        lambda device: True)
    pred = GenerativePredictor(artifact)
    assert pred.table_shape(2) == (3, 2, 64, 128)
    assert pred.kv_cache_bytes(2) == 3 * 2 * 64 * 128 * 4
    sess = pred.new_session(2)
    p = _prompt(10)
    seq = [sess.prefill(1, p)]
    for _ in range(3):
        toks, counts, _ = sess.decode_fused(dec.STEP_WINDOW)
        seq += [int(t) for t in toks[1, :counts[1]]]
    want, _ = dec.greedy_decode(plain, p, len(seq))
    assert seq == want
    rows = np.asarray(sess._kc)
    assert rows[:, 1, :10, :ROW].any() and not rows[..., ROW:].any()


def test_bf16_storage_is_fp32_storage_of_the_same_numbers(tmp_path, opened):
    """The matmul weights are bfloat16 NUMBERS: kept in bfloat16 or widened
    to float32 at rest, the program computes the same logits, bit for bit
    (off the TPU by widening; on it the default precision rounds a float32
    copy to the same operands: `decode._contract`)."""
    half, _ = opened
    wide_meta = dict(META, weight_dtype="float32")
    wide = GenerativePredictor(save_decode_model(
        str(tmp_path / "wide"),
        {n: np.asarray(v, np.float32) for n, v in _drawn(META).items()},
        wide_meta))
    assert wide.param_bytes() > 1.9 * half.param_bytes() * 0.5
    assert all(v.dtype == np.float32 for v in wide._state_host.values())
    a, b = half.new_session(2), wide.new_session(2)
    p = _prompt(14)
    assert a.prefill(0, p) == b.prefill(0, p)
    for _ in range(6):
        (_, la), (_, lb) = a.decode_logits(), b.decode_logits()
        assert (la[0] == lb[0]).all()
    assert (np.asarray(a._kc) == np.asarray(b._kc)).all()


def _layer_inputs(rng, T=9, D=60):
    return jnp.asarray(rng.standard_normal((T, D)), jnp.float32)


def test_the_shares_add_up_to_the_uncut_layer(opened):
    """The guide's share test.  A routed layer's 16 experts over 4 members
    of 4: every member routes over all 16 and computes its own experts'
    part; the 4 parts plus the shared expert ONCE are the uncut layer's
    result - in the reference (`ffn_parts`) and in the program (`moe_ffn`
    told which run it holds), member by member."""
    rng = np.random.default_rng(11)
    D, F = 60, 32
    g = _layer_inputs(rng)
    full = {"router": rng.standard_normal((D, E)) / 8.0,
            "w_gate": rng.standard_normal((E, D, F)) / 8.0,
            "w_up": rng.standard_normal((E, D, F)) / 8.0,
            "w_down": rng.standard_normal((E, F, D)) / 6.0,
            "shared_gate": rng.standard_normal((D, F)) / 8.0,
            "shared_up": rng.standard_normal((D, F)) / 8.0,
            "shared_down": rng.standard_normal((F, D)) / 6.0}
    full = {n: jnp.asarray(v, jnp.float32) for n, v in full.items()}
    model = dict(META, experts_held=[0, E])
    with jax.default_matmul_precision("highest"):
        whole, shared, _ = reference.ffn_parts(g, full, model)
        parts, mine = [], []
        for first in range(0, E, 4):
            w = dict(full, **{n: full[n][first:first + 4]
                              for n in ("w_gate", "w_up", "w_down")})
            part, again, _ = reference.ffn_parts(
                g, w, dict(model, experts_held=[first, 4]))
            assert (np.asarray(again) == np.asarray(shared)).all()
            parts.append(np.asarray(part))
            y, facts = dec.moe_ffn(
                g, w["router"], w["w_gate"], w["w_up"], w["w_down"], 4,
                norm_topk_prob=True, sigmoid=True, scaling=2.5,
                held=(first, 4))
            np.testing.assert_allclose(np.asarray(y), parts[-1], atol=2e-5)
            mine.append(np.asarray(y))
            # the facts count the experts HELD here that a token chose
            s = np.asarray(jax.nn.sigmoid(g @ full["router"]))
            chosen = np.argsort(-s, axis=1)[:, :4]
            here = chosen[(chosen >= first) & (chosen < first + 4)]
            assert int(facts[0]) == len(set(here.tolist()))
            assert int(facts[1]) == (np.bincount(here).max()
                                     if len(here) else 0)
    uncut = np.asarray(whole + shared)
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), uncut,
                               atol=2e-5)
    np.testing.assert_allclose(sum(mine) + np.asarray(shared), uncut,
                               atol=5e-5)
    # no member's part is the whole, and the parts differ
    assert all(np.abs(p - np.asarray(whole)).max() > 1e-2 for p in parts)


def test_a_pair_held_elsewhere_costs_no_row_of_the_grouped_matmul():
    """Pairs routed to an absent expert leave BEFORE the sort: the grouped
    matmuls' group sizes sum to the pairs that stay."""
    rng = np.random.default_rng(3)
    g = _layer_inputs(rng, T=6)
    router = jnp.asarray(rng.standard_normal((60, E)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) / 8.0, jnp.float32)
         for s in ((4, 60, 32), (4, 60, 32), (4, 32, 60))]
    jaxpr = jax.make_jaxpr(lambda h: dec.moe_ffn(
        h, router, *w, 4, sigmoid=True, held=(8, 4))[0])(g)
    sizes = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name.startswith("ragged_dot")]
    assert len(sizes) == 3 and all(
        e.invars[2].aval.shape == (4,) for e in sizes)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(g, router, precision="highest")))
    chosen = np.argsort(-s, axis=1)[:, :4]
    stay = int(((chosen >= 8) & (chosen < 12)).sum())
    assert 0 < stay < chosen.size
    # a token none of whose experts live here gets exactly nothing
    y, facts = dec.moe_ffn(g, router, *w, 4, sigmoid=True, held=(8, 4))
    none = ~((chosen >= 8) & (chosen < 12)).any(axis=1)
    assert not np.asarray(y)[none].any()
    assert int(facts[1]) <= stay


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["share", "crowded"])
def test_the_held_pairs_run_compact_and_stay_dropless(crowded):
    """At sizes where the pairs outnumber four times the member's share
    the grouped matmuls run over the head of the sorted pairs alone (one
    branch of a `cond`), and over every pair when the routing crowds more
    than that onto this member (the other): both are the reference's part,
    no pair dropped."""
    rng = np.random.default_rng(17)
    T, D, F, wide = 100, 60, 32, 32
    g = _layer_inputs(rng, T=T)
    router = rng.standard_normal((D, wide)) / 8.0
    if crowded:         # every token's largest score is expert 0's
        router[:, 0] = 0.0
        g = g.at[:, 0].set(40.0)
        router[0, 0] = 1.0
    w = {"router": router, "w_gate": rng.standard_normal((1, D, F)) / 8.0,
         "w_up": rng.standard_normal((1, D, F)) / 8.0,
         "w_down": rng.standard_normal((1, F, D)) / 6.0}
    w = {n: jnp.asarray(v, jnp.float32) for n, v in w.items()}
    w.update(shared_gate=jnp.zeros((D, F)), shared_up=jnp.zeros((D, F)),
             shared_down=jnp.zeros((F, D)))
    model = dict(META, n_experts=wide, experts_held=[0, 1])

    def mine(h):
        return dec.moe_ffn(h, w["router"], w["w_gate"], w["w_up"],
                           w["w_down"], 4, norm_topk_prob=True,
                           sigmoid=True, scaling=2.5, held=(0, 1))
    text = str(jax.make_jaxpr(mine)(g))
    assert "cond[" in text and text.count("ragged_dot_general[") == 6
    with jax.default_matmul_precision("highest"):
        want, _, _ = reference.ffn_parts(g, w, model)
        got, facts = mine(g)
    stay = int(facts[1])                # one held expert: its tokens
    assert (stay == T) if crowded else (0 < stay <= 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


REFUSALS = {
    "verify_fn": lambda pred, art: pred.verify_fn(2, 2),
    "fused_spec_fn": lambda pred, art: pred.fused_spec_fn(pred, 2, 2),
    "speculative_session": lambda pred, art: SpeculativeDecodeSession(
        pred, pred, 2, 2),
    "int8_kv": lambda pred, art: GenerativePredictor(
        art, kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_cannot_take_a_latent_table_is_refused_by_name(opened,
                                                            artifact, what):
    pred, _ = opened
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](pred, artifact)


def test_tp_lane_and_mesh_refuse_by_name(artifact):
    from paddle_tpu.flags import set_flags
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    group = MeshGroup(devs[:2])
    with pytest.raises(NotImplementedError, match="layer_types"):
        GenerativePredictor(artifact, device=group)
    set_flags({"mesh_tp": True})
    try:
        with pytest.raises(NotImplementedError):
            GenerativePredictor(artifact, device=group)
    finally:
        set_flags({"mesh_tp": False})


@pytest.mark.parametrize("key,value,match", [
    ("layer_types", ["mla", "attention", "mla"], "mixes mla"),
    ("layer_types", ["mla", "conv", "mla"], "mixes mla"),
    ("layer_types", ["mla", "mla"], "each of the 3"),
    ("kv_lora_rank", 0, "kv_lora_rank"),
    ("q_lora_rank", 0, "q_lora_rank"),
    ("v_head_dim", 0, "v_head_dim"),
    ("qk_rope_head_dim", 3, "qk_rope_head_dim"),
    ("position", "learned", "qk_rope_head_dim"),
    ("qk_norm", True, "qk_norm"),
    ("n_kv_heads", 2, "n_kv_heads"),
    ("router", "tanh", "router"),
    ("routed_scaling", 2.5, "routed_scaling"),
    ("experts_held", [14, 4], "experts_held"),
    ("experts_held", [4], "experts_held"),
    ("n_shared_experts", -1, "n_shared_experts"),
    ("weight_dtype", "float16", "weight_dtype"),
])
def test_a_stack_this_module_has_no_math_for_is_a_typed_error(key, value,
                                                              match):
    meta = dict(META, **{key: value})
    if key == "routed_scaling":
        meta["router"] = "softmax"
    with pytest.raises(ValueError, match=match):
        dec.block_of(meta)


@pytest.mark.parametrize("key,value", [("n_shared_experts", 1),
                                       ("experts_held", [0, 2]),
                                       ("sandwich_norm", True)])
def test_the_ffn_keys_go_with_routed_experts(key, value):
    with pytest.raises(ValueError, match=key):
        dec.block_of(dict(TINY, **{key: value}))


def _step_text(pred, n_slots=2):
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    return str(jax.make_jaxpr(pred._step_math())(
        spec, *pred._step_specs(n_slots)))


def _prefill_text(pred, bucket=16):
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    return str(jax.make_jaxpr(pred._prefill_math)(
        spec, jax.ShapeDtypeStruct((1, bucket), np.int32),
        jax.ShapeDtypeStruct((), np.int32)))


OLD_BLOCKS = {
    "gpt2": None,
    "olmoe": {"norm": "rmsnorm", "position": "rope", "qk_norm": True,
              "ffn": "moe_swiglu", "n_experts": 8, "experts_per_token": 2,
              "expert_width": 32},
    "lfm2": {"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
             "rope_theta": 1e6, "qk_norm": "head", "n_kv_heads": 2,
             "layer_types": ["conv", "attention", "conv"], "conv_kernel": 3,
             "n_dense_layers": 1, "dense_width": 96, "ffn": "moe_swiglu",
             "n_experts": 8, "experts_per_token": 2, "expert_width": 32,
             "norm_topk_prob": True, "router": "sigmoid_bias",
             "head": "tied"},
}
NEW_KEYS = {"q_lora_rank": 0, "kv_lora_rank": 0, "qk_nope_head_dim": 0,
            "qk_rope_head_dim": 0, "v_head_dim": 0, "sandwich_norm": False,
            "routed_scaling": 1.0, "n_shared_experts": 0, "experts_held": [],
            "weight_dtype": "float32"}


@pytest.mark.parametrize("phase", ["step", "prefill"])
@pytest.mark.parametrize("name", sorted(OLD_BLOCKS))
def test_an_accepted_configurations_phase_is_unchanged_by_the_new_keys(
        tmp_path, name, phase):
    """An artifact that names none of the new keys is the block it was: the
    phase's jaxpr is that of the same artifact with every new key SPELLED at
    its default, its slot state is the tables it had, its weights float32.
    (Against the PARENT's tree the same phases' texts are compared by
    tools/decode_hlo_dump.py, two trees and `diff`: CHANGES.md, PR 35.)"""
    block = OLD_BLOCKS[name]
    kw = dict(vocab_size=97, d_model=64, n_heads=4, n_layers=3,
              max_seq_len=64, eos_id=0, seed=11, prefill_buckets=[16, 32])
    old = GenerativePredictor(build_tiny_decode_model(
        str(tmp_path / "old"), block=block, **kw))
    assert all(old.block[k] == dict(dec.BLOCK_DEFAULTS)[k] for k in NEW_KEYS)
    spelled = GenerativePredictor(build_tiny_decode_model(
        str(tmp_path / "new"), block=dict(block or {}, **NEW_KEYS), **kw))
    text = _step_text if phase == "step" else _prefill_text
    assert text(old) == text(spelled)
    assert not old.latent and old._n_tables == (3 if name == "lfm2" else 2)
    assert old.new_session(2)._vc is not None
    assert all(v.dtype == np.float32 for v in old._state_host.values())


def test_fetch_spans_say_what_the_stack_holds(opened):
    pred, _ = opened
    sess = pred.new_session(2)
    was = obs_tracing.enabled()
    obs_tracing.set_enabled(True)
    try:
        obs_tracing.clear()
        sess.prefill(0, _prompt(6))
        sess.decode_fused(3)
        fetches = [s for s in obs_tracing.recent_spans()
                   if s["name"] == "decode/fetch"]
    finally:
        obs_tracing.set_enabled(was)
    assert [s["attrs"]["phase"] for s in fetches] == ["prefill", "step"]
    for s in fetches:
        a = s["attrs"]
        assert a["mla_layers"] == 3 and a["moe_experts_held"] == HELD[1]
        assert a["latent_cache_bytes"] == sess.cache_bytes() \
            == 3 * 2 * 64 * ROW * 4
        assert 0 <= a["moe_experts_touched"]
    # the routing facts are the two ROUTED layers', of the held experts
    assert sess.last_routing.shape == (2, 2)
    assert (sess.last_routing[:, 0] <= HELD[1] * 3).all()


def test_the_scopes_the_readers_look_for_are_in_the_step(opened):
    pred, state = opened
    spec = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for n, v in state.items()}
    text = jax.jit(pred._step_math()).lower(
        spec, *pred._step_specs(2)).as_text(debug_info=True)
    for scope in ("mla_proj", "mla_attention", "shared_expert", "moe_ffn",
                  "dense_ffn"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope
    pre = jax.jit(pred._prefill_math).lower(
        spec, jax.ShapeDtypeStruct((1, 16), np.int32),
        jax.ShapeDtypeStruct((), np.int32)).as_text(debug_info=True)
    assert "mla_prefill" in pre and "mla_attention" not in pre


def test_served_through_the_wire_with_the_default_placement(artifact,
                                                            opened):
    """registry.load_model -> DecodeBatcher -> the wire, no flag: three
    streams over two slots, joining and leaving a lane that runs windows,
    each the stream of a session of its own."""
    import threading
    pred, _ = opened
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    prompts = [_prompt(5), _prompt(17, seed=9), _prompt(2, seed=4)]
    outs, errs = [None] * 3, []
    try:
        boot.load_model("pangu", artifact, decode_slots=2)

        def worker(i):
            cli = ServingClient(server.endpoint)
            try:
                outs[i] = [t for c in cli.infer_stream(
                    "pangu", prompts[i], max_new_tokens=12 + i,
                    deadline_ms=60000.0) for t in c]
            except Exception as e:                       # noqa: BLE001
                errs.append(e)
            finally:
                cli.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs, errs
        for i, (p, out) in enumerate(zip(prompts, outs)):
            want, _ = dec.greedy_decode(pred, p, 12 + i)
            assert [int(t) for t in out] == want
        stats = boot.stats()["stats"]["models"]["pangu"]
        assert stats["kv_cache_bytes"] == pred.kv_cache_bytes(2)
    finally:
        boot.close()
        server.shutdown(drain=True)
