"""A stack of block-sparse attention layers (an indexer's compressed-key cache
beside the K/V rows, the top-k blocks of a position attended over) among
linear-attention layers (a decayed running sum a head, the kind `ssm`), and a
prefill that runs its bucket in chunks: the program against the plain
reference benchmark/reference/minicpm_sala_9b.py at a tiny size, the kernel
against its reference, the selection rule, the two kinds of slot state and
`block_of`'s refusals."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from benchmark.reference import minicpm_sala_9b as ref        # noqa: E402
from paddle_tpu.inference import decode, slot_state           # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk               # noqa: E402

KINDS = ["sparse_attention", "linear_attention", "linear_attention",
         "sparse_attention"]
BLOCK = dict(
    norm="rmsnorm", norm_eps=1e-6, position="rope", rope_theta=10000.0,
    rope_layers="linear", qk_norm="head", n_kv_heads=2, head_dim=8,
    layer_types=KINDS, ssm_heads=4, ssm_head_dim=8, ssm_state=8,
    ssm_groups=4, ssm_chunk=16, linear_log_decay=[-0.6, -0.3, -0.1, -0.02],
    sparse_block=16, sparse_topk=6, sparse_init_blocks=1, sparse_window=32,
    sparse_kernel_size=8, sparse_kernel_stride=4, output_gate=True,
    output_norm=True, prefill_chunk=32, ffn="swiglu", dense_width=48,
    head="untied", attention_out_multiplier=0.7, mlp_multipliers=[1.0, 0.7],
    embedding_multiplier=3.0, lm_head_multiplier=0.5)
SIZES = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
             max_seq_len=256, eos_id=0, prefill_buckets=[64, 128, 256])
SEED = 5


def meta_of(**edit):
    return {**BLOCK, **SIZES, **edit}


def state_of(meta):
    return {n: np.asarray(ref.draw_tensor(n, s, SEED, jnp.float32, meta))
            for n, s in ref.tensor_shapes(meta).items()}


def predictor(tmp, meta):
    return decode.load_decode_predictor(decode.save_decode_model(
        str(tmp), state_of(meta), meta))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(meta, weights, predictor, prompt lengths, sequences as served,
    [steps][slots, vocab] logits, the session) after prefills under one
    block, across the top-k's edge and far past it, and 20 decode steps."""
    meta = meta_of()
    pred = predictor(tmp_path_factory.mktemp("lm"), meta)
    rng = np.random.default_rng(0)
    lens = (5, 61, 120, 200)
    sess = pred.new_session(len(lens))
    seqs = [list(rng.integers(1, 64, n)) for n in lens]
    for i, s in enumerate(seqs):
        s.append(sess.prefill(i, s))
    got = []
    for _ in range(20):
        toks, logits = sess.decode_logits()
        got.append(logits)
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    return meta, state_of(meta), pred, lens, seqs, got, sess


def test_the_reference_draws_the_programs_weights():
    meta = meta_of()
    assert ref.tensor_shapes(meta) == decode.decode_state_shapes(meta)


def test_program_matches_the_plain_reference(served):
    """The prefill's token and 20 decode steps through all three kinds of
    slot state (K/V rows and the sparse kernel, the compressed keys, the
    linear layers' states), by logits."""
    meta, state, _, lens, seqs, got, _ = served
    for i, s in enumerate(seqs):
        logits, gaps = ref.forward(state, jnp.asarray(s[:-1], jnp.int32),
                                   meta)
        logits, n = np.asarray(logits), lens[i]
        assert int(np.argmax(logits[n - 1])) == s[n]
        for t in range(20):
            assert np.abs(got[t][i] - logits[n + t]).max() < 1e-4, (n, t)
        # the two long streams select (more than 6 blocks in sight): the
        # reference reports a gap there and none for the short ones
        assert (float(np.asarray(gaps).min()) < ref.NO_GAP) == (n > 96)


def test_the_slot_state_is_the_references(served):
    """After the run a slot holds what the reference's forward leaves: the
    compressed keys (zeros past the last one that is complete) and the
    linear layers' states."""
    meta, state, pred, lens, seqs, _, sess = served
    ki, ss = np.array(sess._ki, copy=True), np.array(sess._ss, copy=True)
    for i, s in enumerate(seqs):
        kept = ref.forward(state, jnp.asarray(s[:-1], jnp.int32), meta,
                           states=True)[2]
        T = len(s) - 1
        J = max((T - 8) // 4 + 1, 0)
        for a, layer in enumerate((0, 3)):
            np.testing.assert_allclose(
                ki[a, i, :J], np.asarray(kept[layer]).reshape(J, -1),
                rtol=1e-4, atol=1e-4)
            assert not ki[a, i, J:].any()
        for a, layer in enumerate((1, 2)):
            np.testing.assert_allclose(ss[a, i], np.asarray(kept[layer]),
                                       rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("chunk", [16, 64, 0])
def test_a_prompt_prefilled_in_chunks_equals_the_whole(tmp_path, served,
                                                       chunk):
    """Chunks of 1, 2 (the fixture's) and 4 blocks and the bucket whole: the
    first token, the rows, the compressed keys and the states a prefill
    leaves do not depend on the chunk."""
    _, _, pred, lens, seqs, _, _ = served
    other = predictor(tmp_path, meta_of(prefill_chunk=chunk))
    assert other.prefill_chunks(100) == (128 // chunk if chunk else 0)
    for n, s in zip(lens, seqs):
        a, b = pred.new_session(1), other.new_session(1)
        assert a.prefill(0, s[:n]) == b.prefill(0, s[:n]) == s[n]
        for leaf in ("_kc", "_vc", "_ki", "_ss"):
            np.testing.assert_allclose(
                np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)),
                rtol=1e-4, atol=1e-4, err_msg=leaf)


@pytest.mark.parametrize("chunk,n,size", [(32, 129, 160), (32, 200, 224),
                                          (0, 150, 160)])
def test_a_prompt_past_the_last_bucket_prefills_in_whole_chunks(
        tmp_path, chunk, n, size):
    """A prompt longer than every configured bucket and inside the cache:
    the one-off compile is the next whole number of chunks (of sparse blocks
    and scan chunks where the bucket runs whole), warned of once, and its
    first token and the steps after it are the reference's."""
    meta = meta_of(prefill_chunk=chunk, prefill_buckets=[64, 128])
    pred = predictor(tmp_path, meta)
    with pytest.warns(RuntimeWarning, match="%d positions" % size):
        assert pred.prompt_bucket(n) == size
    assert pred.prefill_chunks(n) == (size // chunk if chunk else 0)
    seq = list(np.random.default_rng(n).integers(1, 64, n))
    sess = pred.new_session(1)
    seq.append(sess.prefill(0, seq))
    got = []
    for _ in range(6):
        toks, logits = sess.decode_logits()
        got.append(logits[0])
        seq.append(int(toks[0]))
    logits = np.asarray(ref.forward(state_of(meta), jnp.asarray(
        seq[:-1], jnp.int32), meta)[0])
    assert int(np.argmax(logits[n - 1])) == seq[n]
    for t in range(6):
        assert np.abs(got[t] - logits[n + t]).max() < 1e-4, t


def test_a_compressed_key_appears_exactly_when_its_last_position_lands(
        tmp_path):
    """Key j covers positions 4 j .. 4 j + 7: row j of the indexer's cache is
    zeros until position 4 j + 7 is cached, in a prefill and step by step."""
    pred = predictor(tmp_path, meta_of())
    sess = pred.new_session(2)
    sess.prefill(0, list(range(1, 11)))           # positions 0 .. 9: key 0
    sess.prefill(1, list(range(1, 7)))            # 0 .. 5: none
    for length in range(10, 24):
        ki = np.array(sess._ki, copy=True)
        for slot, held in ((0, length), (1, length - 4)):
            complete = max((held - 8) // 4 + 1, 0)
            assert ki[:, slot, :complete].any(axis=-1).all(), (held, slot)
            assert not ki[:, slot, complete:].any(), (held, slot)
        sess.decode()


def test_the_selection_is_dense_attention_while_topk_blocks_are_in_sight():
    """`_sparse_select` chooses every block in sight while they are no more
    than `sparse_topk`, and past that exactly topk of them: the init block
    and the blocks of the window among them, highest scores first."""
    blk = decode.block_of(meta_of())
    rng = np.random.default_rng(1)
    J, NB = 62, 16
    s = jnp.asarray(rng.normal(size=(7, 2, 2, J)), jnp.float32)
    t = np.array([3, 40, 95, 96, 130, 200, 255])
    ids, count, score, vals = decode._sparse_select(s, t[:, None], blk, NB)
    ids, count, score = np.asarray(ids), np.asarray(count), np.asarray(score)
    for i, pos in enumerate(t):
        in_sight = pos // 16 + 1
        assert (count[i] == min(in_sight, 6)).all()
        for g in range(2):
            mine = set(ids[i, g, :count[i, g]])
            forced = {0} | {b for b in range(in_sight)
                            if 16 * b + 15 >= pos - 31}
            assert forced <= mine and max(mine) < in_sight
            if in_sight <= 6:
                assert mine == set(range(in_sight))
            else:
                free = [b for b in range(in_sight) if b not in forced]
                best = sorted(free, key=lambda b: (-score[i, g, b], b))[
                    :6 - len(forced)]
                assert mine == forced | set(best)
    # a compressed key not yet seen counts for nothing: scores of blocks
    # whose keys are all ahead of the position are 0 or forced
    assert (score[0, :, 1:] == -1).all()


def _kernel_case(seed, N, S, K, block, lengths, counts, G=4, L=2):
    """Seeded tables, queries and block ids of `sparse_decode_attention`: a
    (slot, head)'s ids are its slot's own last block, then distinct others
    in a drawn order."""
    rng = np.random.default_rng(seed)
    Hc, D = 2, 128
    kc, vc = (jnp.asarray(rng.normal(size=(L, N, S, Hc * D)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(N, Hc * G, D)), jnp.float32)
    lengths = np.asarray(lengths, np.int32)
    last = np.maximum(-(-lengths // block), 1) - 1
    ids = np.zeros((N, Hc, K), np.int32)
    for n in range(N):
        for g in range(Hc):
            ids[n, g] = [last[n]] + [b for b in rng.permutation(S // block)
                                     if b != last[n]][:K - 1]
    return q, kc, vc, ids, np.asarray(counts, np.int32), lengths, last


_T64 = pk.sparse_tiles_per_step(64, 64, 128)
_T12 = pk.sparse_tiles_per_step(12, 64, 128)
SPARSE_KERNEL_CASES = {
    # the K = 3 case: K is not a multiple of the tiles a step stages
    "K=3": dict(N=5, S=512, K=3, lengths=[0, 200, 512, 70, 300],
                counts=[[0, 0], [3, 2], [3, 3], [1, 2], [0, 0]]),
    # 64 selected of 64-row blocks; counts at and around whole steps, the
    # two K/V heads of a slot apart
    "K=64": dict(N=5, S=8192, K=64,
                 lengths=[4100, 8192, 7000, 5000, 6000],
                 counts=[[64, 63], [1, _T64], [_T64 - 1, _T64 + 1],
                         [_T64 + 1, 64], [63, 1]]),
    "K=64, a head with count 0": dict(
        N=3, S=4096, K=64, lengths=[4096, 4000, 3000],
        counts=[[0, 64], [_T64, 0], [_T64 + 1, _T64 - 1]]),
    # slots that do not run first, in the middle and last
    "idle slots": dict(N=7, S=2048, K=12,
                       lengths=[0, 0, 900, 0, 2048, 1500, 0],
                       counts=[[0, 0], [0, 0], [12, 5], [0, 0], [1, 12],
                               [_T12 + 1, _T12], [0, 0]]),
    "no slot runs": dict(N=2, S=512, K=3, lengths=[0, 0],
                         counts=[[0, 0], [0, 0]]),
    # K = 12 where a step stages 8: the last step holds 4 and 4 repeats
    "K not a multiple of T": dict(N=3, S=2048, K=12,
                                  lengths=[2048, 1000, 1300],
                                  counts=[[12, 12], [9, 12], [8, 11]]),
}


@pytest.mark.parametrize("name", sorted(SPARSE_KERNEL_CASES))
def test_sparse_decode_attention_against_its_reference(name):
    """The Mosaic kernel's body under interpret emulation: selected blocks
    only, T tiles a grid step (the last step's tiles past the count are
    repeats, masked), the slot's own last block masked by its length,
    grouped-query; a (slot, head) with count 0 is not visited and reads
    zeros."""
    case = SPARSE_KERNEL_CASES[name]
    block = 64
    if name in ("K=3", "K not a multiple of T"):
        assert case["K"] % pk.sparse_tiles_per_step(case["K"], block, 128)
    q, kc, vc, ids, counts, lengths, _ = _kernel_case(
        0, case["N"], case["S"], case["K"], block, case["lengths"],
        case["counts"])
    out = pk.sparse_decode_attention(q, kc, vc, jnp.asarray(ids),
                                     jnp.asarray(counts), lengths, 1, block)
    want = pk.sparse_decode_attention_reference(
        q, kc[1], vc[1], jnp.asarray(ids), jnp.asarray(counts), lengths,
        block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    G = q.shape[1] // 2
    idle = np.repeat(counts == 0, G, axis=1)                 # [N, H]
    assert not np.asarray(out)[idle].any()
    assert np.asarray(out)[~idle].all()


@pytest.mark.parametrize("S,lengths", [
    (512, [1, 200, 512, 70, 300]),          # 8 blocks: K = 8, one step
    (2048, [2048, 1, 1100, 65, 1984]),      # 32 blocks: several steps
])
def test_sparse_decode_attention_over_every_block_is_decode_attention(
        S, lengths):
    """With every block of a slot selected the sparse kernel is
    `decode_attention`: against ITS reference, whole rows under the
    lengths."""
    block = 64
    q, kc, vc, _, _, lengths, last = _kernel_case(
        1, len(lengths), S, S // block, block, lengths, [[0, 0]])
    N = len(lengths)
    every = jnp.broadcast_to(jnp.arange(S // block, dtype=jnp.int32),
                             (N, 2, S // block))
    dense = pk.decode_attention_reference(q, kc[1], vc[1], lengths)
    out = pk.sparse_decode_attention(
        q, kc, vc, every, jnp.asarray(np.broadcast_to(
            (last + 1)[:, None], (N, 2)).astype(np.int32)), lengths, 1,
        block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-5)


def test_the_tiles_a_step_stages_come_from_the_shapes():
    """T is worked out from the shapes alone: a power of two, no more than
    the blocks selected, 4 T tiles (K and V, double-buffered) inside the
    VMEM budget; at the cell's shapes it is 8 or more."""
    assert pk.sparse_tiles_per_step(64, 64, 128) >= 8
    for topk, block, D, size in ((64, 64, 128, 4), (3, 64, 128, 4),
                                 (6, 16, 8, 4), (1, 64, 128, 4),
                                 (64, 128, 256, 4), (64, 64, 128, 2)):
        T = pk.sparse_tiles_per_step(topk, block, D, size)
        assert 1 <= T <= topk and T & (T - 1) == 0
        assert T == 1 or 4 * T * block * D * size <= pk._SPARSE_STAGE_BYTES
        assert 2 * T > topk \
            or 8 * T * block * D * size > pk._SPARSE_STAGE_BYTES


def _prefill_case(seed, C, B, block, chunk, true_len, G=2, D=8,
                  picks="drawn", topk=3):
    """Seeded operands of `sparse_prefill_attention`: chunk `chunk`'s C
    queries, B buffered rows (zeros at and past `true_len`, as the prefill
    keeps them) and a selection a (query, K/V head).  `picks`: "drawn" =
    the query's own block and `topk` drawn of those in sight; "all" = every
    block in sight; "own" = the query's own block alone (every earlier key
    tile holds nothing it sees)."""
    rng = np.random.default_rng(seed)
    Hc = 2
    q = jnp.asarray(rng.normal(size=(C, Hc * G, D)), jnp.float32)
    live = (np.arange(B) < true_len)[:, None]
    k, v = (jnp.asarray(rng.normal(size=(B, Hc * D)) * live, jnp.float32)
            for _ in range(2))
    sel = np.zeros((C, Hc, B // block), bool)
    for t in range(C):
        own = min(chunk * C + t, B - 1) // block
        for g in range(Hc):
            sel[t, g, own] = True
            if picks == "all":
                sel[t, g, :own] = True
            elif picks == "drawn":
                sel[t, g, rng.permutation(own + 1)[:topk]] = True
    return q, k, v, jnp.asarray(sel.transpose(1, 2, 0))


# C = 32 queries a chunk, B = 128 buffered rows (4 chunks), blocks of 16
SPARSE_PREFILL_CASES = {
    "the first chunk": dict(chunk=0, true_len=32),
    "a middle chunk": dict(chunk=2, true_len=128),
    "the bucket's last chunk": dict(chunk=3, true_len=128),
    "the prompt ends inside the chunk": dict(chunk=2, true_len=77),
    "the prompt ends inside a query block": dict(
        chunk=1, true_len=45, block_q=8, block_kv=16),
    "the prompt ends on the chunk's edge": dict(chunk=1, true_len=64,
                                                block_q=8, block_kv=16),
    "the prompt's one position": dict(chunk=0, true_len=1, block_q=8,
                                      block_kv=16),
    "every block in sight is selected": dict(chunk=2, true_len=96,
                                             picks="all", block_q=16,
                                             block_kv=32),
    "tiles that hold nothing a query sees": dict(
        chunk=3, true_len=128, picks="own", block_q=8, block_kv=16),
    "sixteen query heads a K/V head": dict(chunk=1, true_len=60, G=16,
                                           block_q=16, block_kv=32),
    "one query head a K/V head": dict(chunk=3, true_len=120, G=1,
                                      block_q=16, block_kv=64),
    # a block of 8 queries ends inside a 48-key tile and a tile inside a
    # block of queries: neither frontier is the other's multiple
    "tiles that do not divide each other's frontier": dict(
        chunk=1, true_len=96, B=96, block_q=8, block_kv=48),
    "queries past the keys' tile": dict(chunk=1, true_len=64, block_q=32,
                                        block_kv=16),
    "bf16 operands": dict(chunk=2, true_len=90, block_q=16, block_kv=32,
                          compute_dtype="bfloat16", atol=3e-2),
}


@pytest.mark.parametrize("name", sorted(SPARSE_PREFILL_CASES))
def test_sparse_prefill_attention_against_its_reference(name):
    """The flash body of the chunked prefill's stage 2 under interpret
    emulation against the parent's form (running softmax over tiles of C
    keys in plain XLA): the prompt's positions of the chunk are equal, a
    block of queries wholly past the prompt reads zeros, nothing is NaN
    whatever a tile holds."""
    case = dict(SPARSE_PREFILL_CASES[name])
    C, block = 32, 16
    B, chunk, true_len = case.pop("B", 128), case["chunk"], case["true_len"]
    q, k, v, sel = _prefill_case(
        3, C, B, block, chunk, true_len, G=case.get("G", 2),
        picks=case.get("picks", "drawn"))
    Qb, Tk = pk.sparse_prefill_tiles(C, B, q.shape[1] // 2, 8, block)
    Qb, Tk = case.get("block_q", Qb), case.get("block_kv", Tk)
    out = np.asarray(pk.sparse_prefill_attention(
        q, k, v, sel, jnp.int32(chunk), jnp.int32(true_len), block,
        block_q=Qb, block_kv=Tk, compute_dtype=case.get("compute_dtype")))
    want = np.asarray(pk.sparse_prefill_attention_reference(
        q, k, v, sel, chunk, block))
    n = min(C, true_len - chunk * C)
    assert n >= 1 and np.isfinite(out).all()
    np.testing.assert_allclose(out[:n], want[:n],
                               atol=case.get("atol", 1e-5))
    assert np.abs(out[:n]).max() > 1e-2
    dead = -(-n // Qb) * Qb                 # the first wholly dead block
    assert not out[dead:].any()
    if case.get("picks") == "all":
        # dense causal attention over the prompt, computed here
        H, G = q.shape[1], q.shape[1] // 2
        kh, vh = (np.repeat(np.asarray(t).reshape(B, 2, 8), G, axis=1)
                  for t in (k, v))
        s = np.einsum("qhd,khd->hqk", np.asarray(q), kh) / np.sqrt(8.0)
        s = np.where(np.arange(B)[None, None]
                     <= chunk * C + np.arange(C)[None, :, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        dense = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), vh)
        np.testing.assert_allclose(out[:n], dense[:n], atol=1e-5)


def test_a_row_that_sees_nothing_in_a_tile_keeps_its_running_state():
    """With key tiles of one block each, a query of chunk 3 that selected
    its own block alone walks six tiles in which it sees nothing before the
    one it does: its result is, bit for bit, what the same rows give as
    chunk 0's, where its own tile is the first (no rescale by a masked
    tile's maximum, no exponent of a masked score)."""
    C, B, block = 32, 128, 16
    q, k, v, sel = _prefill_case(4, C, B, block, 3, 128, picks="own")
    late = np.asarray(pk.sparse_prefill_attention(
        q, k, v, sel, 3, 128, block, block_q=8, block_kv=16))
    first = np.asarray(pk.sparse_prefill_attention(
        q, jnp.roll(k, -96, axis=0), jnp.roll(v, -96, axis=0),
        jnp.roll(sel, -6, axis=1), 0, 32, block, block_q=8, block_kv=16))
    assert np.isfinite(late).all() and np.abs(late).max() > 1e-2
    np.testing.assert_array_equal(late, first)


def test_the_prefill_tiles_come_from_the_shapes():
    """(Qb, Tk) from the shapes alone: divisors of the chunk and the
    buffer, whole tiles under Mosaic and the block's state inside its VMEM
    budget; the reference where Mosaic has no such tiles."""
    Qb, Tk = pk.sparse_prefill_tiles(2048, 16384, 16, 128, 64, mosaic=True)
    assert 2048 % Qb == 0 and Qb % 16 == 0 and Qb >= 128
    assert 16384 % Tk == 0 and Tk % 128 == 0 and Tk % 64 == 0
    assert Qb * 16 * (16 * 128 + 8 * 128) <= pk._PREFILL_BLOCK_BYTES
    assert pk.sparse_prefill_tiles(2048, 16384, 16, 96, 64,
                                   mosaic=True) is None
    assert pk.sparse_prefill_tiles(2048, 16384, 16, 128, 12,
                                   mosaic=True) is None
    # the tests' stack: head size 8, blocks of 16, interpret emulation
    assert pk.sparse_prefill_tiles(32, 128, 2, 8, 16, mosaic=False) \
        == (32, 128)
    q, k, v, sel = _prefill_case(5, 32, 128, 16, 1, 64)
    with pk.mosaic_lowering():
        # no whole tiles at head size 8: the reference, in plain XLA
        out = pk.sparse_prefill_attention(q, k, v, sel, 1, 64, 16)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(pk.sparse_prefill_attention_reference(
            q, k, v, sel, 1, 16)))


def test_the_lightning_step_is_ssm_update_and_the_chunked_scan():
    """S <- decay S + v (outer) k; o = S . q through `pk.ssm_update` a
    position at a time, against the plain recurrence and against
    `ssd_chunked_scan` (dt = 1) started from a CARRIED state."""
    rng = np.random.default_rng(2)
    T, Hs, P, N = 40, 4, 8, 8
    A = jnp.asarray([-0.6, -0.3, -0.1, -0.02], jnp.float32)
    v, k, q = (jnp.asarray(rng.normal(size=(T, Hs, d)), jnp.float32)
               for d in (P, N, N))
    S = np.zeros((Hs, P, N), np.float32)
    want = []
    for t in range(T):
        S = np.exp(np.asarray(A))[:, None, None] * S \
            + np.asarray(v[t])[:, :, None] * np.asarray(k[t])[:, None, :]
        want.append((S * np.asarray(q[t])[:, None, :]).sum(-1))
    table = jnp.zeros((2, 1, Hs, P, N), jnp.float32)
    got = []
    for t in range(T):
        y, table = pk.ssm_update(
            table, jnp.exp(A)[None], v[t][None], k[t][None], q[t][None],
            jnp.ones((1,), bool), 1)
        got.append(np.asarray(y[0]))
    np.testing.assert_allclose(np.stack(got), np.stack(want), atol=1e-4)
    np.testing.assert_allclose(np.asarray(table[1, 0]), S, atol=1e-4)
    assert not np.asarray(table[0]).any()
    ones = jnp.ones((T, Hs), jnp.float32)
    y1, mid = decode.ssd_chunked_scan(v[:24], k[:24], q[:24], ones[:24], A,
                                      16)
    y2, end = decode.ssd_chunked_scan(v[24:], k[24:], q[24:], ones[24:], A,
                                      16, state=mid)
    np.testing.assert_allclose(np.concatenate([y1, y2]), np.stack(want),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(end), S, atol=1e-4)


REFUSED = {
    "sparse_sizes_without_a_sparse_layer": dict(
        layer_types=["attention"] * 4, linear_log_decay=[], ssm_heads=0,
        ssm_head_dim=0, ssm_state=0, ssm_groups=1, output_gate=False,
        output_norm=False, prefill_chunk=0, rope_layers="all"),
    "a_sparse_layer_without_its_sizes": dict(sparse_topk=0),
    "a_block_that_is_not_whole_strides": dict(sparse_block=18),
    "topk_under_the_forced_blocks": dict(sparse_topk=3),
    "another_operator_in_the_stack": dict(
        layer_types=["sparse_attention", "attention", "linear_attention",
                     "sparse_attention"]),
    "linear_layers_alone": dict(
        layer_types=["linear_attention"] * 4, sparse_block=0, sparse_topk=0,
        sparse_init_blocks=0, sparse_window=0, sparse_kernel_size=0,
        sparse_kernel_stride=0),
    "groups_that_are_not_the_heads": dict(ssm_groups=2),
    "a_conv_before_a_linear_layer": dict(ssm_conv_kernel=4),
    "slopes_of_another_count": dict(linear_log_decay=[-0.1, -0.2]),
    "a_decay_that_grows": dict(linear_log_decay=[0.1, -0.1, -0.1, -0.1]),
    "rope_layers_linear_without_rope": dict(position="learned"),
    "a_chunk_that_is_not_whole_blocks": dict(prefill_chunk=24),
    "a_bucket_that_is_not_whole_chunks": dict(prefill_buckets=[48, 128]),
    # a prompt past every bucket prefills in the next whole chunks, which
    # the cache has to hold
    "a_cache_that_is_not_whole_chunks": dict(max_seq_len=272,
                                             prefill_buckets=[64, 128]),
    "a_gate_on_an_attention_stack": dict(
        layer_types=["attention"] * 4, linear_log_decay=[], ssm_heads=0,
        ssm_head_dim=0, ssm_state=0, ssm_groups=1, output_norm=False,
        prefill_chunk=0, rope_layers="all", sparse_block=0, sparse_topk=0,
        sparse_init_blocks=0, sparse_window=0, sparse_kernel_size=0,
        sparse_kernel_stride=0),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_block_of_refuses_keys_that_do_not_go_together(case):
    with pytest.raises(ValueError, match="decode meta"):
        decode.block_of(meta_of(**REFUSED[case]))


def test_older_artifacts_describe_no_new_key():
    blk = decode.block_of(dict(SIZES))
    for key in ("sparse_block", "sparse_topk", "prefill_chunk"):
        assert blk[key] == 0
    assert blk["linear_log_decay"] == () and not blk["output_gate"]


def test_the_two_operators_slot_state_and_what_refuses_it(served):
    """`slot_state`: a sparse layer holds `kv` and `index`, a linear layer
    `ssm`; the tables' shapes, the bytes and the fetch spans' attributes;
    rollback, a mesh, the speculative phases and int8 each refuse the
    indexer's cache with its own sentence."""
    meta, _, pred, _, _, _, sess = served
    blk = decode.block_of(meta)
    assert slot_state.HOLDS["sparse_attention"] == ("kv", "index")
    assert slot_state.HOLDS["linear_attention"] == ("ssm",)
    assert [(k.name, n) for k, n in slot_state.kinds_held(meta, blk)] == [
        ("kv", 2), ("ssm", 2), ("index", 2)]
    assert slot_state.kind_shapes(meta, blk, 4, None) == {
        "kv": (2, 4, 256, 16), "ssm": (2, 4, 4, 8, 8),
        "index": (2, 4, 63, 16)}
    assert pred._table_names == ("kc", "vc", "ss", "ki")
    kinds, totals = slot_state.state_bytes(meta, blk, 4, None)
    assert kinds == {"kv": 2 * 2 * 4 * 256 * 16 * 4,
                     "ssm": 2 * 4 * 4 * 8 * 8 * 4,
                     "index": 2 * 4 * 63 * 16 * 4}
    assert totals["kv_cache_bytes"] == sum(kinds.values())
    assert sess._stack_attrs == {
        "ssm_layers": 2, "ssm_state_bytes": kinds["ssm"],
        "sparse_layers": 2, "index_cache_bytes": kinds["index"],
        "linear_layers": 2}
    # each kind without a rule refuses with its own sentence: the scanned
    # state first (the record's order), the indexer's cache where it alone
    # is asked
    def only(name):
        return type("P", (), {
            "_kinds": [k for k in pred._kinds if k[0].name == name],
            "_block_meta": pred._block_meta})()
    for what, capability in (("a rollback", "rollback"),
                             ("a mesh placement", "mesh"),
                             ("the speculative verify", "speculative"),
                             ("an int8 KV cache", "int8")):
        with pytest.raises(NotImplementedError, match="running sum of k"):
            pred._require(what, capability)
        with pytest.raises(NotImplementedError,
                           match="compressed key every 4 positions"):
            decode.GenerativePredictor._require(only("index"), what,
                                                capability)
        decode.GenerativePredictor._require(only("kv"), what, capability)


def test_the_step_counts_the_blocks_it_stages(served):
    """`_kv_stream` of a sparse stack counts what the sparse kernel stages:
    min(topk, blocks in sight) a K/V head a sparse layer a running slot, and
    the rows attended over the rows in sight."""
    _, _, _, _, _, _, sess = served
    sess.lengths[:] = [10, 95, 96, 250]
    counts = np.array([2, 2, 2, 0])
    out = sess._kv_stream(counts, 2)
    # slots 0..2 run both trips under lengths + trip + 1 positions
    seen = np.array([[11, 96, 97], [12, 97, 98]])
    blocks = np.minimum(-(-seen // 16), 6)
    assert out["kv_blocks_live"] == int(blocks.sum()) * 2 * 2
    assert out["kv_blocks_total"] == 2 * 4 * 2 * 2 * 16
    assert out["rows_in_sight"] == int(seen.sum())
    # 97 positions are 7 blocks: 5 whole ones and the 1 of the last
    assert out["selected_rows"] == 11 + 96 + (5 * 16 + 1) + 12 + 81 + 82
    assert out["selected_blocks"] == out["kv_blocks_live"] // 2


def test_the_step_counts_the_grid_steps_it_stages_them_in(served):
    """`kv_grid_steps` of a sparse stack's `_kv_stream`: the kernel stages T
    tiles a grid step (`pk.sparse_tiles_per_step`), so a running slot's K/V
    head takes ceil(min(topk, blocks in sight) / T) steps a sparse layer a
    trip, and a slot that does not run none."""
    _, _, _, _, _, _, sess = served
    T = pk.sparse_tiles_per_step(6, 16, 8)
    assert T == 4
    sess.lengths[:] = [10, 47, 48, 250]
    # one trip of slots 0, 1, 3: 11, 48 and 251 positions are 1, 3 and 16
    # blocks in sight, of which 1, 3 and 6 are chosen: 1, 1 and 2 steps
    out = sess._kv_stream(np.array([1, 1, 0, 1]), 1)
    assert out["kv_blocks_live"] == (1 + 3 + 6) * 2 * 2
    assert out["kv_grid_steps"] == (1 + 1 + 2) * 2 * 2
    # two trips of slots 1 and 2: 48, 49 and 49, 50 positions: 3, 4, 4, 4
    # blocks chosen, a step each
    out = sess._kv_stream(np.array([0, 2, 2, 0]), 2)
    assert out["kv_blocks_live"] == (3 + 4 + 4 + 4) * 2 * 2
    assert out["kv_grid_steps"] == 4 * 2 * 2
    # slot 2 alone for three trips: 5 blocks in sight from 65 positions on
    sess.lengths[:] = [10, 47, 63, 250]
    out = sess._kv_stream(np.array([0, 0, 3, 0]), 3)
    assert out["kv_blocks_live"] == (4 + 5 + 5) * 2 * 2
    assert out["kv_grid_steps"] == (1 + 2 + 2) * 2 * 2
    assert sess._kv_stream(np.zeros(4, np.int64), 1)["kv_grid_steps"] == 0


def test_the_counted_blocks_are_those_the_step_hands_its_kernel(served):
    """`_sparse_stream` works its counts out on the host from the slots'
    lengths, by the selection's rule; nothing is fetched from the device for
    them.  Here the rule is held to the device's own: the blocks a step
    selected (`last_picks`, -1 past a slot's count, which is what the kernel
    is given to stage) are as many as the host counted for that step."""
    _, _, pred, lens, seqs, _, _ = served
    sess = pred.new_session(len(lens))
    for i, (n, s) in enumerate(zip(lens, seqs)):
        sess.prefill(i, s[:n])
    for _ in range(3):
        counted = sess._kv_stream(np.ones(len(lens), np.int32), 1)
        sess.decode_logits()
        picks = sess.last_picks            # [sparse layers, N, Hc, k]
        assert int((picks >= 0).sum()) == counted["kv_blocks_live"] \
            == counted["selected_blocks"]
        np.testing.assert_array_equal(
            (picks >= 0).sum(-1)[0, :, 0],
            np.minimum(-(-sess.lengths // 16), 6))


def test_the_prefill_hands_out_its_last_positions_selection(served):
    """The blocks a prompt's last position selected ride the first token's
    fetch (`last_prefill_picks`, -1 past their count): they are the blocks
    the STEP selects at that position, when the prompt less its last token
    is prefilled and the token stepped."""
    _, _, pred, lens, seqs, _, _ = served
    for n, s in zip(lens, seqs):
        a, b = pred.new_session(1), pred.new_session(1)
        assert a.prefill(0, s[:n]) == s[n]
        b.prefill(0, s[:n - 1])
        b.last_tokens[0] = s[n - 1]
        b.decode_logits()
        ours, steps = a.last_prefill_picks, b.last_picks[:, 0]
        assert ours.shape[:2] == steps.shape[:2] == (2, 2)
        for layer in range(2):
            for head in range(2):
                assert sorted(i for i in ours[layer, head] if i >= 0) \
                    == sorted(i for i in steps[layer, head] if i >= 0)
        assert (ours >= 0).sum() == 2 * 2 * min(-(-n // 16), 6)
