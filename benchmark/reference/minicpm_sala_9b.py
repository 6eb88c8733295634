"""Plain reference of the `minicpm_sala_9b` configuration: the MiniCPM-SALA
decoder (HF `openbmb/MiniCPM-SALA` config.json, `model_type` minicpm_sala:
hidden 4096, 32 query heads / 2 K/V heads of 128, a dense SwiGLU of 16384 in
every layer, `mixer_types` = `minicpm4` layers (learned block-sparse
attention, InfLLM-v2 of the MiniCPM4 report, arXiv:2506.07900; no rotary)
among `lightning-attn` layers (linear attention with a fixed decay a head, 32
heads of 128, rotary), output gates on both, an output norm on the linear
ones, q/k norm a head, muP multipliers, untied head over 73,448 rows) - the
FULL forward to logits over a whole sequence, float32 `jax.numpy` at
"highest" matmul precision.  The linear layers' recurrence is SEQUENTIAL,
position by position (`lax.scan` over positions); the sparse layers'
selection and attention are WHOLE MASKS over all keys: no kernel, no cache,
no chunk, no bucket, no batching.  Rows are taken a block at a time
(`ROW_BLOCK`) where a [T, 16384] or a [heads, T, T] tensor would not fit;
what a row reads is the whole sequence.

The layer, x [T, D] (position t = row t), r = `attention_out_multiplier` =
`mlp_multipliers[1]` = scale_depth / sqrt(the PUBLISHED depth 32):

    h = rms(x; ln1_g)
    # a `sparse_attention` layer (minicpm4)
    q = rms a head(h wq) [H, Dh];  k = rms a head(h wk), v = h wv  [Hc, Dh]
    kc_j = mean(k[stride j .. stride j + size - 1])     a K/V head, seen from
                                    position t once stride j + size - 1 <= t
    p    = softmax over the seen j of (q . kc_j / sqrt(Dh))   a query head,
           SUMMED over the K/V head's H / Hc query heads
    score_b = max of p over the compressed keys that overlap block b
           (positions block b .. block b + block - 1); +inf for the first
           `sparse_init_blocks` blocks and those that hold one of positions
           t - sparse_window + 1 .. t; blocks past t are out of sight
    the `sparse_topk` highest are SELECTED (ties to the lower index), all
           of those in sight where they are fewer
    a    = softmax over the keys j <= t of the selected blocks of
           (q . k_j / sqrt(Dh)) v_j   query head i reads K/V head i // (H / Hc)
    x    = x + r ((a * sigmoid(h wg)) wo)
    # a `linear_attention` layer (lightning-attn)
    q, k = rope(rms a head(h wq)), rope(rms a head(h wk))  [Hs, N]; v [Hs, P]
    S_t  = exp(linear_log_decay_h) S_{t-1} + v_t (outer) k_t     S_{-1} = 0
    o_t  = S_t . q_t / sqrt(N)
    x    = x + r ((rms a head(o; on_g) * sigmoid(h wg)) wo)
    # every layer
    g = rms(x; ln2_g);  x = x + r ((g ffn_up * silu(g ffn_gate)) ffn_down)
    logits = (rms(x; lnf_g) lm_head) * lm_head_multiplier,
    x_0  = embed[token] * embedding_multiplier

What the published config is silent on is the configuration file's `assumed`
(the sparse sizes from MiniCPM4-8B's `sparse_config`, the decay slopes of
Lightning Attention, the output norm a head) and its two DEPARTURES: the
rule is applied at every position (no `dense_len` switch), and stage 1's
softmax is exact.

THE WEIGHTS are a pure function of (seed, tensor name); every matmul weight
is a BFLOAT16 number (drawn in float32, rounded once: the release's dtype,
the program's `weight_dtype`), gains float32.  THE SCALES (`weight_std`):
each matrix normal(0, gain / sqrt(fan_in)), the gain undoing the multipliers
on its product (embedding 1/12, head 16, the branches' last matrices 1/r
times an O(1) factor), so that every branch moves the residual stream by
the order of what it holds and the logits have std ~1.  A sparse layer's
key gain `kn_g` is `QK_GAIN` = 1.5 (its scores have std ~1.5, as
`falcon_h1_34b.py`'s: at 3 the softmax hangs on a handful of keys, one
sparse layer's rounding alone moved the logits by 0.13-0.16, and a block
that enters or leaves the selection by O(1): my chip run, PR 48).

THE VOCABULARY is drawn in blocks of `VOCAB_BLOCK` rows, as
`falcon_h1_34b.py` draws its own and for its reason (`embed_tokens`,
`head_blocked`).

THE PRECISION BELOW: `layer` and `head` compute in the dtype of what they
are given; handed a bfloat16 stream and `layer_weights(dtype=bfloat16)`,
every tensor of the forward, the linear layers' states among them, is
bfloat16.

THE SELECTION'S GAP.  `layer` returns, a position, how near the selection
came to another set: (score of the 64th block - score of the 65th) / the
64th's, the least over the K/V heads, `NO_GAP` where no 65th is in sight (or
the layer is linear).  With seeded weights the blocks' scores are nearly
flat (a compressed key is the mean of 32 independent keys), so the 64th and
the 65th lie within a program's rounding at most positions: a program whose
other matmuls round to bfloat16 rightly keeps another block there, which is
another function and no fault.  So the comparison FOLLOWS the program where
it hands its selection over (`sparse_attention`'s `hint`, as
`lfm2_24b_a2b.py` follows a router's picks) and reads the gap as it reads a
router's where it does not (the prompt's positions, a served stream).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np

SPARSE_WEIGHTS = ("ln1_g", "ln2_g", "wq", "wk", "wv", "wo", "wg", "qn_g",
                  "kn_g", "ffn_gate", "ffn_up", "ffn_down")
LINEAR_WEIGHTS = SPARSE_WEIGHTS + ("on_g",)
# no 65th block in sight, or no selection in the layer (finite: logs are JSON)
NO_GAP = 1e9
# the "gap" of a hinted position whose hint the reference did not follow
UNFOLLOWED = -1.0
# rows of the vocabulary drawn at a time (73,448 = 8 x 9,181)
VOCAB_BLOCK = 9181
# rows a layer takes at a time where T is more
ROW_BLOCK = 128
# std of a sparse layer's scores (its key gain), and what the branches'
# last matrices carry beside 1/r
QK_GAIN, SPARSE_OUT_GAIN, LINEAR_OUT_GAIN, FFN_DOWN_GAIN = 1.5, 4.0, 2.0, 2.0


def _sizes(model):
    D, H = int(model["d_model"]), int(model["n_heads"])
    Dh = int(model.get("head_dim") or D // H)
    Hc = int(model.get("n_kv_heads") or H)
    Hs, P, N = (int(model[k]) for k in ("ssm_heads", "ssm_head_dim",
                                        "ssm_state"))
    return D, H, Hc, Dh, Hs, P, N


def layer_weight_names(model, i):
    return LINEAR_WEIGHTS if model["layer_types"][i] == "linear_attention" \
        else SPARSE_WEIGHTS


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta)."""
    D, H, Hc, Dh, Hs, P, N = _sizes(model)
    V, F = int(model["vocab_size"]), int(model["dense_width"])
    common = {"ln1_g": (D,), "ln2_g": (D,), "ffn_gate": (D, F),
              "ffn_up": (D, F), "ffn_down": (F, D)}
    sparse = dict(common, wq=(D, H * Dh), wk=(D, Hc * Dh), wv=(D, Hc * Dh),
                  wo=(H * Dh, D), wg=(D, H * Dh), qn_g=(Dh,), kn_g=(Dh,))
    linear = dict(common, wq=(D, Hs * N), wk=(D, Hs * N), wv=(D, Hs * P),
                  wo=(Hs * P, D), wg=(D, Hs * P), qn_g=(N,), kn_g=(N,),
                  on_g=(Hs * P,))
    shapes = {"embed": (V, D), "lnf_g": (D,), "lm_head": (D, V)}
    for i, kind in enumerate(model["layer_types"]):
        one = linear if kind == "linear_attention" else sparse
        shapes.update({"l%d_%s" % (i, n): one[n]
                       for n in layer_weight_names(model, i)})
    return shapes


def _split(name):
    """(layer index or None, bare name)."""
    if name[:1] == "l" and name[1].isdigit():
        head, bare = name.split("_", 1)
        return int(head[1:]), bare
    return None, name


def weight_std(name, shape, model):
    """The std a MATRIX is drawn at (the module's docstring): gain /
    sqrt(fan_in), the gain undoing the multipliers on its product."""
    i, bare = _split(name)
    r = float(model.get("attention_out_multiplier", 1.0))
    down = float((model.get("mlp_multipliers") or (1.0, 1.0))[1])
    if bare == "embed":
        return 1.0 / float(model.get("embedding_multiplier", 1.0))
    if bare == "lm_head":
        gain = 1.0 / float(model.get("lm_head_multiplier", 1.0))
    elif bare == "wo":
        gain = (LINEAR_OUT_GAIN if model["layer_types"][i]
                == "linear_attention" else SPARSE_OUT_GAIN) / r
    elif bare == "ffn_down":
        gain = FFN_DOWN_GAIN / down
    else:
        gain = 1.0
    return gain / np.sqrt(shape[-2])


def at_rest(name, shape):
    """bfloat16 for a matmul weight, float32 for a gain."""
    return jnp.float32 if len(shape) == 1 else jnp.bfloat16


@jax.jit
def _seed_key(seed_u32):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)


def _key(name, seed):
    return jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                              np.uint32(zlib.crc32(name.encode())))


_normal = jax.jit(
    lambda key, shape, std: jax.random.normal(key, shape, jnp.float32) * std,
    static_argnums=(1, 2))


def _vector(name, shape, model):
    i, bare = _split(name)
    if bare == "kn_g" and model is not None \
            and model["layer_types"][i] == "sparse_attention":
        return jnp.full(shape, QK_GAIN, jnp.float32)
    return jnp.ones(shape, jnp.float32)


def vocab_blocks(V):
    """[(first row, rows)] of the blocks the vocabulary is drawn in."""
    return [(lo, min(VOCAB_BLOCK, V - lo)) for lo in range(0, V, VOCAB_BLOCK)]


def draw_vocab_block(name, shape, seed, model, b, dtype=None):
    """Block `b` of `embed` ([rows, D]) or of `lm_head` ([D, rows]), from a
    key of its own."""
    V = shape[0] if name == "embed" else shape[1]
    _, rows = vocab_blocks(V)[b]
    part = (rows, shape[1]) if name == "embed" else (shape[0], rows)
    key = jax.random.fold_in(_key(name, seed), np.uint32(b))
    rest = at_rest(name, shape)
    return _normal(key, part, float(weight_std(name, shape, model))).astype(
        rest).astype(dtype or rest)


def draw_tensor(name, shape, seed, dtype=None, model=None):
    """One weight, on the device, from (seed, name) alone, rounded to the
    dtype it has at rest and given in `dtype` (None: as it is at rest).
    `model`: the meta (a matrix's scale and a sparse layer's key gain read
    it)."""
    rest = at_rest(name, shape)
    if len(shape) == 1:
        return _vector(name, shape, model).astype(dtype or rest)
    if name in ("embed", "lm_head"):
        V = shape[0] if name == "embed" else shape[1]
        return jnp.concatenate(
            [draw_vocab_block(name, shape, seed, model, b, dtype)
             for b in range(len(vocab_blocks(V)))],
            axis=0 if name == "embed" else 1)
    return _normal(_key(name, seed), tuple(shape),
                   float(weight_std(name, shape, model))).astype(
        rest).astype(dtype or rest)


def layer_weights(model, seed, i, dtype=jnp.float32):
    """Layer i's weights under their bare names, drawn from the seed."""
    shapes = tensor_shapes(model)
    return {n: draw_tensor("l%d_%s" % (i, n), shapes["l%d_%s" % (i, n)],
                           seed, dtype, model)
            for n in layer_weight_names(model, i)}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * g


def _rope(x, theta):
    """x [T, heads, d], position t = row index, half-split."""
    T, _, d = x.shape
    half = d // 2
    inv = jnp.float32(theta) ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                                 / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _by_rows(fn, T, *rows, live=None):
    """fn(first row, *a block of each of `rows`) over blocks of `ROW_BLOCK`
    rows, concatenated; whole where T is no more (or not whole blocks).
    `live` (a scalar, may be traced): the rows from it on are PADDING behind
    a sequence; a block that begins there is not computed and reads zeros
    (causal: nothing before it reads them)."""
    if T <= ROW_BLOCK or T % ROW_BLOCK:
        return fn(0, *rows)
    n = T // ROW_BLOCK

    def block(a):
        if live is None:
            return fn(a[0], *a[1:])
        return jax.lax.cond(
            a[0] < live, lambda: fn(a[0], *a[1:]),
            lambda: jax.tree_util.tree_map(
                lambda o: jnp.zeros(o.shape, o.dtype),
                jax.eval_shape(fn, a[0], *a[1:])))

    out = jax.lax.map(
        block,
        (jnp.arange(n) * ROW_BLOCK,) + tuple(
            r.reshape((n, ROW_BLOCK) + r.shape[1:]) for r in rows))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((T,) + o.shape[2:]), out)


# a block's rows where the tokens are its own, `out` elsewhere: shapes that
# depend on the number of tokens alone, so one trace a length (a gather and a
# scatter of each block's OWN tokens were sixteen traces a sequence, ~10 s of
# the chip's set-up each: my chip run, PR 48)
_rows_of_block = jax.jit(lambda out, block, at, mine: jnp.where(
    mine[:, None], block[at], out))


def embed_tokens(model, seed, tokens, dtype=jnp.float32):
    """x_0 [n, D] of tokens [n], the table's blocks drawn one at a time."""
    tokens = np.asarray(tokens)
    shape = tensor_shapes(model)["embed"]
    out = jnp.zeros((len(tokens), shape[1]), dtype)
    for b, (lo, rows) in enumerate(vocab_blocks(shape[0])):
        mine = (tokens >= lo) & (tokens < lo + rows)
        if mine.any():
            block = draw_vocab_block("embed", shape, seed, model, b, dtype)
            out = _rows_of_block(out, block, np.where(mine, tokens - lo, 0),
                                 mine)
    return out * jnp.asarray(float(model.get("embedding_multiplier", 1.0)),
                             dtype)


def sparse_attention(h, w, model, hint=None, margin=0.0, live=None):
    """A `sparse_attention` layer's mixer on the normed input h [T, D] ->
    (result [T, D] before r, the selection's gap [T] float32, the
    compressed keys [J, Hc, Dh] for the CPU tests).

    `hint` = (rows [M] int, ids [M, Hc, k] int, -1 = none): the blocks a
    program selected at those positions (a row >= T names none: a hint of
    such rows alone is no hint, under the trace of one that is).  A hinted
    position FOLLOWS the
    hint where the reference's OWN scores call it a near-tie and nowhere
    else: the hinted set has the size of the reference's, holds every
    forced block and none out of sight, each of its blocks scores no less
    than the reference's k-th best less `margin` of it, and no block left
    out scores more than that plus `margin` of it (rounding may decide such
    blocks either way, and the two stay one function of the later
    positions).  Its gap reads `NO_GAP` where it was followed (a position
    whose selection was handed over is held with no excuse) and
    `UNFOLLOWED` where it was not: a selection the reference's scores do
    not nearly tie on is a fault of its own, whatever it does to the
    logits (one block of 64 more or less moves them little).  `live`:
    `_by_rows`'s."""
    T = h.shape[0]
    _, H, Hc, Dh = _sizes(model)[:4]
    G = H // Hc
    eps = float(model["norm_eps"])
    size, stride, block, topk, init, window = (int(model[k]) for k in (
        "sparse_kernel_size", "sparse_kernel_stride", "sparse_block",
        "sparse_topk", "sparse_init_blocks", "sparse_window"))
    dt = h.dtype
    q = _rms((h @ w["wq"]).reshape(T, Hc, G, Dh), w["qn_g"], eps)
    k = _rms((h @ w["wk"]).reshape(T, Hc, Dh), w["kn_g"], eps)
    v = (h @ w["wv"]).reshape(T, Hc, Dh)
    scale = jnp.asarray(1.0 / np.sqrt(Dh), dt)
    J = max((T - size) // stride + 1, 0)
    lo_j = stride * np.arange(J)
    hi_j = lo_j + size - 1
    kc = jnp.mean(k[lo_j[:, None] + np.arange(size)[None]], axis=1) if J \
        else jnp.zeros((0, Hc, Dh), dt)
    NB = -(-T // block)
    first = block * np.arange(NB)
    # the compressed keys that overlap each block, listed (J = none: a
    # column of zeros behind p)
    overlap = (lo_j[None] <= first[:, None] + block - 1) & (
        hi_j[None] >= first[:, None])                             # [NB, J]
    width = max(int(overlap.sum(axis=1).max()) if J else 0, 1)
    listed = np.full((NB, width), J, np.int64)
    for b in range(NB):
        mine = np.nonzero(overlap[b])[0]
        listed[b, :len(mine)] = mine
    key_block = jnp.asarray(np.arange(T) // block)
    k_top = min(topk, NB)
    hinted = jnp.zeros((T,), bool)
    sets = jnp.zeros((T, Hc, NB), bool)
    if hint is not None:
        # (a row past the sequence names no position and is dropped)
        at, ids = (jnp.asarray(a) for a in hint)
        hinted = hinted.at[at].set(True, mode="drop")
        sets = sets.at[at[:, None, None], jnp.arange(Hc)[None, :, None],
                       jnp.clip(ids, 0, NB - 1)].max(ids >= 0, mode="drop")

    def rows(t0, qi, hinted, sets):
        t = t0 + jnp.arange(qi.shape[0])
        if J:
            s1 = jnp.einsum("qhgd,jhd->qhgj", qi, kc) * scale
            seen = jnp.asarray(hi_j)[None] <= t[:, None]         # [Q, J]
            s1 = jnp.where(seen[:, None, None], s1.astype(jnp.float32),
                           -jnp.inf)
            p = jnp.where(seen[:, None, None],
                          jax.nn.softmax(s1, axis=-1), 0.0)
            p = jnp.sum(jnp.where(jnp.isnan(p), 0.0, p), axis=2)  # [Q,Hc,J]
            score = jnp.max(jnp.pad(p, ((0, 0), (0, 0), (0, 1)))[
                :, :, listed], axis=-1)                           # [Q,Hc,NB]
        else:
            score = jnp.zeros((qi.shape[0], Hc, NB), jnp.float32)
        fb = jnp.asarray(first)
        forced = (jnp.arange(NB)[None] < init) | (
            fb[None] + block - 1 >= t[:, None] - (window - 1))   # [Q, NB]
        in_sight = fb[None] <= t[:, None]
        score = jnp.where(in_sight[:, None],
                          jnp.where(forced[:, None], jnp.inf, score), -1.0)
        order = jnp.argsort(-score, axis=-1, stable=True)         # [Q,Hc,NB]
        rank = jnp.argsort(order, axis=-1, stable=True)
        chosen = (rank < k_top) & in_sight[:, None]
        ranked = jnp.take_along_axis(score, order, axis=-1)
        # a hint is followed through a near-tie of THESE scores alone
        kth = ranked[..., k_top - 1:k_top]
        slack = jnp.asarray(margin, jnp.float32) * jnp.abs(
            jnp.where(jnp.isfinite(kth), kth, 0.0))
        near = (jnp.sum(sets, -1) == jnp.sum(chosen, -1)) & jnp.all(
            jnp.where(sets, in_sight[:, None] & (score >= kth - slack),
                      ~in_sight[:, None] | (~forced[:, None]
                                            & (score <= kth + slack))), -1)
        follow = hinted & jnp.all(near, axis=-1)                  # [Q]
        chosen = jnp.where(follow[:, None, None], sets, chosen)
        if NB > k_top:
            a, b = ranked[..., k_top - 1], ranked[..., k_top]
            gap = jnp.where((b >= 0) & jnp.isfinite(a),
                            (a - b) / jnp.maximum(a, 1e-30), NO_GAP)
            gap = jnp.min(gap, axis=-1)
        else:
            gap = jnp.full(t.shape, NO_GAP, jnp.float32)
        gap = jnp.where(hinted, jnp.where(follow, NO_GAP, UNFOLLOWED), gap)
        mask = chosen[:, :, key_block] & (
            jnp.arange(T)[None, None] <= t[:, None, None])       # [Q, Hc, T]
        s2 = jnp.einsum("qhgd,khd->qhgk", qi, k) * scale
        s2 = jnp.where(mask[:, :, None], s2, -jnp.inf)
        a = jnp.einsum("qhgk,khd->qhgd", jax.nn.softmax(s2, axis=-1), v)
        return a.reshape(qi.shape[0], H * Dh), gap.astype(jnp.float32)

    a, gap = _by_rows(rows, T, q, hinted, sets, live=live)
    return (a * jax.nn.sigmoid(h @ w["wg"])) @ w["wo"], gap, kc


def linear_attention(h, w, model):
    """A `linear_attention` layer's mixer on the normed input h [T, D], the
    recurrence position by position -> (result [T, D] before r, the state
    after the last position [Hs, P, N])."""
    T = h.shape[0]
    Hs, P, N = _sizes(model)[4:]
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    q = _rope(_rms((h @ w["wq"]).reshape(T, Hs, N), w["qn_g"], eps), theta)
    k = _rope(_rms((h @ w["wk"]).reshape(T, Hs, N), w["kn_g"], eps), theta)
    v = (h @ w["wv"]).reshape(T, Hs, P)
    q = q * jnp.asarray(1.0 / np.sqrt(N), h.dtype)
    decay = jnp.exp(jnp.asarray(model["linear_log_decay"],
                                jnp.float32)).astype(h.dtype)

    def step(S, at):
        q_t, k_t, v_t = at
        S = decay[:, None, None] * S + v_t[:, :, None] * k_t[:, None, :]
        return S, jnp.sum(S * q_t[:, None, :], axis=-1)

    S, o = jax.lax.scan(step, jnp.zeros((Hs, P, N), h.dtype), (q, k, v))
    o = _rms(o, w["on_g"].reshape(Hs, P), eps).reshape(T, Hs * P)
    return (o * jax.nn.sigmoid(h @ w["wg"])) @ w["wo"], S


def ffn(x, w, model, live=None):
    """x + r ffn(rms(x)) by blocks of rows (`live`: `_by_rows`'s)."""
    gate, down = (float(v) for v in (model.get("mlp_multipliers")
                                     or (1.0, 1.0)))
    eps = float(model["norm_eps"])

    def rows(_, xi):
        g = _rms(xi, w["ln2_g"], eps)
        return xi + (((g @ w["ffn_up"]) * jax.nn.silu(
            (g @ w["ffn_gate"]) * jnp.asarray(gate, xi.dtype)))
            @ w["ffn_down"]) * jnp.asarray(down, xi.dtype)

    return _by_rows(rows, x.shape[0], x, live=live)


def layer_states(x, w, model, i, hint=None, margin=0.0, live=None):
    """x [T, D] -> (x', gap [T], what a cache would hold of layer i after
    the last position: a sparse layer's compressed keys, a linear layer's
    state), computed in x's dtype; `w` the layer's weights under their bare
    names; `hint`, `margin`: `sparse_attention`'s; `live`: the sequence's
    length where padding follows it (`_by_rows`: x' and the gap read zeros
    from the first whole block of padding on)."""
    with jax.default_matmul_precision("highest"):
        r = jnp.asarray(float(model.get("attention_out_multiplier", 1.0)),
                        x.dtype)
        w = {n: v.astype(x.dtype) for n, v in w.items()}
        h = _rms(x, w["ln1_g"], float(model["norm_eps"]))
        if model["layer_types"][i] == "linear_attention":
            mixed, kept = linear_attention(h, w, model)
            gap = jnp.full(x.shape[:1], NO_GAP, jnp.float32)
        else:
            mixed, gap, kept = sparse_attention(h, w, model, hint, margin,
                                                live)
        return ffn(x + r * mixed, w, model, live), gap, kept


def layer(x, w, model, i, hint=None, margin=0.0, live=None):
    """x [T, D] -> (x', the selection's gap [T] float32)."""
    return layer_states(x, w, model, i, hint, margin, live)[:2]


def head(x, lnf_g, lm_head, model):
    """Logits in x's dtype, from the head whole or a block of its columns."""
    with jax.default_matmul_precision("highest"):
        return (_rms(x, lnf_g.astype(x.dtype), float(model["norm_eps"]))
                @ lm_head.astype(x.dtype)) * jnp.asarray(
            float(model.get("lm_head_multiplier", 1.0)), x.dtype)


def head_blocked(model, seed, x, dtype=jnp.float32):
    """`head` without the whole `lm_head` on the device.  x [n, D] ->
    logits [n, V] float32, on the host."""
    shapes = tensor_shapes(model)
    shape = shapes["lm_head"]
    lnf = draw_tensor("lnf_g", shapes["lnf_g"], seed, dtype, model)
    fn = jax.jit(lambda x, g, part: head(x, g, part, model))
    out = np.empty((x.shape[0], shape[1]), np.float32)
    for b, (lo, rows) in enumerate(vocab_blocks(shape[1])):
        part = draw_vocab_block("lm_head", shape, seed, model, b, dtype)
        out[:, lo:lo + rows] = np.asarray(fn(x, lnf, part), np.float32)
    return out


def forward(state, tokens, model, states=False):
    """tokens [T] int32 -> (logits [T, vocab], gaps [T, n_layers]); with
    `states` also the list of what each layer's cache would hold after the
    LAST position (`layer_states`).  logits[t] predicts token t + 1.
    `state` is the artifact's weight dict (widened here), `model` its
    meta."""
    x = (state["embed"][tokens].astype(jnp.float32)
         * float(model.get("embedding_multiplier", 1.0)))
    gaps, kept = [], []
    for i in range(int(model["n_layers"])):
        x, gap, left = layer_states(
            x, {n: state["l%d_%s" % (i, n)]
                for n in layer_weight_names(model, i)}, model, i)
        gaps.append(gap)
        kept.append(left)
    out = (head(x, state["lnf_g"], state["lm_head"], model),
           jnp.stack(gaps, axis=1))
    return out + ((kept,) if states else ())
