"""Plain reference of the `falcon_h1_34b` configuration: the Falcon-H1
decoder (HF `tiiuae/Falcon-H1-34B-Instruct` config.json, `model_type`
falcon_h1: hidden 5120; in EVERY layer grouped-query attention (20 query
heads over 4 K/V heads of `head_dim` 128, which is not hidden / heads; rope
theta 1e11, no biases) IN PARALLEL with a Mamba-2 (SSD) mixer (`mamba_d_ssm`
4096 = 32 heads of 128, `mamba_n_groups` 2, `mamba_d_state` 256,
`mamba_d_conv` 4 with bias, `mamba_rms_norm`, `mamba_norm_before_gate`
false), both on the same normed input and summed into one residual; a dense
gated FFN of 21504 (SiLU) in every layer; ten fixed muP multipliers;
`rms_norm_eps` 1e-5; untied head over 261120 rows) - the FULL forward to
logits over a whole sequence, float32 `jax.numpy` at "highest" matmul
precision.  The state-space recurrence is SEQUENTIAL, position by position
(`lax.scan` over positions): no chunks, no cache, no kernel, no bucket, no
batching.

The layer, x [T, D] (position t = row t):

    h    = rms(x; ln1_g)
    # attention
    q, k, v = (h * attention_in_multiplier) wq, wk, wv -> [H | Hkv | Hkv, Dh]
    k    = k * key_multiplier;  q, k = rope(q), rope(k)   half-split, whole head
    a    = softmax_causal(q k^T / sqrt(Dh)) v       query head i reads K/V
                                                    head i // (H / Hkv)
    att  = (a wo) * attention_out_multiplier
    # state-space mixer, on the SAME h
    p    = ((h * ssm_in_multiplier) ssm_in) * mup_vector
           mup_vector = ssm_multipliers over [z d_ssm | x d_ssm | B G N | C G N | dt Hs]
    z, xBC, dt = split(p, [d_ssm, d_ssm + 2 G N, Hs])
    xBC  = silu(conv(xBC; ssm_conv_w [C, K]) + ssm_conv_b)   causal, depthwise,
                                                    zeros before the start
    xs, B, C = split(xBC) -> [Hs, P], [G, N], [G, N]
    dt   = softplus(dt + ssm_dt_bias) [Hs];   A = -exp(ssm_A_log) [Hs]
    S_t  = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t      [Hs, P, N], S_{-1} = 0,
                                                    head j reads group j // (Hs / G)
    y_t  = S_t . C_t + ssm_D xs_t                           [Hs, P]
    y    = rms(y * silu(z)) by group of d_ssm / G, times ssm_norm_g   (the gate
                                                    BEFORE the norm)
    ssm  = (y ssm_out) * ssm_out_multiplier
    x    = x + att + ssm
    g    = rms(x; ln2_g)
    x    = x + (((g ffn_up) * silu((g ffn_gate) * mlp_multipliers[0])) ffn_down)
               * mlp_multipliers[1]
    logits = (rms(x; lnf_g) lm_head) * lm_head_multiplier,
    x_0  = embed[token] * embedding_multiplier

Readings of what the config is silent on (each also in the configuration's
`assumed`), by the family's modelling code: no limits on dt beyond softplus
(0, inf); the gated norm's group count is `mamba_n_groups`; the conv's state
is its PRE-activation inputs; `ssm_A_log`, `ssm_D`, `ssm_dt_bias` are trained
parameters, here seeded.

THE WEIGHTS are a pure function of (seed, tensor name).  Every matmul weight
is a BFLOAT16 NUMBER (drawn in float32, rounded once): the release is
bfloat16 and the program keeps them so at rest (`weight_dtype`); gains, the
SSM's vectors and its depthwise taps are float32 (`at_rest`).  THE SCALES
(`weight_std`): the published multipliers are small (0.0375, 0.088, 0.0078),
so with every matrix at normal(0, 1/sqrt(fan_in)) a branch's fault would
drown in the residual stream.  Each matrix is therefore drawn at
gain / sqrt(fan_in) with the gain the reciprocal of the multipliers that
scale its product (times the O(1) factors of `QK_GAIN`, `ATT_OUT_GAIN`), so
that WITH the multipliers q, v, the conv's x segment, the FFN's gate and up
have std ~1, the softmax's scores std ~1.5, each branch moves the residual
stream by the order of what it holds and the logits have std ~1.  The SSM's
vectors follow the Mamba-2 initialisation: dt_bias the inverse softplus of
exp(uniform(log 1e-3, log 1e-1)), A_log = log(uniform(1, 16)), so dt |A|
spreads over ~[3e-4, 6] and a head's state forgets within one position or
remembers over thousands (all-forget or all-keep would leave the recurrence
untested within 640 positions); D = 1 + 0.25 normal; conv bias 0.2 normal.

THE VOCABULARY is drawn in BLOCKS of `VOCAB_BLOCK` rows (`vocab_blocks`,
`draw_vocab_block`): `embed` [V, D] and `lm_head` [D, V] are 5.35 GB each in
float32, so the whole table is the concatenation of blocks that each have a
key of their own, and a caller that has no room for it (the driver's check
on the chip, beside 8.8 GB of the program's weights) draws a block, uses it
and drops it (`embed_tokens`, `head_blocked`).  `draw_tensor` gives the same
table whole.

THE PRECISION BELOW.  `layer` and `head` compute in the dtype of what they
are given: handed a bfloat16 residual stream and
`layer_weights(dtype=bfloat16)`, every tensor of the forward, the scanned
state among them, is bfloat16.

Besides the logits, `layer` returns a "router gap" a position for the
driver's interface: this stack has no router, so no near-tie (`NO_ROUTER`).
`forward(..., states=True)` also returns what a cache would hold after the
LAST position: each layer's conv window and scanned state (the CPU tests
hold the program's slot tables to them).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LAYER_WEIGHTS = ("ln1_g", "ln2_g", "wq", "wk", "wv", "wo", "ssm_in",
                 "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_A_log",
                 "ssm_D", "ssm_norm_g", "ssm_out", "ffn_gate", "ffn_up",
                 "ffn_down")
# no router anywhere: no position is a near-tie (finite: logs are JSON)
NO_ROUTER = 1e9
# rows of the vocabulary drawn at a time (261,120 = 32 x 8,160)
VOCAB_BLOCK = 8160
# std of the softmax's scores (q std 1, so k's after its multiplier), and
# what makes up for the averaging of v by the softmax in wo's product
QK_GAIN, ATT_OUT_GAIN = 1.5, 2.0


def _sizes(model):
    D, H = int(model["d_model"]), int(model["n_heads"])
    Dh = int(model.get("head_dim") or D // H)
    Hkv = int(model.get("n_kv_heads") or H)
    Hs, P, N, G = (int(model[k]) for k in ("ssm_heads", "ssm_head_dim",
                                           "ssm_state", "ssm_groups"))
    d_ssm = Hs * P
    return D, H, Hkv, Dh, Hs, P, N, G, d_ssm, d_ssm + 2 * G * N


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta)."""
    D, H, Hkv, Dh, Hs, P, N, G, d_ssm, conv = _sizes(model)
    V, F = int(model["vocab_size"]), int(model["dense_width"])
    K = int(model["ssm_conv_kernel"])
    one = {"ln1_g": (D,), "ln2_g": (D,), "wq": (D, H * Dh),
           "wk": (D, Hkv * Dh), "wv": (D, Hkv * Dh), "wo": (H * Dh, D),
           "ssm_in": (D, d_ssm + conv + Hs), "ssm_conv_w": (conv, K),
           "ssm_conv_b": (conv,), "ssm_dt_bias": (Hs,), "ssm_A_log": (Hs,),
           "ssm_D": (Hs,), "ssm_norm_g": (d_ssm,), "ssm_out": (d_ssm, D),
           "ffn_gate": (D, F), "ffn_up": (D, F), "ffn_down": (F, D)}
    shapes = {"embed": (V, D), "lnf_g": (D,), "lm_head": (D, V)}
    for i in range(int(model["n_layers"])):
        shapes.update({"l%d_%s" % (i, n): one[n] for n in LAYER_WEIGHTS})
    return shapes


def _bare(name):
    return name.split("_", 1)[1] if name[:1] == "l" and name[1].isdigit() \
        else name


def weight_std(name, shape, model):
    """The std a MATRIX is drawn at (the module's docstring): gain /
    sqrt(fan_in), the gain undoing the multipliers on its product."""
    m = {k: float(model.get(k, 1.0)) for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "key_multiplier",
        "attention_out_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier")}
    ssm_x = float((model.get("ssm_multipliers") or [1.0] * 5)[1])
    gate, down = (float(v) for v in (model.get("mlp_multipliers")
                                     or (1.0, 1.0)))
    bare = _bare(name)
    if bare == "embed":
        return 1.0 / m["embedding_multiplier"]
    if bare == "ssm_conv_w":
        return 1.0 / np.sqrt(shape[-1])
    gain = {"lm_head": 1.0 / m["lm_head_multiplier"],
            "wq": 1.0 / m["attention_in_multiplier"],
            "wk": QK_GAIN / (m["attention_in_multiplier"]
                             * m["key_multiplier"]),
            "wv": 1.0 / m["attention_in_multiplier"],
            "wo": ATT_OUT_GAIN / m["attention_out_multiplier"],
            "ssm_in": 1.0 / (m["ssm_in_multiplier"] * ssm_x),
            "ssm_out": 1.0 / m["ssm_out_multiplier"],
            "ffn_gate": 1.0 / gate, "ffn_up": 1.0,
            "ffn_down": 1.0 / down}[bare]
    return gain / np.sqrt(shape[-2])


def at_rest(name, shape):
    """The dtype the artifact keeps a tensor in: bfloat16 for a matmul
    weight; float32 for a gain, the SSM's vectors and its depthwise taps
    ([channels, taps]: no matmul's operand)."""
    return jnp.float32 if len(shape) == 1 or name.endswith("ssm_conv_w") \
        else jnp.bfloat16


@jax.jit
def _seed_key(seed_u32):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)


def _key(name, seed):
    return jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                              np.uint32(zlib.crc32(name.encode())))


_normal = jax.jit(
    lambda key, shape, std: jax.random.normal(key, shape, jnp.float32) * std,
    static_argnums=(1, 2))
_uniform = jax.jit(
    lambda key, shape, lo, hi: jax.random.uniform(key, shape, jnp.float32,
                                                  lo, hi),
    static_argnums=(1, 2, 3))


def _vector(name, shape, seed):
    bare = _bare(name)
    if bare.endswith("_g"):
        return jnp.ones(shape, jnp.float32)
    key = _key(name, seed)
    if bare == "ssm_dt_bias":
        dt = jnp.exp(_uniform(key, tuple(shape), float(np.log(1e-3)),
                              float(np.log(1e-1))))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    if bare == "ssm_A_log":
        return jnp.log(_uniform(key, tuple(shape), 1.0, 16.0))
    if bare == "ssm_D":
        return 1.0 + _normal(key, tuple(shape), 0.25)
    if bare == "ssm_conv_b":
        return _normal(key, tuple(shape), 0.2)
    raise KeyError(name)


def vocab_blocks(V):
    """[(first row, rows)] of the blocks the vocabulary is drawn in."""
    return [(lo, min(VOCAB_BLOCK, V - lo)) for lo in range(0, V, VOCAB_BLOCK)]


def draw_vocab_block(name, shape, seed, model, b, dtype=None):
    """Block `b` of `embed` ([rows, D]) or of `lm_head` ([D, rows]): rows
    `vocab_blocks(V)[b]` of the vocabulary, from a key of its own."""
    V = shape[0] if name == "embed" else shape[1]
    _, rows = vocab_blocks(V)[b]
    part = (rows, shape[1]) if name == "embed" else (shape[0], rows)
    key = jax.random.fold_in(_key(name, seed), np.uint32(b))
    rest = at_rest(name, shape)
    return _normal(key, part, float(weight_std(name, shape, model))).astype(
        rest).astype(dtype or rest)


def draw_tensor(name, shape, seed, dtype=None, model=None):
    """One weight, on the device, from (seed, name) alone, rounded to the
    dtype it has at rest (`at_rest`) and given in `dtype` (None: as it is at
    rest).  `model`: the meta, whose multipliers set a matrix's scale
    (`weight_std`; a vector needs none).  The vocabulary's two tables are
    their blocks side by side."""
    rest = at_rest(name, shape)
    if len(shape) == 1:
        return _vector(name, shape, seed).astype(dtype or rest)
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
                           seed, dtype, model) for n in LAYER_WEIGHTS}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [T, heads, Dh], position t = row index, half-split."""
    T, _, d = x.shape
    half = d // 2
    inv = jnp.float32(theta) ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                                 / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mult(model, key):
    return float(model.get(key, 1.0))


def embed(table, tokens, model):
    """x_0 [T, D] from the whole table (times `embedding_multiplier`)."""
    x = table[tokens]
    return x * jnp.asarray(_mult(model, "embedding_multiplier"), x.dtype)


def embed_tokens(model, seed, tokens, dtype=jnp.float32):
    """`embed` without the whole table on the device: the table's blocks
    drawn one at a time, each giving the rows of the tokens that lie in it.
    tokens [n] int -> [n, D]."""
    tokens = np.asarray(tokens)
    shape = tensor_shapes(model)["embed"]
    out = jnp.zeros((len(tokens), shape[1]), dtype)
    for b, (lo, rows) in enumerate(vocab_blocks(shape[0])):
        mine = np.nonzero((tokens >= lo) & (tokens < lo + rows))[0]
        if len(mine):
            block = draw_vocab_block("embed", shape, seed, model, b, dtype)
            out = out.at[mine].set(block[tokens[mine] - lo])
    return out * jnp.asarray(_mult(model, "embedding_multiplier"), dtype)


def attention(h, w, model):
    """Grouped-query causal attention of the normed input h [T, D]."""
    T = h.shape[0]
    _, H, Hkv, Dh = _sizes(model)[:4]
    theta = float(model["rope_theta"])
    hin = h * jnp.asarray(_mult(model, "attention_in_multiplier"), h.dtype)
    q = (hin @ w["wq"]).reshape(T, H, Dh)
    k = (hin @ w["wk"]).reshape(T, Hkv, Dh) \
        * jnp.asarray(_mult(model, "key_multiplier"), h.dtype)
    v = (hin @ w["wv"]).reshape(T, Hkv, Dh)
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(Dh)).astype(
        h.dtype)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return (a.reshape(T, H * Dh) @ w["wo"]) * jnp.asarray(
        _mult(model, "attention_out_multiplier"), h.dtype)


def ssm(h, w, model):
    """The state-space mixer of the normed input h [T, D], the recurrence
    position by position -> (result [T, D], the conv's last K - 1
    PRE-activation inputs [K - 1, C], the state after the last position
    [Hs, P, N])."""
    T = h.shape[0]
    D, _, _, _, Hs, P, N, G, d_ssm, conv = _sizes(model)
    K, k = int(model["ssm_conv_kernel"]), Hs // G
    mup = np.repeat(np.float32(model.get("ssm_multipliers") or [1.0] * 5),
                    [d_ssm, d_ssm, G * N, G * N, Hs])
    p = ((h * jnp.asarray(_mult(model, "ssm_in_multiplier"), h.dtype))
         @ w["ssm_in"]) * jnp.asarray(mup, h.dtype)
    z, xBC, dt = p[:, :d_ssm], p[:, d_ssm:d_ssm + conv], p[:, d_ssm + conv:]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv), h.dtype), xBC])
    window = padded[T:]                                 # the last K - 1
    xBC = jax.nn.silu(sum(w["ssm_conv_w"][:, j] * padded[j:j + T]
                          for j in range(K)) + w["ssm_conv_b"])
    xs = xBC[:, :d_ssm].reshape(T, Hs, P)
    Bm = xBC[:, d_ssm:d_ssm + G * N].reshape(T, G, N)
    Cm = xBC[:, d_ssm + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + w["ssm_dt_bias"])
    A = -jnp.exp(w["ssm_A_log"])

    def step(S, at):
        x_t, B_t, C_t, dt_t = at
        B_h, C_h = (jnp.repeat(t, k, axis=0)[:, None, :] for t in (B_t, C_t))
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_h
        return S, jnp.sum(S * C_h, axis=-1) + w["ssm_D"][:, None] * x_t

    S, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), h.dtype),
                        (xs, Bm, Cm, dt))
    y = (y.reshape(T, d_ssm) * jax.nn.silu(z)).reshape(T, G, d_ssm // G)
    y = (y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                           + jnp.asarray(float(model["norm_eps"]), h.dtype))
         ).reshape(T, d_ssm) * w["ssm_norm_g"]
    return (y @ w["ssm_out"]) * jnp.asarray(
        _mult(model, "ssm_out_multiplier"), h.dtype), window, S


def ffn(g, w, model):
    gate, down = (float(v) for v in (model.get("mlp_multipliers")
                                     or (1.0, 1.0)))
    return (((g @ w["ffn_up"]) * jax.nn.silu(
        (g @ w["ffn_gate"]) * jnp.asarray(gate, g.dtype))) @ w["ffn_down"]) \
        * jnp.asarray(down, g.dtype)


def layer_states(x, w, model):
    """x [T, D] -> (x', conv window, scanned state): one decoder layer,
    computed in x's dtype; `w` the layer's weights under their bare
    names."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["norm_eps"])
        w = {n: v.astype(x.dtype) for n, v in w.items()}
        h = _rms(x, w["ln1_g"], eps)
        mixed, window, S = ssm(h, w, model)
        x = x + attention(h, w, model) + mixed
        return x + ffn(_rms(x, w["ln2_g"], eps), w, model), window, S


def layer(x, w, model):
    """x [T, D] -> (x', "router gap" [T] float32: `NO_ROUTER`
    everywhere)."""
    return (layer_states(x, w, model)[0],
            jnp.full(x.shape[:1], NO_ROUTER, jnp.float32))


def head(x, lnf_g, lm_head, model):
    """Logits in x's dtype, from the head whole or from a block of its
    columns."""
    with jax.default_matmul_precision("highest"):
        return (_rms(x, lnf_g.astype(x.dtype), float(model["norm_eps"]))
                @ lm_head.astype(x.dtype)) * jnp.asarray(
            _mult(model, "lm_head_multiplier"), x.dtype)


def head_blocked(model, seed, x, dtype=jnp.float32):
    """`head` without the whole `lm_head` on the device: its blocks drawn
    one at a time.  x [n, D] -> logits [n, V] float32, on the host."""
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
    `states` also ([n_layers, K - 1, C] conv windows, [n_layers, Hs, P, N]
    scanned states) after the LAST position.  logits[t] predicts token
    t + 1.  `state` is the artifact's weight dict (in whatever dtype it is
    kept: widened here), `model` its meta."""
    x = embed(state["embed"], tokens, model).astype(jnp.float32)
    windows, scanned = [], []
    for i in range(int(model["n_layers"])):
        x, window, S = layer_states(
            x, {n: state["l%d_%s" % (i, n)] for n in LAYER_WEIGHTS}, model)
        windows.append(window)
        scanned.append(S)
    out = (head(x, state["lnf_g"], state["lm_head"], model),
           jnp.full((x.shape[0], len(windows)), NO_ROUTER, jnp.float32))
    return out + ((jnp.stack(windows), jnp.stack(scanned)) if states
                  else ())
