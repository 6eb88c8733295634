"""Plain reference of the `lfm2_24b_a2b` configuration: the LFM2-MoE decoder
(HF `LiquidAI/LFM2-24B-A2B` config.json, `model_type` lfm2_moe: hidden 2048;
`layer_types` a layer, "conv" or "full_attention"; 32 query heads over 8 K/V
heads of 64; `conv_L_cache` 3, no conv bias; the first `num_dense_layers`
layers a dense SwiGLU of `intermediate_size` 11776, the others 64 experts of
`moe_intermediate_size` 1536, 4 a token, `use_expert_bias`, `norm_topk_prob`,
`routed_scaling_factor` 1; `norm_eps` 1e-5; rope theta 1e6, default type;
vocabulary 65536) - the FULL forward to logits over a whole sequence, float32
`jax.numpy` at "highest" matmul precision.  No cache of any kind, no kernel,
no bucket, no sort and no gather of experts: EVERY expert is computed for
EVERY token and weighted by the top-k mask.  The serving driver holds prefill
plus decode through both kinds of slot state to this.

As published (the `lfm2_moe` modelling code the config names), layer i, with
h = RMSNorm(x; operator_norm):

  conv (Lfm2ShortConv):  B, C, u = split3(h W_in);  z_t = B_t * u_t;
      y_t = C_t * sum_{j<K} w[:, j] * z_{t-(K-1)+j}   (depthwise, causal,
      z = 0 before the start);  x += y W_out
  attention:  q = h Wq -> [32, 64], k, v = h Wk, h Wv -> [8, 64]; q and k
      RMS-normed PER HEAD (a gain of 64) before the rotary embedding (half-
      split, the whole head, position t from 0); causal softmax scaled by
      1/8; query head a reads K/V head a // 4;  x += o Wo
  FFN, g = RMSNorm(x; ffn_norm):  layers < num_dense_layers:
      x += (silu(g W1) * (g W3)) W2.   Others: s = sigmoid(g Wr);
      the 4 experts of the largest s + b_e;  w = s[those] / (their sum +
      1e-6), times routed_scaling_factor;  x += sum_e w_e * expert_e(g)

then the final RMSNorm (`embedding_norm`) and the head, which is the embedding
table itself (tied).

Computed in blocks so that it fits beside 10.8 GB of the program's weights:
attention one K/V head (its 4 query heads, a [4, T, T] score block under the
explicit [T, T] mask) at a time, the experts one at a time (each over ALL
tokens, times its column of the weights, zero where the token did not choose
it).  Blocks of a sum, not another formula.

Departures (each also in the configuration's `assumed`): the head is tied
(the family's releases tie it; the catalog's config is silent);
`routed_scaling_factor` 1 is a multiplication by one and is not spelled;
depth is cut (`reduced`), fp32 where the release is bf16, weights from a
seed.

Besides the logits, `layer` returns the gap between the 4th and the 5th
BIASED score at every position (+inf for a dense layer): where it is tiny the
program (whose other matmuls round to bf16) may rightly keep another expert.
Such a choice does not stay at its position here: a conv layer reads the two
positions before it.  So `layer_hinted` can be told what the other side
chose and follows it THROUGH A NEAR-TIE, and only there (`_routed_ffn`):
what it computes is still this file's mathematics with the reference's own
scores, and a choice the scores do not nearly tie on is not followed.

The state is a pure function of (seed, tensor name) (`draw_tensor`), so the
driver draws the reference's weights one layer at a time and never hands it
the predictor's arrays.  `draw_tensor` and `layer_weights` take a `dtype`,
and `layer` and `head` compute in the dtype of what they are given: handed
bfloat16 weights and a bfloat16 residual stream the whole forward is
bfloat16 (the precision below, which the driver's limit has to refuse).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln1_g", "ln2_g")
OPERATOR = {"conv": ("conv_in", "conv_w", "conv_out"),
            "attention": ("wq", "wk", "wv", "wo", "qn_g", "kn_g")}
FFN = {"dense": ("ffn_gate", "ffn_up", "ffn_down"),
       "routed": ("router", "expert_bias", "w_gate", "w_up", "w_down")}


def layer_names(model, i):
    """The bare names of layer i's weights: its two norms, its operator's
    (model `layer_types`), its FFN's (dense for i < `n_dense_layers`)."""
    return (NORMS + OPERATOR[model["layer_types"][i]]
            + FFN["dense" if i < int(model["n_dense_layers"]) else "routed"])


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta).  No `lm_head`: the head is the
    embedding table."""
    V, D, H = (int(model[k]) for k in ("vocab_size", "d_model", "n_heads"))
    Dh, K = D // H, int(model["conv_kernel"])
    kv = int(model["n_kv_heads"]) * Dh
    E, F = int(model["n_experts"]), int(model["expert_width"])
    Fd = int(model["dense_width"])
    one = {"ln1_g": (D,), "ln2_g": (D,),
           "conv_in": (D, 3 * D), "conv_w": (D, K), "conv_out": (D, D),
           "wq": (D, D), "wk": (D, kv), "wv": (D, kv), "wo": (D, D),
           "qn_g": (Dh,), "kn_g": (Dh,),
           "ffn_gate": (D, Fd), "ffn_up": (D, Fd), "ffn_down": (Fd, D),
           "router": (D, E), "expert_bias": (E,), "w_gate": (E, D, F),
           "w_up": (E, D, F), "w_down": (E, F, D)}
    shapes = {"embed": (V, D), "lnf_g": (D,)}
    for i in range(int(model["n_layers"])):
        shapes.update({"l%d_%s" % (i, n): one[n]
                       for n in layer_names(model, i)})
    return shapes


@jax.jit
def _seed_key(seed_u32):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)


def draw_tensor(name, shape, seed, dtype=jnp.float32):
    """One weight, on the device, from (seed, name) alone, drawn in float32
    and rounded to `dtype`: a norm gain is 1; the router's expert bias
    normal(0, 0.05) (a trained buffer in the release; zero would leave the
    selection by biased score untested); a matrix normal(0, 1/sqrt(fan_in)),
    the depthwise taps' fan-in being the taps, the tied table's its use as
    the head (the hidden size), which keeps the logits at std ~1."""
    if len(shape) == 1 and not name.endswith("expert_bias"):
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                             np.uint32(zlib.crc32(name.encode())))
    if len(shape) == 1:
        std = 0.05
    else:
        fan_in = shape[-1] if name.endswith(("conv_w", "embed")) \
            else shape[-2]
        std = 1.0 / np.sqrt(fan_in)
    return _normal(key, tuple(shape), float(std)).astype(dtype)


_normal = jax.jit(
    lambda key, shape, std: jax.random.normal(key, shape, jnp.float32) * std,
    static_argnums=(1, 2))


def layer_weights(model, seed, i, dtype=jnp.float32):
    """Layer i's weights under their bare names, drawn from the seed."""
    shapes = tensor_shapes(model)
    return {n: draw_tensor("l%d_%s" % (i, n), shapes["l%d_%s" % (i, n)],
                           seed, dtype) for n in layer_names(model, i)}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [T, H, Dh], position t = row index."""
    T, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]  # [T, half]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # [T, 1, dh]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def embed(table, tokens):
    return table[tokens]


def _short_conv(h, w):
    T, K = h.shape[0], w["conv_w"].shape[1]
    b, c, u = jnp.split(h @ w["conv_in"], 3, axis=-1)
    z = jnp.pad(b * u, ((K - 1, 0), (0, 0)))       # z = 0 before the start
    y = sum(w["conv_w"][:, j] * z[j:j + T] for j in range(K))
    return (c * y) @ w["conv_out"]


def _attention(h, w, model):
    T, D = h.shape
    H, Hkv = int(model["n_heads"]), int(model["n_kv_heads"])
    Dh, G = D // H, H // Hkv
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    q = _rope(_rms((h @ w["wq"]).reshape(T, H, Dh), w["qn_g"], eps), theta)
    k = _rope(_rms((h @ w["wk"]).reshape(T, Hkv, Dh), w["kn_g"], eps), theta)
    v = (h @ w["wv"]).reshape(T, Hkv, Dh)
    mask = jnp.tril(jnp.ones((T, T), bool))

    def one_kv_head(qkv):           # its G query heads: a // G == this head
        qg, kh, vh = qkv            # [T, G, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("qgd,kd->gqk", qg, kh) / jnp.sqrt(float(Dh))
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), vh)

    o = jax.lax.map(one_kv_head, (
        q.reshape(T, Hkv, G, Dh).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))     # [Hkv, T, G, Dh]
    return o.transpose(1, 0, 2, 3).reshape(T, D) @ w["wo"]


def _routed_ffn(g, w, model, hint=None, margin=0.0):
    """(sum over the token's experts, gap [T] between the 4th and 5th
    biased score, the experts used [T, 4] in ascending order, how far below
    the 4th biased score the least of a hinted position's experts lay [T]:
    0 where they are the top 4, +inf where nothing was hinted).

    `hint` [T, 4] int32 names, at some positions (a row of -1 = none), the
    experts ANOTHER computation of this model chose there.  The reference
    keeps its own top 4 unless every hinted expert lies within `margin` (a
    number, or one a position [T]) of its 4th biased score: a near-tie, which rounding may rightly decide
    the other way, is decided the hint's way, so that the two computations
    stay ONE function of the positions that follow."""
    k = int(model["experts_per_token"])
    s = jax.nn.sigmoid(g @ w["router"])                       # [T, E]
    biased = s + w["expert_bias"]
    top, top_i = jax.lax.top_k(biased, k + 1)
    used = top_i[:, :k]
    short = jnp.full(g.shape[:1], jnp.inf, jnp.float32)
    if hint is not None:
        hinted = hint[:, 0] >= 0
        theirs = jnp.take_along_axis(biased, jnp.maximum(hint, 0), axis=-1)
        short = jnp.where(hinted, (top[:, k - 1] - jnp.min(theirs, axis=-1))
                          .astype(jnp.float32), short)
        used = jnp.where((hinted & (short <= margin))[:, None], hint, used)
    keep = jnp.sum(jax.nn.one_hot(used, s.shape[1], dtype=s.dtype),
                   axis=1)                                    # [T, E]
    weight = s * keep
    if model.get("norm_topk_prob"):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + jnp.asarray(1e-6, s.dtype))

    def one_expert(acc, e):
        w_gate, w_up, w_down, col = e
        y = (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down   # every token
        return acc + col[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(g),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return (out, (top[:, k - 1] - top[:, k]).astype(jnp.float32),
            jnp.sort(used, axis=-1).astype(jnp.int32), short)


def layer_hinted(x, w, model, hint=None, margin=0.0):
    """x [T, D] -> (x', gap [T] float32, experts used [T, 4] int32, short
    [T] float32; the last two None for a dense layer): one decoder layer,
    computed in x's dtype; `w` the layer's weights under their bare names
    (`layer_names`), which say what kind of layer it is.  `hint`, `margin`
    and the last two results: `_routed_ffn`.  A conv layer hands a
    position's routing on to the positions after it, so a comparison with
    a computation that rounds otherwise (the program's other matmuls round
    to bf16) has to FOLLOW that computation through the router's near-ties,
    and through nothing else."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["norm_eps"])
        h = _rms(x, w["ln1_g"], eps)
        x = x + (_short_conv(h, w) if "conv_in" in w
                 else _attention(h, w, model))
        g = _rms(x, w["ln2_g"], eps)
        if "router" not in w:
            y = (jax.nn.silu(g @ w["ffn_gate"]) * (g @ w["ffn_up"])) \
                @ w["ffn_down"]
            return (x + y, jnp.full(x.shape[:1], jnp.inf, jnp.float32),
                    None, None)
        y, gap, used, short = _routed_ffn(g, w, model, hint, margin)
        return x + y, gap, used, short


def layer(x, w, model):
    """x [T, D] -> (x', gap [T] float32): `layer_hinted` with no hint (the
    interface `serve_decode_arch` drives)."""
    return layer_hinted(x, w, model)[:2]


def head(x, lnf_g, table, model):
    """Logits in x's dtype; `table` is the embedding table (tied head)."""
    with jax.default_matmul_precision("highest"):
        return _rms(x, lnf_g, float(model["norm_eps"])) @ table.T


def forward(state, tokens, model):
    """tokens [T] int32 -> (logits [T, vocab], gaps [T, n_layers]);
    logits[t] predicts token t + 1.  `state` is the artifact's weight dict,
    `model` its meta."""
    x = embed(state["embed"], tokens)
    gaps = []
    for i in range(int(model["n_layers"])):
        x, g = layer(x, {n: state["l%d_%s" % (i, n)]
                         for n in layer_names(model, i)}, model)
        gaps.append(g)
    return (head(x, state["lnf_g"], state["embed"], model),
            jnp.stack(gaps, axis=1))
