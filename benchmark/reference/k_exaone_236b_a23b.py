"""Plain reference of the `k_exaone_236b_a23b` configuration: the K-EXAONE
decoder (HF `LGAI-EXAONE/K-EXAONE-236B-A23B` config.json, `model_type`
exaone_moe: hidden 6144; 64 query heads over 8 K/V heads of 128; layers in
the pattern LLLG, three that attend over the last `sliding_window` 128
positions to one that attends over all; RoPE theta 1e6; layer 0's FFN a dense
SwiGLU of 18432, then 128 routed experts of 2048, 8 a token by sigmoid score
+ a per-expert selection bias, weights renormalised and scaled by 2.5, beside
one shared expert; `rms_norm_eps` 1e-5; untied head) - the FULL forward to
logits over a whole sequence, float32 `jax.numpy` at "highest" matmul
precision.  Whole [T, T] masks, no ring, no cache, no kernel, no bucket, no
blocks of queries, no sort and no gather of experts: every HELD expert is
computed for every token and weighted by the top-k mask.  (The scores are
taken one K/V head's group of 8 query heads at a time, `lax.map`: [64, 3072,
3072] float32 is 2.4 GB beside the program's 8.6; the mathematics is a
head's own.)

The layer, x [T, D]:

    h      = rms(x; ln1_g)
    q,k,v  = h wq -> [64, 128],  h wk -> [8, 128],  h wv -> [8, 128]
    q, k   = rms over each head's 128 (gains qn_g, kn_g)
    WINDOW layer:  q, k = rope(., t)   theta 1e6, whole head, half-split
    FULL layer:    q, k unrotated      (no position signal)
    a_t    = sum_j softmax_j(q_t . k_j / sqrt(128)) v_j
             window layer: j in (t - 128, t]   (128 keys, the token's own)
             full layer:   j in [0, t];  query head a reads K/V head a // 8
    x      = x + a wo
    g      = rms(x; ln2_g)
    dense (layer 0):  f = (silu(g ffn_gate) * (g ffn_up)) ffn_down
    routed:  s = sigmoid(g router) [E];  idx = top-8(s + expert_bias)
             w = 2.5 * s[idx] / (sum s[idx] + 1e-20)
             f = sum_{e in idx, e held here} w_e SwiGLU_e(g) + SwiGLU_shared(g)
    x      = x + f
    logits = rms(x; lnf_g) lm_head

WHICH KIND a layer is rides its weights: `layer_weights` gives a window
layer one more entry, `"window"` (an int32 scalar, the meta's
`sliding_window`), and `layer` masks and rotates where it finds it.  (The
benchmark's driver calls `layer(x, w, model)` without the layer's index.)

Readings of the catalog's config, listed as `assumed` in the configuration
file: one RMSNorm in FRONT of each sublayer; per-head qk-norm BEFORE the
rotation; rotary on the sliding layers only; the selection bias, seeded,
used to select and never to weigh; the window's edge (128 keys, the token's
own among them).

THE CHIP'S SHARE (the `model-configs` guide, section 4): `experts_held` =
(first, count), as in openpangu_ultra_moe_718b.py; `ffn_parts` returns the
routed and the shared part apart, so that a test can add the shares up.

THE WEIGHTS are a pure function of (seed, tensor name), every matmul weight
a BFLOAT16 NUMBER (drawn in float32, rounded once), the router's matrix and
the gains float32, the selection bias float32 normal(0, 0.05): as in
openpangu_ultra_moe_718b.py and lfm2_24b_a2b.py.

THE PRECISION BELOW: `layer` and `head` compute in the dtype of what they are
given; handed a bfloat16 residual stream and `layer_weights(dtype=bfloat16)`,
every tensor of the forward is bfloat16.

Besides the logits `forward` returns, a position and routed layer, the gap
between the 8th and the 9th BIASED router score (`DENSE_GAP` for a dense
layer): the quantity the selection is made on.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION_WEIGHTS = ("ln1_g", "ln2_g", "wq", "wk", "wv", "wo", "qn_g", "kn_g")
DENSE_WEIGHTS = ("ffn_gate", "ffn_up", "ffn_down")
ROUTED_WEIGHTS = ("router", "expert_bias", "w_gate", "w_up", "w_down",
                  "shared_gate", "shared_up", "shared_down")
# a dense layer routes nothing: no near-tie there (finite: logs are JSON)
DENSE_GAP = 1e9
BIAS_STD = 0.05


def layer_names(model, i):
    return ATTENTION_WEIGHTS + (
        DENSE_WEIGHTS if i < int(model["n_dense_layers"]) else ROUTED_WEIGHTS)


def is_window(model, i):
    return model["layer_types"][i] == "window_attention"


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta)."""
    V, D, L, H = (int(model[k]) for k in ("vocab_size", "d_model",
                                          "n_layers", "n_heads"))
    Hkv, Dh = int(model["n_kv_heads"]), int(model["head_dim"])
    E, F = int(model["n_experts"]), int(model["expert_width"])
    held = int(model["experts_held"][1]) if model.get("experts_held") else E
    Fd, Fs = int(model["dense_width"]), int(model["n_shared_experts"]) * F
    every = {"ln1_g": (D,), "ln2_g": (D,), "wq": (D, H * Dh),
             "wk": (D, Hkv * Dh), "wv": (D, Hkv * Dh), "wo": (H * Dh, D),
             "qn_g": (Dh,), "kn_g": (Dh,),
             "ffn_gate": (D, Fd), "ffn_up": (D, Fd), "ffn_down": (Fd, D),
             "router": (D, E), "expert_bias": (E,),
             "w_gate": (held, D, F), "w_up": (held, D, F),
             "w_down": (held, F, D), "shared_gate": (D, Fs),
             "shared_up": (D, Fs), "shared_down": (Fs, D)}
    shapes = {"embed": (V, D), "lnf_g": (D,), "lm_head": (D, V)}
    for i in range(L):
        shapes.update({"l%d_%s" % (i, n): every[n]
                       for n in layer_names(model, i)})
    return shapes


@jax.jit
def _seed_key(seed_u32):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)


_normal = jax.jit(
    lambda key, shape, std: jax.random.normal(key, shape, jnp.float32) * std,
    static_argnums=(1, 2))


def at_rest(name, shape):
    """The dtype the artifact keeps a tensor in: bfloat16 for a matmul
    weight, float32 for a gain, the selection bias and the router's matrix
    (read at "highest" by the program)."""
    return jnp.float32 if len(shape) == 1 or name.endswith("_router") \
        else jnp.bfloat16


def draw_tensor(name, shape, seed, dtype=None):
    """One weight, on the device, from (seed, name) alone: a norm gain is 1,
    the selection bias normal(0, 0.05) (zero would make the selection by
    biased score the selection by score), a matrix normal(0, 1/sqrt(fan_in))
    drawn in float32 and rounded to the dtype it has at rest (`at_rest`),
    then given in `dtype` (None: as it is at rest)."""
    rest = at_rest(name, shape)
    if len(shape) == 1 and not name.endswith("_expert_bias"):
        return jnp.ones(shape, dtype or rest)
    key = jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                             np.uint32(zlib.crc32(name.encode())))
    std = BIAS_STD if len(shape) == 1 else float(1.0 / np.sqrt(shape[-2]))
    return _normal(key, tuple(shape), std).astype(rest).astype(dtype or rest)


def make_state_on_device(model, seed, names=None, dtype=jnp.float32):
    """{name: weight} for `names` (default: every tensor of the model)."""
    shapes = tensor_shapes(model)
    return {n: draw_tensor(n, shapes[n], seed, dtype)
            for n in (shapes if names is None else names)}


def layer_weights(model, seed, i, dtype=jnp.float32):
    """Layer i's weights under their bare names, drawn from the seed; a
    window layer's with its `"window"` (int32, the positions it sees)."""
    names = layer_names(model, i)
    st = make_state_on_device(model, seed, ["l%d_%s" % (i, n) for n in names],
                              dtype)
    return marked(model, i, {n: st["l%d_%s" % (i, n)] for n in names})


def marked(model, i, w):
    """Layer i's weights `w`, a window layer's with its `"window"`."""
    if is_window(model, i):
        w["window"] = jnp.int32(int(model["sliding_window"]))
    return w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [T, heads, lanes], position t = row index, half-split."""
    T, _, d = x.shape
    half = d // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]  # [T, half]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(g, gate, up, down):
    return (jax.nn.silu(g @ gate) * (g @ up)) @ down


def embed(table, tokens):
    return table[tokens]


def attention(h, w, model):
    """The attention of the normed input h [T, D]: a window layer's if `w`
    holds `"window"`, a full layer's otherwise."""
    T = h.shape[0]
    H, Hkv, Dh = (int(model[k]) for k in ("n_heads", "n_kv_heads",
                                          "head_dim"))
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    q = _rms((h @ w["wq"]).reshape(T, H, Dh), w["qn_g"], eps)
    k = _rms((h @ w["wk"]).reshape(T, Hkv, Dh), w["kn_g"], eps)
    v = (h @ w["wv"]).reshape(T, Hkv, Dh)
    t = jnp.arange(T)
    mask = t[None, :] <= t[:, None]                     # [query, key]
    if "window" in w:
        q, k = _rope(q, theta), _rope(k, theta)
        mask = mask & (t[None, :] > t[:, None] - w["window"])

    def group(qkv):
        # one K/V head and the G query heads that read it
        qg, kg, vg = qkv                    # [T, G, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("qgd,kd->gqk", qg, kg) \
            / jnp.sqrt(float(Dh)).astype(h.dtype)
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), vg)

    a = jax.lax.map(group, (q.reshape(T, Hkv, H // Hkv, Dh).swapaxes(0, 1),
                            k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return a.swapaxes(0, 1).reshape(T, H * Dh) @ w["wo"]


def ffn_parts(g, w, model):
    """(routed part, shared part, gap) of a routed layer's FFN on the normed
    input g [T, D]: the held experts' weighted sum, the shared expert's
    result, and the gap between the k-th and the (k+1)-th BIASED score."""
    k = int(model["experts_per_token"])
    E = int(model["n_experts"])
    first, count = model.get("experts_held") or (0, E)
    s = jax.nn.sigmoid(g @ w["router"])                         # [T, E]
    top, top_i = jax.lax.top_k(s + w["expert_bias"], k + 1)
    keep = jnp.sum(jax.nn.one_hot(top_i[:, :k], E, dtype=s.dtype), axis=1)
    weight = s * keep                       # the UNBIASED scores weigh
    if model.get("norm_topk_prob"):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + jnp.asarray(1e-20, s.dtype))
    weight = (weight * jnp.asarray(float(model["routed_scaling"]), s.dtype)
              )[:, int(first):int(first) + int(count)]
    act = jax.nn.silu(jnp.einsum("td,edf->tef", g, w["w_gate"])) \
        * jnp.einsum("td,edf->tef", g, w["w_up"])
    routed = jnp.einsum("tef,efd->td", act * weight[:, :, None], w["w_down"])
    shared = _swiglu(g, w["shared_gate"], w["shared_up"], w["shared_down"])
    return routed, shared, (top[:, k - 1] - top[:, k]).astype(jnp.float32)


def layer(x, w, model):
    """x [T, D] -> (x', gap [T] float32): one decoder layer, computed in x's
    dtype; `w` the layer's weights under their bare names (`layer_names`), a
    window layer's with `"window"`, a dense layer's if it has no router."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["norm_eps"])
        w = {n: v if n == "window" else v.astype(x.dtype)
             for n, v in w.items()}
        x = x + attention(_rms(x, w["ln1_g"], eps), w, model)
        g = _rms(x, w["ln2_g"], eps)
        if "router" in w:
            routed, shared, gap = ffn_parts(g, w, model)
            return x + routed + shared, gap
        return (x + _swiglu(g, w["ffn_gate"], w["ffn_up"], w["ffn_down"]),
                jnp.full(x.shape[:1], DENSE_GAP, jnp.float32))


def head(x, lnf_g, lm_head, model):
    """Logits in x's dtype."""
    with jax.default_matmul_precision("highest"):
        return _rms(x, lnf_g.astype(x.dtype), float(model["norm_eps"])) \
            @ lm_head.astype(x.dtype)


def forward(state, tokens, model):
    """tokens [T] int32 -> (logits [T, vocab], gaps [T, n_layers]);
    logits[t] predicts token t + 1.  `state` is the artifact's weight dict
    (in whatever dtype it is kept: widened here), `model` its meta."""
    x = embed(state["embed"], tokens).astype(jnp.float32)
    gaps = []
    for i in range(int(model["n_layers"])):
        x, g = layer(x, marked(model, i, {
            n: state["l%d_%s" % (i, n)] for n in layer_names(model, i)}),
            model)
        gaps.append(g)
    return (head(x, state["lnf_g"], state["lm_head"], model),
            jnp.stack(gaps, axis=1))
