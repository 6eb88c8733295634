"""Plain reference of the `olmoe_1b_7b` configuration: the OLMoE decoder
(HF `allenai/OLMoE-1B-7B-0125-Instruct` config.json, `model_type` olmoe:
hidden 2048, 16 attention heads = 16 KV heads of 128, 64 experts of width
1024, 8 per token, `norm_topk_prob` false, SiLU-gated experts,
`rms_norm_eps` 1e-5, `rope_theta` 10000, no `rope_scaling`, `clip_qkv`
null, no bias, untied head, vocabulary 50304, 4096 positions) - the FULL
forward to logits over a whole sequence, float32 `jax.numpy` at "highest"
matmul precision.  No cache, no kernel, no bucket, no sort and no gather
of experts: EVERY expert is computed for EVERY token and weighted by the
top-k mask.  The serving driver holds prefill plus decode-through-the-cache
to this.

As published, per layer: x += attention(rms(x)) with q and k RMS-normed
over the WHOLE projection before the split into heads, rotary embedding
over all of a head (half-split convention, position t from 0), causal
softmax scaled by 1/sqrt(head size); x += sum over the token's top-k
experts e of p_e * ((silu(h W_gate[e]) * (h W_up[e])) W_down[e]) with h =
rms(x) and p = softmax(h W_router) over all experts, the kept p AS THEY
ARE (`norm_topk_prob` false: they do not sum to 1), no capacity, no
dropped token, no shared expert; then a final RMSNorm and the untied head.

Departures: none in the mathematics.  The configuration under test cuts
DEPTH (`reduced`), computes in fp32 where the release is bf16, and draws
its weights from a seed; each is listed in the configuration file.

Besides the logits, `forward` returns the gap between the k-th and the
(k+1)-th router probability at every position and layer: where it is tiny
the program (whose other matmuls round to bf16) may rightly keep another
expert, and the comparison counts such positions instead of hiding them.

The state is a pure function of (seed, tensor name) (`draw_tensor`), so the
driver can draw the reference's weights one layer at a time from the seed
(`embed`, `layer`, `head` are `forward`'s own pieces) and never hand it the
predictor's arrays: at published widths two copies of the weights do not
fit one chip.

THE PRECISION BELOW.  `draw_tensor` and `layer_weights` take a `dtype`, and
`layer` and `head` compute in the dtype of what they are given: handed
bfloat16 weights and a bfloat16 residual stream, every tensor of the forward
(norms, rotary tables, scores, softmax, router, experts) is bfloat16, the
matmuls accumulating in fp32 as the hardware does.  That is the reading the
driver's precision limit has to refuse (`tolerances.precision_ratio`).  In
float32 nothing changes: the casts are to the dtype the value already has.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LAYER_WEIGHTS = ("ln1_g", "wq", "wk", "wv", "wo", "qn_g", "kn_g", "ln2_g",
                 "router", "w_gate", "w_up", "w_down")


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta)."""
    V, D, L = (int(model[k]) for k in ("vocab_size", "d_model", "n_layers"))
    E, F = int(model["n_experts"]), int(model["expert_width"])
    per_layer = {"ln1_g": (D,), "ln2_g": (D,), "qn_g": (D,), "kn_g": (D,),
                 "wq": (D, D), "wk": (D, D), "wv": (D, D), "wo": (D, D),
                 "router": (D, E), "w_gate": (E, D, F), "w_up": (E, D, F),
                 "w_down": (E, F, D)}
    shapes = {"embed": (V, D), "lnf_g": (D,), "lm_head": (D, V)}
    for i in range(L):
        shapes.update({"l%d_%s" % (i, n): s for n, s in per_layer.items()})
    return shapes


@jax.jit
def _seed_key(seed_u32):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)


def draw_tensor(name, shape, seed, dtype=jnp.float32):
    """One weight, on the device, from (seed, name) alone: a norm gain is
    1, a matrix normal(0, 1/sqrt(fan_in)); drawn in float32 and rounded to
    `dtype`."""
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                             np.uint32(zlib.crc32(name.encode())))
    return _normal(key, tuple(shape)).astype(dtype)


_normal = jax.jit(
    lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    / np.sqrt(shape[-2]), static_argnums=1)


def make_state_on_device(model, seed, names=None, dtype=jnp.float32):
    """{name: weight} for `names` (default: every tensor of the model)."""
    shapes = tensor_shapes(model)
    return {n: draw_tensor(n, shapes[n], seed, dtype)
            for n in (shapes if names is None else names)}


def layer_weights(model, seed, i, dtype=jnp.float32):
    """Layer i's weights under their bare names, drawn from the seed."""
    st = make_state_on_device(model, seed,
                              ["l%d_%s" % (i, n) for n in LAYER_WEIGHTS],
                              dtype)
    return {n: st["l%d_%s" % (i, n)] for n in LAYER_WEIGHTS}


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


def layer(x, w, model):
    """x [T, D] -> (x', gap [T] float32): one decoder layer, computed in
    x's dtype; `w` the layer's weights under their bare names
    (LAYER_WEIGHTS)."""
    with jax.default_matmul_precision("highest"):
        T, D = x.shape
        H = int(model["n_heads"])
        k = int(model["experts_per_token"])
        eps = float(model["norm_eps"])
        h = _rms(x, w["ln1_g"], eps)
        q = _rms(h @ w["wq"], w["qn_g"], eps).reshape(T, H, D // H)
        kk = _rms(h @ w["wk"], w["kn_g"], eps).reshape(T, H, D // H)
        v = (h @ w["wv"]).reshape(T, H, D // H)
        q = _rope(q, float(model["rope_theta"]))
        kk = _rope(kk, float(model["rope_theta"]))
        s = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(float(D // H))
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(T, D) @ w["wo"]

        h = _rms(x, w["ln2_g"], eps)
        p = jax.nn.softmax(h @ w["router"], axis=-1)            # [T, E]
        top, top_i = jax.lax.top_k(p, k + 1)
        keep = jnp.sum(jax.nn.one_hot(top_i[:, :k], p.shape[1],
                                      dtype=p.dtype), axis=1)   # [T, E]
        weight = p * keep
        if model.get("norm_topk_prob"):
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        act = jax.nn.silu(jnp.einsum("td,edf->tef", h, w["w_gate"])) \
            * jnp.einsum("td,edf->tef", h, w["w_up"])
        x = x + jnp.einsum("tef,efd->td", act * weight[:, :, None],
                           w["w_down"])
        return x, (top[:, k - 1] - top[:, k]).astype(jnp.float32)


def head(x, lnf_g, lm_head, model):
    """Logits in x's dtype."""
    with jax.default_matmul_precision("highest"):
        return _rms(x, lnf_g, float(model["norm_eps"])) @ lm_head


def forward(state, tokens, model):
    """tokens [T] int32 -> (logits [T, vocab], gaps [T, n_layers]);
    logits[t] predicts token t + 1, gaps[t, i] is layer i's gap between
    the k-th and (k+1)-th router probability at position t.  `state` is
    the artifact's weight dict, `model` its meta."""
    x = embed(state["embed"], tokens)
    gaps = []
    for i in range(int(model["n_layers"])):
        x, g = layer(x, {n: state["l%d_%s" % (i, n)]
                         for n in LAYER_WEIGHTS}, model)
        gaps.append(g)
    return (head(x, state["lnf_g"], state["lm_head"], model),
            jnp.stack(gaps, axis=1))
