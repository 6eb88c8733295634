"""Plain reference of the `openpangu_ultra_moe_718b` configuration: the
openPangu-Ultra-MoE decoder (HF `FreedomIntelligence/openPangu-Ultra-MoE-718B`
config.json, `model_type` pangu_ultra_moe: hidden 7680, 128 heads of 128 nope
+ 64 rope query/key lanes and 128 value lanes, `q_lora_rank` 1536,
`kv_lora_rank` 512, a leading dense SwiGLU of 18432, then 256 routed experts
of 2048, 8 a token, scaled by 2.5, beside one shared expert, `sandwich_norm`,
`rms_norm_eps` 1e-5, `rope_theta` 25.6e6, untied head) - the FULL forward to
logits over a whole sequence, float32 `jax.numpy` at "highest" matmul
precision.  EXPANDED attention only: every position's latent goes up to its
128 heads' keys and values and the attention is the textbook one.  No cache,
no absorption, no kernel, no bucket, no sort and no gather of experts: every
HELD expert is computed for every token and weighted by the top-k mask.

The layer, x [T, D]:

    h      = rms(x; ln1_g)
    c_q    = rms(h wq_a; q_a_g);  q = c_q wq_b -> [H, nope | rope]
    [c_kv | k_rope] = h wkv_a;  c_kv = rms(c_kv; kv_a_g)
    q_rope, k_rope = rope(., t)     half-split over the rope lanes, ONE
                                    k_rope a position for all heads
    [k_nope_h | v_h] = c_kv wkv_b -> [H, nope | v]
    s_h(t, s) = (q_nope_h . k_nope_h(s) + q_rope_h . k_rope(s)) / sqrt(192)
    a = concat_h(softmax_causal(s_h) v_h) wo
    x = x + rms(a; ln1p_g)                                  (sandwich)
    g = rms(x; ln2_g)
    dense (leading layers):  f = (silu(g ffn_gate) * (g ffn_up)) ffn_down
    routed:  s = sigmoid(g router) [E];  idx = top-8(s)
             w = 2.5 * s[idx] / (sum s[idx] + 1e-20)
             f = sum_{e in idx, e held here} w_e SwiGLU_e(g) + SwiGLU_shared(g)
    x = x + rms(f; ln2p_g)                                  (sandwich)
    logits = rms(x; lnf_g) lm_head

Readings of the catalog's config, listed as `assumed` in the configuration
file: the norms' placement (before AND after each sublayer, as the
`pangu_ultra_moe` modelling code the config names places them), sigmoid
scores with no selection bias and no expert groups (the config has no key for
either), and the rope lanes' layout (half-split; any fixed layout is a
permutation of seeded weights).

THE CHIP'S SHARE (the `model-configs` guide, section 4).  `experts_held` =
(first, count): this member of the expert-parallel deployment holds that run
of the router's E experts.  The router keeps its E outputs and its top-8;
what the experts held elsewhere would have added is left out, here as in the
program, and that partial result goes on to the next layer.  `ffn_parts`
returns the routed and the shared part apart, so that a test can add the
shares up: all members' routed parts plus the shared expert ONCE are the
uncut layer's (`experts_held` = (0, E)).

THE WEIGHTS are a pure function of (seed, tensor name), as in
olmoe_1b_7b.py, and every matmul weight is a BFLOAT16 NUMBER (drawn in
float32, rounded once): the release is bfloat16, and the program keeps those
weights in bfloat16 at rest (`weight_dtype`).  `draw_tensor(dtype=None)`
hands a tensor out as the artifact stores it (bfloat16 matmul weights; gains
and the router float32); the reference itself widens them to float32 and
loses nothing.

THE PRECISION BELOW.  As in olmoe_1b_7b.py: `layer` and `head` compute in the
dtype of what they are given; handed a bfloat16 residual stream and
`layer_weights(dtype=bfloat16)`, every tensor of the forward is bfloat16.

Besides the logits `forward` returns, a position and routed layer, the gap
between the 8th and the 9th router score (`DENSE_GAP` for a dense layer).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np

MLA_WEIGHTS = ("ln1_g", "ln1p_g", "ln2_g", "ln2p_g", "wq_a", "q_a_g", "wq_b",
               "wkv_a", "kv_a_g", "wkv_b", "wo")
DENSE_WEIGHTS = ("ffn_gate", "ffn_up", "ffn_down")
ROUTED_WEIGHTS = ("router", "w_gate", "w_up", "w_down", "shared_gate",
                  "shared_up", "shared_down")
# a dense layer routes nothing: no near-tie there (finite: logs are JSON)
DENSE_GAP = 1e9


def layer_names(model, i):
    return MLA_WEIGHTS + (DENSE_WEIGHTS if i < int(model["n_dense_layers"])
                          else ROUTED_WEIGHTS)


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta)."""
    V, D, L, H = (int(model[k]) for k in ("vocab_size", "d_model",
                                          "n_layers", "n_heads"))
    rq, rkv, dn, dr, dv = (int(model[k]) for k in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim"))
    E, F = int(model["n_experts"]), int(model["expert_width"])
    held = int(model["experts_held"][1]) if model.get("experts_held") else E
    Fd, Fs = int(model["dense_width"]), int(model["n_shared_experts"]) * F
    every = {"ln1_g": (D,), "ln1p_g": (D,), "ln2_g": (D,), "ln2p_g": (D,),
             "wq_a": (D, rq), "q_a_g": (rq,), "wq_b": (rq, H * (dn + dr)),
             "wkv_a": (D, rkv + dr), "kv_a_g": (rkv,),
             "wkv_b": (rkv, H * (dn + dv)), "wo": (H * dv, D),
             "ffn_gate": (D, Fd), "ffn_up": (D, Fd), "ffn_down": (Fd, D),
             "router": (D, E), "w_gate": (held, D, F), "w_up": (held, D, F),
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
    lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    / np.sqrt(shape[-2]), static_argnums=1)


def at_rest(name, shape):
    """The dtype the artifact keeps a tensor in: bfloat16 for a matmul
    weight, float32 for a gain and for the router's matrix (read at
    "highest" by the program)."""
    return jnp.float32 if len(shape) == 1 or name.endswith("_router") \
        else jnp.bfloat16


def draw_tensor(name, shape, seed, dtype=None):
    """One weight, on the device, from (seed, name) alone: a norm gain is 1,
    a matrix normal(0, 1/sqrt(fan_in)) drawn in float32 and rounded to the
    dtype it has at rest (`at_rest`), then given in `dtype` (None: as it is
    at rest)."""
    rest = at_rest(name, shape)
    if len(shape) == 1:
        return jnp.ones(shape, dtype or rest)
    key = jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                             np.uint32(zlib.crc32(name.encode())))
    return _normal(key, tuple(shape)).astype(rest).astype(dtype or rest)


def make_state_on_device(model, seed, names=None, dtype=jnp.float32):
    """{name: weight} for `names` (default: every tensor of the model)."""
    shapes = tensor_shapes(model)
    return {n: draw_tensor(n, shapes[n], seed, dtype)
            for n in (shapes if names is None else names)}


def layer_weights(model, seed, i, dtype=jnp.float32):
    """Layer i's weights under their bare names, drawn from the seed."""
    names = layer_names(model, i)
    st = make_state_on_device(model, seed, ["l%d_%s" % (i, n) for n in names],
                              dtype)
    return {n: st["l%d_%s" % (i, n)] for n in names}


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
    """The expanded latent attention of the normed input h [T, D]."""
    T = h.shape[0]
    H = int(model["n_heads"])
    rkv, dn, dr, dv = (int(model[k]) for k in (
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim"))
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    q = (_rms(h @ w["wq_a"], w["q_a_g"], eps) @ w["wq_b"]).reshape(
        T, H, dn + dr)
    kv = h @ w["wkv_a"]
    c_kv = _rms(kv[:, :rkv], w["kv_a_g"], eps)
    k_rope = _rope(kv[:, None, rkv:], theta)[:, 0]              # [T, dr]
    q_rope = _rope(q[..., dn:], theta)
    up = (c_kv @ w["wkv_b"]).reshape(T, H, dn + dv)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], up[..., :dn])
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) \
        / jnp.sqrt(float(dn + dr)).astype(h.dtype)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), up[..., dn:])
    return a.reshape(T, H * dv) @ w["wo"]


def ffn_parts(g, w, model):
    """(routed part, shared part, gap) of a routed layer's FFN on the normed
    input g [T, D]: the held experts' weighted sum, the shared expert's
    result, and the gap between the k-th and the (k+1)-th router score."""
    k = int(model["experts_per_token"])
    E = int(model["n_experts"])
    first, count = model.get("experts_held") or (0, E)
    s = jax.nn.sigmoid(g @ w["router"])                         # [T, E]
    top, top_i = jax.lax.top_k(s, k + 1)
    keep = jnp.sum(jax.nn.one_hot(top_i[:, :k], E, dtype=s.dtype), axis=1)
    weight = s * keep
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
    dtype; `w` the layer's weights under their bare names (`layer_names`),
    a dense layer's if it has no router."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["norm_eps"])
        w = {n: v.astype(x.dtype) for n, v in w.items()}
        x = x + _rms(attention(_rms(x, w["ln1_g"], eps), w, model),
                     w["ln1p_g"], eps)
        g = _rms(x, w["ln2_g"], eps)
        if "router" in w:
            routed, shared, gap = ffn_parts(g, w, model)
            f = routed + shared
        else:
            f = _swiglu(g, w["ffn_gate"], w["ffn_up"], w["ffn_down"])
            gap = jnp.full(x.shape[:1], DENSE_GAP, jnp.float32)
        return x + _rms(f, w["ln2p_g"], eps), gap


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
        x, g = layer(x, {n: state["l%d_%s" % (i, n)]
                         for n in layer_names(model, i)}, model)
        gaps.append(g)
    return (head(x, state["lnf_g"], state["lm_head"], model),
            jnp.stack(gaps, axis=1))
