"""Plain reference of the `mimo_v2_flash` configuration: the MiMo-V2-Flash
decoder (HF `XiaomiMiMo/MiMo-V2-Flash` config.json, `model_type`
mimo_v2_flash: hidden 4096; 64 query heads whose q and k are 192 wide and
whose v is 128; layers in the pattern F SSSS F SSSSS F ..., a FULL layer
(4 K/V heads, rope theta 5e6) to five that attend over the last
`sliding_window` 128 positions (8 K/V heads, rope theta 1e4, a learned sink
logit a query head in the softmax's denominator); rotary over the first
int(192 x 0.334) = 64 lanes of a head; v times 0.707; layer 0's FFN a dense
SwiGLU of 16384, then 256 routed experts of 2048, 8 a token by sigmoid score
+ a per-expert selection bias, weights renormalised, no shared expert;
`layernorm_epsilon` 1e-5; untied head) - the FULL forward to logits over a
whole sequence, float32 `jax.numpy` at "highest" matmul precision.  Whole
[T, T] masks, no ring, no cache, no kernel, no bucket, no blocks of queries,
no sort and no gather of experts: every HELD expert is computed for every
token and weighted by the top-k mask.  (The scores are taken one K/V head's
group of query heads at a time, `lax.map`; the mathematics is a head's own.)

The layer, x [T, D]:

    h      = rms(x; ln1_g)
    q,k,v  = h wq -> [64, 192],  h wk -> [Hc, 192],  h wv -> [Hc, 128]
             Hc = 4 (full layer) | 8 (window layer)
    q, k   = lanes 0..63 of each head turned by rope(., t; theta), half-split
             among themselves (lane i with lane i + 32, angle t theta^(-i/32)),
             lanes 64..191 as they are;  theta 5e6 (full) | 1e4 (window)
    v      = 0.707 v
    a_t    = sum_j p_tj v_j,  scores a_tj = q_t . k_j / sqrt(192)
             full layer:    j in [0, t],  p = softmax_j(a)
             window layer:  j in (t - 128, t]  (128 keys, the token's own),
                            p_j = exp(a_j - m)
                                  / (exp(s_h - m) + sum_j exp(a_j - m))
                            with s_h the head's learned sink: computed as ONE
                            MORE COLUMN of the scores, softmaxed with the
                            rest and dropped (it carries no value)
             query head a reads K/V head a // (64 / Hc)
    x      = x + a wo                         wo [64 x 128, 4096]
    g      = rms(x; ln2_g)
    dense (layer 0):  f = (silu(g ffn_gate) * (g ffn_up)) ffn_down
    routed:  s = sigmoid(g router) [256];  idx = top-8(s + expert_bias)
             w = s[idx] / (sum s[idx] + 1e-20)
             f = sum_{e in idx, e held here} w_e SwiGLU_e(g)
    x      = x + f
    logits = rms(x; lnf_g) lm_head

WHICH KIND a layer is rides its weights: `layer_weights` gives a window
layer its `sink` [64] and one more entry, `"window"` (an int32 scalar, the
meta's `sliding_window`); `layer` masks, adds the sink's column and takes the
window layers' theta where it finds it, and reads the K/V head count off
`wk`'s width.  (The benchmark's driver calls `layer(x, w, model)` without the
layer's index.)

DEPARTURES from the published description, each listed as `assumed` in the
configuration file: the window's edge (128 keys, the token's own among
them); WHICH lanes turn (the first 64, half-split among themselves); the
sinks' values (seeded normal(0, 1): trained ones are not to be had, and a
sink of 0 beside scores of std ~1 would move little); the selection bias
seeded normal(0, 0.05), used to select and never to weigh; no qk-norm; one
RMSNorm in FRONT of each sublayer; `routed_scaling_factor` null read as 1;
the three multi-token-prediction layers of the model card are no part of the
main forward pass and are not run.

THE CHIP'S SHARE (the `model-configs` guide, section 4): `experts_held` =
(first, count), as in k_exaone_236b_a23b.py; `ffn_parts` returns the held
experts' part alone (there is no shared expert to count once), so that a
test can add the 32 shares up to the uncut layer.

THE WEIGHTS are a pure function of (seed, tensor name), every matmul weight
a BFLOAT16 NUMBER (drawn in float32, rounded once), the router's matrix, the
gains, the selection bias and the sinks float32: as in k_exaone_236b_a23b.py.

THE PRECISION BELOW: `layer` and `head` compute in the dtype of what they are
given; handed a bfloat16 residual stream and `layer_weights(dtype=bfloat16)`,
every tensor of the forward is bfloat16.

Besides the logits `forward` returns, a position and routed layer, the gap
between the 8th and the 9th BIASED router score (`DENSE_GAP` for a dense
layer): the quantity the selection is made on.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION_WEIGHTS = ("ln1_g", "ln2_g", "wq", "wk", "wv", "wo")
DENSE_WEIGHTS = ("ffn_gate", "ffn_up", "ffn_down")
ROUTED_WEIGHTS = ("router", "expert_bias", "w_gate", "w_up", "w_down")
# a dense layer routes nothing: no near-tie there (finite: logs are JSON)
DENSE_GAP = 1e9
# the vectors that are drawn (every other one is a gain of 1), and their std
VECTOR_STD = {"expert_bias": 0.05, "sink": 1.0}


def is_window(model, i):
    return model["layer_types"][i] == "window_attention"


def layer_names(model, i):
    return ATTENTION_WEIGHTS + (
        ("sink",) if is_window(model, i) and model.get("window_sink")
        else ()) + (
        DENSE_WEIGHTS if i < int(model["n_dense_layers"]) else ROUTED_WEIGHTS)


def kv_heads(model, i):
    return int(model["window_kv_heads"] if is_window(model, i)
               else model["n_kv_heads"])


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta)."""
    V, D, L, H = (int(model[k]) for k in ("vocab_size", "d_model",
                                          "n_layers", "n_heads"))
    Dk, Dv = int(model["head_dim"]), int(model["v_head_dim"])
    E, F = int(model["n_experts"]), int(model["expert_width"])
    held = int(model["experts_held"][1]) if model.get("experts_held") else E
    Fd = int(model["dense_width"])
    shapes = {"embed": (V, D), "lnf_g": (D,), "lm_head": (D, V)}
    for i in range(L):
        Hc = kv_heads(model, i)
        every = {"ln1_g": (D,), "ln2_g": (D,), "wq": (D, H * Dk),
                 "wk": (D, Hc * Dk), "wv": (D, Hc * Dv), "wo": (H * Dv, D),
                 "sink": (H,),
                 "ffn_gate": (D, Fd), "ffn_up": (D, Fd), "ffn_down": (Fd, D),
                 "router": (D, E), "expert_bias": (E,),
                 "w_gate": (held, D, F), "w_up": (held, D, F),
                 "w_down": (held, F, D)}
        shapes.update({"l%d_%s" % (i, n): every[n]
                       for n in layer_names(model, i)})
    return shapes


# elements a draw: ONE compiled draw serves every shape.  A draw of a
# tensor's own shape costs the TPU's compiler 1-4 s a SHAPE the first time
# whatever the bit generator (thirteen matrix shapes here; threefry up to
# 17.9 s for the experts': my chip runs, PR 51, PERF.md section 6), and a
# cold run's set-up pays each once
DRAW = 1 << 20


@jax.jit
def _normal(seed_u32, name_u32, j):
    # the chip's own bit generator (a threefry draw of 1 M elements is no
    # faster to compile and slower to run); chunk j of tensor `name`
    key = jax.random.key(0, impl="unsafe_rbg")
    for word in (seed_u32, name_u32, j):
        key = jax.random.fold_in(key, word)
    return jax.random.normal(key, (DRAW,), jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _shaped(chunks, shape, std, rest):
    # (elementwise: a fraction of a second a shape to compile.)  It returns
    # the tensor AS IT IS AT REST and no wider: asked for float32 in the same
    # program, the chip's compiler may keep the float32 value through a
    # bfloat16 -> float32 pair (`xla_allow_excess_precision`), and the
    # reference would compute on weights the artifact does not hold (my chip
    # run, PR 51: every position moved by 0.026, PERF.md section 6)
    n = int(np.prod(shape))
    return (jnp.concatenate(chunks)[:n].reshape(shape) * std).astype(rest)


def at_rest(name, shape):
    """The dtype the artifact keeps a tensor in: bfloat16 for a matmul
    weight, float32 for a gain, the selection bias, the sinks and the
    router's matrix (read at "highest" by the program)."""
    return jnp.float32 if len(shape) == 1 or name.endswith("_router") \
        else jnp.bfloat16


def draw_tensor(name, shape, seed, dtype=None):
    """One weight, on the device, from (seed, name) alone: a norm gain is 1,
    the selection bias normal(0, 0.05) (zero would make the selection by
    biased score the selection by score), a sink normal(0, 1) (beside
    scores of std ~1: leaving it out, or giving it to a full layer, moves
    the logits far past the bounds), a matrix normal(0, 1/sqrt(fan_in))
    drawn in float32 and rounded to the dtype it has at rest (`at_rest`),
    then given in `dtype` (None: as it is at rest)."""
    rest = at_rest(name, shape)
    std = VECTOR_STD.get(name.split("_", 1)[-1]) if len(shape) == 1 \
        else float(1.0 / np.sqrt(shape[-2]))
    if std is None:
        return jnp.ones(shape, dtype or rest)
    words = (np.uint32(int(seed) % (1 << 32)),
             np.uint32(zlib.crc32(name.encode())))
    chunks = [_normal(*words, np.uint32(j))
              for j in range(-(-int(np.prod(shape)) // DRAW))]
    return _shaped(chunks, tuple(shape), std, jnp.dtype(rest)).astype(
        dtype or rest)


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


def _rope(x, theta, lanes):
    """x [T, heads, Dk], position t = row index: the first `lanes` lanes of
    each head turned, half-split among themselves; the rest as they are."""
    T = x.shape[0]
    half = lanes // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / lanes)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]  # [T, half]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    r = x[..., :lanes]
    r1, r2 = r[..., :half], r[..., half:]
    turned = r * cos + jnp.concatenate([-r2, r1], -1) * sin
    return jnp.concatenate([turned, x[..., lanes:]], axis=-1)


def _swiglu(g, gate, up, down):
    return (jax.nn.silu(g @ gate) * (g @ up)) @ down


def embed(table, tokens):
    return table[tokens]


def attention(h, w, model):
    """The attention of the normed input h [T, D]: a window layer's if `w`
    holds `"window"`, a full layer's otherwise; its K/V heads are `wk`'s
    width over a key head's."""
    T = h.shape[0]
    H, Dk, Dv = (int(model[k]) for k in ("n_heads", "head_dim",
                                         "v_head_dim"))
    Hc = w["wk"].shape[1] // Dk
    G = H // Hc
    window = "window" in w
    theta = float(model["window_rope_theta"] if window
                  else model["rope_theta"])
    lanes = int(model["rotary_dim"])
    q = _rope((h @ w["wq"]).reshape(T, H, Dk), theta, lanes)
    k = _rope((h @ w["wk"]).reshape(T, Hc, Dk), theta, lanes)
    v = (h @ w["wv"]).reshape(T, Hc, Dv) \
        * jnp.asarray(float(model["value_scale"]), h.dtype)
    t = jnp.arange(T)
    mask = t[None, :] <= t[:, None]                     # [query, key]
    if window:
        mask = mask & (t[None, :] > t[:, None] - w["window"])
    sink = w["sink"].reshape(Hc, G) if "sink" in w else None

    def group(qkv):
        # one K/V head and the G query heads that read it
        qg, kg, vg = qkv[:3]                # [T, G, Dk], [T, Dk], [T, Dv]
        s = jnp.einsum("qgd,kd->gqk", qg, kg) \
            / jnp.sqrt(float(Dk)).astype(h.dtype)
        s = jnp.where(mask[None], s, -jnp.inf)
        if sink is not None:
            # the sink: one more column, softmaxed with the keys' and
            # dropped (it carries no value)
            col = jnp.broadcast_to(qkv[3].astype(s.dtype)[:, None, None],
                                   (G, T, 1))
            p = jax.nn.softmax(jnp.concatenate([s, col], axis=-1),
                               axis=-1)[..., :T]
        else:
            p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vg)

    xs = (q.reshape(T, Hc, G, Dk).swapaxes(0, 1), k.swapaxes(0, 1),
          v.swapaxes(0, 1)) + (() if sink is None else (sink,))
    a = jax.lax.map(group, xs)
    return a.swapaxes(0, 1).reshape(T, H * Dv) @ w["wo"]


def ffn_parts(g, w, model):
    """(the held experts' part, gap) of a routed layer's FFN on the normed
    input g [T, D]: the held experts' weighted sum, and the gap between
    the k-th and the (k+1)-th BIASED score.  No shared expert."""
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
    weight = (weight * jnp.asarray(float(model.get("routed_scaling", 1.0)),
                                   s.dtype)
              )[:, int(first):int(first) + int(count)]
    act = jax.nn.silu(jnp.einsum("td,edf->tef", g, w["w_gate"])) \
        * jnp.einsum("td,edf->tef", g, w["w_up"])
    routed = jnp.einsum("tef,efd->td", act * weight[:, :, None], w["w_down"])
    return routed, (top[:, k - 1] - top[:, k]).astype(jnp.float32)


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
            routed, gap = ffn_parts(g, w, model)
            return x + routed, gap
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
