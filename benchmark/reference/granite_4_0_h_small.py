"""Plain reference of the `granite_4_0_h_small` configuration: the
Granite-4.0-H decoder (HF `ibm-granite/granite-4.0-h-small` config.json,
`model_type` granitemoehybrid: hidden 4096; `layer_types` "mamba" in nine
layers of ten, a Mamba-2 (SSD) mixer ALONE (`mamba_n_heads` 128 heads of
`mamba_d_head` 64, `mamba_d_state` 128, `mamba_n_groups` 1, `mamba_d_conv` 4
with bias, chunk 256), and "attention" in the tenth (32 query heads over 8
K/V heads of 128, no bias, `position_embedding_type` "nope": NO rotation and
no position table; scores times `attention_multiplier` 1/128, not
1/sqrt(128)); behind EVERY layer a routed FFN, the 10 largest of 72 router
logits, softmax over those 10, experts of width 768, and a shared SwiGLU of
1536 beside it; `residual_multiplier` 0.22 on both sublayers' results,
`embedding_multiplier` 12, logits / `logits_scaling` 16 over the tied table;
`rms_norm_eps` 1e-5) - the FULL forward to logits over a whole sequence,
float32 `jax.numpy` at "highest" matmul precision.  The recurrence is
SEQUENTIAL, position by position (`lax.scan`): no chunks, no cache, no
kernel, no bucket, no batching.

The layer, x [T, D] (position t = row t), r = residual_multiplier:

    h    = rms(x; ln1_g)
    # a "ssm" layer (the meta's name of the release's "mamba")
    z, xBC, dt = split(h ssm_in, [d_ssm, d_ssm + 2 G N, Hs])      no bias
    xBC  = silu(conv(xBC; ssm_conv_w [C, K]) + ssm_conv_b)  causal, depthwise,
                                                    zeros before the start
    xs, B, C = split(xBC) -> [Hs, P], [G, N], [G, N]
    dt   = softplus(dt + ssm_dt_bias) [Hs];   A = -exp(ssm_A_log) [Hs]
    S_t  = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t     [Hs, P, N], S_{-1} = 0
    y_t  = S_t . C_t + ssm_D xs_t                          [Hs, P]
    y    = rms(y * silu(z)) over all d_ssm (ONE group), times ssm_norm_g
                                                    (the gate BEFORE the norm)
    x    = x + r (y ssm_out)
    # an "attention" layer
    q, k, v = h wq, wk, wv -> [H | Hkv | Hkv, Dh]           no rotation
    a    = softmax_causal(q k^T * attention_multiplier) v   query head i reads
                                                    K/V head i // (H / Hkv)
    x    = x + r (a wo)
    # every layer
    g    = rms(x; ln2_g)
    l    = g router                                 [T, 72] float32, "highest"
    used = the 10 largest l;  p = softmax(l[used])  (the program's softmax over
           all 72, its 10 largest renormalised, is the same function)
    routed = sum over the used experts e HELD HERE of p_e ((silu(g w_gate[e])
           * (g w_up[e])) w_down[e])                experts_held = (first,
           count): what the others would add is left out, as the program
           leaves it out, and the partial result goes on
    shared = (silu(g shared_gate) * (g shared_up)) shared_down
    x    = x + r (routed + shared)
    logits = (rms(x; lnf_g) embed^T) * lm_head_multiplier,
    x_0  = embed[token] * embedding_multiplier

THE WEIGHTS are a pure function of (seed, tensor name).  Every matmul weight
is a BFLOAT16 NUMBER (drawn in float32, rounded once; `at_rest`): the release
is bfloat16 and the program keeps them so; gains, the SSM's vectors and
depthwise taps and the ROUTER are float32.  THE SCALES (`weight_std`): the
published multipliers are small (0.22, 1/128, 1/16), so with every matrix at
normal(0, 1/sqrt(fan_in)) a branch's fault would drown in the residual
stream.  Each matrix is drawn at gain / sqrt(fan_in), the gain the
reciprocal of the multipliers on its product times an O(1) factor: embed std
1 / embedding_multiplier (x_0 std 1); wk gain QK_GAIN / (attention_multiplier
sqrt(Dh)) (q std 1, so the scores have std ~1.5: at 1/sqrt(128) in place of
1/128 they would have 17); wo, ssm_out, w_down and shared_down the reciprocal
of residual_multiplier times `OUT_GAINS`, so that each sublayer moves the
residual stream by the order of what it holds (the held quarter of the
routed experts a third of what the shared MLP does); the final norm's gain
`lnf_g` is the constant 1 / (lm_head_multiplier sqrt(D) std(embed)), so that
the logits have std ~1 under the tied table.  The SSM's vectors follow the
Mamba-2 initialisation as falcon_h1_34b.py draws them.

THE PRECISION BELOW.  `layer*` and `head` compute in the dtype of what they
are given: handed a bfloat16 residual stream and
`layer_weights(dtype=bfloat16)`, every tensor of the forward, the scanned
state and the router's logits among them, is bfloat16.

THE PRECISION THE CONFIGURATION STATES, AND THE NEAREST BELOW IT
(`precision`, float32 tensors throughout; `None` is the float32 forward
above).  "stated" is `assumed.dtype` taken at its word: a matmul against a
weight takes its activation ROUNDED TO BFLOAT16 (the weight is a bfloat16
number already) and accumulates in float32, and so do a PROMPT position's
score and value contractions (q, k, exp(s - max) and v rounded; positions
from `prompt_len` on are decode steps, whose kernel contracts at "highest");
the router, the recurrence, the conv, the norms, the softmax and every sum
stay float32.  "below" keeps, besides, every matmul's RESULT, every norm's
and the residual stream a layer hands on as bfloat16 numbers (the scanned
state, the recurrence and the router's logits still float32): a program
that holds its activations in bfloat16.  A program at the stated precision
lies nearer "stated" (or nearer the float32 forward, where it rounds
nothing) than "below", by a factor of two on the chip; one that keeps
bfloat16 activations lies as near "below" as the others
(`serve_decode_recurrent_moe.check_against_reference`).  Neither
is the comparison's reference: the logits are held to the float32 forward.

ROUTER NEAR-TIES.  A state-space layer hands a position's routing on to
EVERY later position (through its scanned state), and so does the attention
layer (through its K/V rows): `layer_hinted` takes the experts another
computation of this model chose (`hint`) and keeps them where its OWN logits
put every hinted expert within `margin` of its 10th (a near-tie, which
rounding may decide either way) and nowhere else, as lfm2_24b_a2b.py does.
`forward(..., states=True)` also returns what a cache would hold after the
LAST position (conv windows, scanned states, the attention layer's K and V
rows): the CPU tests hold the program's slot tables to them.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

SSM_WEIGHTS = ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
               "ssm_A_log", "ssm_D", "ssm_norm_g", "ssm_out")
ATTENTION_WEIGHTS = ("wq", "wk", "wv", "wo")
FFN_WEIGHTS = ("router", "w_gate", "w_up", "w_down", "shared_gate",
               "shared_up", "shared_down")
# std of the softmax's scores (q std 1, so k's after the scale)
QK_GAIN = 1.5
# what a sublayer's output projection is drawn at over 1 / (residual
# multiplier sqrt(fan_in)): wo makes up for the softmax's averaging of v;
# w_down for the routed sum's weights (10 of them summing to 1; the held
# quarter's part then moves the stream by ~0.4 a layer beside the mixer's 1.0
# and the shared MLP's 1.5: at twice this gain one of the program's other
# router decisions moved its token by a tenth of what the stream holds); the
# shared MLP's silu(.) * (.) has std ~0.4
OUT_GAINS = {"wo": 2.0, "ssm_out": 1.0, "w_down": 4.0, "shared_down": 2.5}


def _sizes(model):
    D, H = int(model["d_model"]), int(model["n_heads"])
    Dh = int(model.get("head_dim") or D // H)
    Hkv = int(model.get("n_kv_heads") or H)
    Hs, P, N, G = (int(model[k]) for k in ("ssm_heads", "ssm_head_dim",
                                           "ssm_state", "ssm_groups"))
    d_ssm = Hs * P
    return D, H, Hkv, Dh, Hs, P, N, G, d_ssm, d_ssm + 2 * G * N


def held(model):
    """(first, count) of the experts this member holds (all if unsaid)."""
    first, count = model.get("experts_held") or (0, int(model["n_experts"]))
    return int(first), int(count)


def layer_names(model, i):
    """Layer i's weights, by the kind its `layer_types` entry names."""
    mixer = SSM_WEIGHTS if model["layer_types"][i] == "ssm" \
        else ATTENTION_WEIGHTS
    return ("ln1_g", "ln2_g") + mixer + FFN_WEIGHTS


def tensor_shapes(model):
    """{weight name: shape} of the whole model, from the configuration's
    `model` block (the artifact's meta): no `lm_head` (tied), no `pos`."""
    D, H, Hkv, Dh, Hs, P, N, G, d_ssm, conv = _sizes(model)
    V, F, E = (int(model[k]) for k in ("vocab_size", "expert_width",
                                       "n_experts"))
    Fs = int(model["n_shared_experts"]) * F
    K, Eh = int(model["ssm_conv_kernel"]), held(model)[1]
    one = {"ln1_g": (D,), "ln2_g": (D,), "wq": (D, H * Dh),
           "wk": (D, Hkv * Dh), "wv": (D, Hkv * Dh), "wo": (H * Dh, D),
           "ssm_in": (D, d_ssm + conv + Hs), "ssm_conv_w": (conv, K),
           "ssm_conv_b": (conv,), "ssm_dt_bias": (Hs,), "ssm_A_log": (Hs,),
           "ssm_D": (Hs,), "ssm_norm_g": (d_ssm,), "ssm_out": (d_ssm, D),
           "router": (D, E), "w_gate": (Eh, D, F), "w_up": (Eh, D, F),
           "w_down": (Eh, F, D), "shared_gate": (D, Fs),
           "shared_up": (D, Fs), "shared_down": (Fs, D)}
    shapes = {"embed": (V, D), "lnf_g": (D,)}
    for i in range(int(model["n_layers"])):
        shapes.update({"l%d_%s" % (i, n): one[n]
                       for n in layer_names(model, i)})
    return shapes


def _bare(name):
    return name.split("_", 1)[1] if name[:1] == "l" and name[1].isdigit() \
        else name


def _mult(model, key, default=1.0):
    return float(model.get(key) or default)


def attention_scale(model):
    return _mult(model, "attention_multiplier",
                 1.0 / np.sqrt(_sizes(model)[3]))


def weight_std(name, shape, model):
    """The std a MATRIX is drawn at (the module's docstring): gain /
    sqrt(fan_in), the gain undoing the multipliers on its product."""
    bare = _bare(name)
    if bare == "embed":
        return 1.0 / _mult(model, "embedding_multiplier")
    if bare == "ssm_conv_w":
        return 1.0 / np.sqrt(shape[-1])
    gain = 1.0
    if bare == "wk":
        gain = QK_GAIN / (attention_scale(model)
                          * np.sqrt(_sizes(model)[3]))
    elif bare in OUT_GAINS:
        gain = OUT_GAINS[bare] / _mult(model, "residual_multiplier")
    return gain / np.sqrt(shape[-2])


def final_gain(model):
    """`lnf_g`'s constant: the logits' std is ~1 under the tied table."""
    return 1.0 / (_mult(model, "lm_head_multiplier")
                  * np.sqrt(float(model["d_model"]))
                  * weight_std("embed", None, model))


def at_rest(name, shape):
    """The dtype the artifact keeps a tensor in: bfloat16 for a matmul
    weight; float32 for a gain, the SSM's vectors and its depthwise taps
    ([channels, taps]: no matmul's operand) and the router (read at
    "highest")."""
    return jnp.float32 if len(shape) == 1 or name.endswith(
        ("ssm_conv_w", "router")) else jnp.bfloat16


@jax.jit
def _seed_key(seed_u32):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)


def _key(name, seed):
    return jax.random.fold_in(_seed_key(np.uint32(int(seed) % (1 << 32))),
                              np.uint32(zlib.crc32(name.encode())))


# (a bfloat16 number is made INSIDE the jitted draw and widened outside it:
# within one program the TPU may keep the float32 value)
_normal = jax.jit(
    lambda key, shape, std, rest: (jax.random.normal(
        key, shape, jnp.float32) * std).astype(rest),
    static_argnums=(1, 2, 3))
_uniform = jax.jit(
    lambda key, shape, lo, hi: jax.random.uniform(key, shape, jnp.float32,
                                                  lo, hi),
    static_argnums=(1, 2, 3))


def _vector(name, shape, seed, model):
    bare = _bare(name)
    if bare == "lnf_g":
        return jnp.full(shape, final_gain(model), jnp.float32)
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
        return 1.0 + _normal(key, tuple(shape), 0.25, jnp.float32)
    if bare == "ssm_conv_b":
        return _normal(key, tuple(shape), 0.2, jnp.float32)
    raise KeyError(name)


def draw_tensor(name, shape, seed, dtype=None, model=None):
    """One weight, on the device, from (seed, name) alone, rounded to the
    dtype it has at rest (`at_rest`) and given in `dtype` (None: as it is at
    rest).  `model`: the meta, whose multipliers set a matrix's scale
    (`weight_std`) and the final norm's gain.  An expert's matrix has a key
    of its own by its index among ALL the experts, so the run a member holds
    is that run of the layer drawn whole."""
    rest = at_rest(name, shape)
    if len(shape) == 1:
        return _vector(name, shape, seed, model).astype(dtype or rest)
    std = float(weight_std(name, shape, model))
    if len(shape) == 3:
        first, key = held(model)[0], _key(name, seed)
        return jnp.stack([
            _normal(jax.random.fold_in(key, np.uint32(first + e)),
                    tuple(shape[1:]), std, rest).astype(dtype or rest)
            for e in range(shape[0])])
    return _normal(_key(name, seed), tuple(shape), std, rest).astype(
        dtype or rest)


def layer_weights(model, seed, i, dtype=jnp.float32):
    """Layer i's weights under their bare names, drawn from the seed."""
    shapes = tensor_shapes(model)
    return {n: draw_tensor("l%d_%s" % (i, n), shapes["l%d_%s" % (i, n)],
                           seed, dtype, model)
            for n in layer_names(model, i)}


PRECISIONS = (None, "stated", "below")


def _bf16(x):
    """x's values as bfloat16 numbers, in x's dtype (`reduce_precision`: a
    convert there and back is one the TPU's compiler may drop)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(x, w, precision=None, contract=jnp.matmul):
    """`contract(x, w)` as `precision` computes it (the module's docstring):
    the activation rounded under "stated" and "below", the result too under
    "below"."""
    y = contract(_bf16(x) if precision else x, w)
    return _bf16(y) if precision == "below" else y


def _rms(x, g, eps, precision=None):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + jnp.asarray(eps, x.dtype)) * g
    return _bf16(y) if precision == "below" else y


def embed(table, tokens, model):
    """x_0 [T, D] from the table (times `embedding_multiplier`)."""
    x = table[tokens]
    return x * jnp.asarray(_mult(model, "embedding_multiplier"), x.dtype)


def attention(h, w, model, precision=None, prompt_len=0):
    """Grouped-query causal attention of the normed input h [T, D], no
    position signal -> (result [T, D] before the residual multiplier, K
    rows [T, Hkv * Dh], V rows).  Under a `precision` the query positions
    before `prompt_len` contract rounded operands."""
    T = h.shape[0]
    _, H, Hkv, Dh = _sizes(model)[:4]
    q = _mm(h, w["wq"], precision).reshape(T, H, Dh)
    k = _mm(h, w["wk"], precision).reshape(T, Hkv, Dh)
    v = _mm(h, w["wv"], precision).reshape(T, Hkv, Dh)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scale = jnp.asarray(attention_scale(model), h.dtype)
    prompt = (jnp.arange(T) < prompt_len)[:, None, None]

    def group(qkv):
        # one K/V head and the G query heads that read it
        qg, kg, vg = qkv                    # [T, G, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("qgd,kd->gqk", qg, kg) * scale
        s = jnp.where(mask[None], s, -jnp.inf)
        a = jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), vg)
        if not precision:
            return a
        # a prompt's position: the same sums over rounded q, k, exp(s -
        # max) and v, the softmax's denominator of the unrounded
        s = jnp.einsum("qgd,kd->gqk", _bf16(qg), _bf16(kg)) * scale
        s = jnp.where(mask[None], s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        low = jnp.einsum("gqk,kd->qgd", _bf16(e), _bf16(vg)) \
            / jnp.sum(e, axis=-1).T[:, :, None]
        return jnp.where(prompt, low, a)

    a = jax.lax.map(group, (q.reshape(T, Hkv, H // Hkv, Dh).swapaxes(0, 1),
                            k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return (_mm(a.swapaxes(0, 1).reshape(T, H * Dh), w["wo"], precision),
            k.reshape(T, Hkv * Dh), v.reshape(T, Hkv * Dh))


def ssm(h, w, model, precision=None):
    """The state-space mixer of the normed input h [T, D], the recurrence
    position by position -> (result [T, D] before the residual multiplier,
    the conv's last K - 1 PRE-activation inputs [K - 1, C], the state after
    the last position [Hs, P, N])."""
    T = h.shape[0]
    D, _, _, _, Hs, P, N, G, d_ssm, conv = _sizes(model)
    K, k = int(model["ssm_conv_kernel"]), Hs // G
    p = _mm(h, w["ssm_in"], precision)
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
    return _mm(y, w["ssm_out"], precision), window, S


def routed_ffn(g, w, model, hint=None, margin=0.0, precision=None):
    """(the held experts' weighted sum [T, D], gap [T] between the 10th and
    the 11th router logit, the experts used [T, k] ascending, how far below
    the 10th logit the least of a hinted position's experts lay [T]: 0
    where they are the top k, +inf where nothing was hinted).

    `hint` [T, k] int32 names, at some positions (a row of -1 = none), the
    experts ANOTHER computation of this model chose there.  The reference
    keeps its own top k unless every hinted expert lies within `margin` (a
    number, or one a position [T]) of its k-th logit."""
    k, E = int(model["experts_per_token"]), int(model["n_experts"])
    first, count = held(model)
    logits = g @ w["router"].astype(g.dtype)                    # [T, E]
    top, top_i = jax.lax.top_k(logits, k + 1)
    used = top_i[:, :k]
    short = jnp.full(g.shape[:1], jnp.inf, jnp.float32)
    if hint is not None:
        hinted = hint[:, 0] >= 0
        theirs = jnp.take_along_axis(logits, jnp.maximum(hint, 0), axis=-1)
        short = jnp.where(hinted, (top[:, k - 1] - jnp.min(theirs, axis=-1))
                          .astype(jnp.float32), short)
        used = jnp.where((hinted & (short <= margin))[:, None], hint, used)
    # softmax over the k kept logits, each weight at its expert's place
    p = jax.nn.softmax(jnp.take_along_axis(logits, used, axis=-1), axis=-1)
    weight = jnp.einsum("tk,tke->te", p,
                        jax.nn.one_hot(used, E, dtype=p.dtype))
    weight = weight[:, first:first + count]
    into = functools.partial(jnp.einsum, "td,edf->tef")
    act = jax.nn.silu(_mm(g, w["w_gate"], precision, into)) \
        * _mm(g, w["w_up"], precision, into)
    if precision:
        # an expert's result is rounded as ITS matmul's, then weighted
        routed = jnp.einsum("te,ted->td", weight, _mm(
            act, w["w_down"], precision,
            functools.partial(jnp.einsum, "tef,efd->ted")))
    else:
        routed = jnp.einsum("tef,efd->td", act * weight[:, :, None],
                            w["w_down"])
    return (routed, (top[:, k - 1] - top[:, k]).astype(jnp.float32),
            jnp.sort(used, axis=-1).astype(jnp.int32), short)


def shared_mlp(g, w, precision=None):
    return _mm(jax.nn.silu(_mm(g, w["shared_gate"], precision))
               * _mm(g, w["shared_up"], precision), w["shared_down"],
               precision)


def layer_states(x, w, model, hint=None, margin=0.0, precision=None,
                 prompt_len=0):
    """x [T, D] -> (x', gap [T], experts used [T, k], short [T], what a
    cache would hold of the mixer): one decoder layer, computed in x's
    dtype; `w` the layer's weights under their bare names, which say what
    kind of layer it is.  `precision` (float32 tensors; `PRECISIONS`) and
    `prompt_len`: the module's docstring."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["norm_eps"])
        r = jnp.asarray(_mult(model, "residual_multiplier"), x.dtype)
        w = {n: v.astype(x.dtype) for n, v in w.items()}
        h = _rms(x, w["ln1_g"], eps, precision)
        mixed, *kept = ssm(h, w, model, precision) if "ssm_in" in w \
            else attention(h, w, model, precision, prompt_len)
        x = x + r * mixed
        g = _rms(x, w["ln2_g"], eps, precision)
        routed, gap, used, short = routed_ffn(g, w, model, hint, margin,
                                              precision)
        x = x + r * (routed + shared_mlp(g, w, precision))
        return (_bf16(x) if precision == "below" else x, gap, used, short,
                tuple(kept))


def layer_hinted(x, w, model, hint=None, margin=0.0, precision=None,
                 prompt_len=0):
    """x [T, D] -> (x', gap [T] float32, experts used [T, k] int32, short
    [T] float32): `routed_ffn`'s hint, margin and results."""
    return layer_states(x, w, model, hint, margin, precision,
                        prompt_len)[:4]


def layer(x, w, model):
    """x [T, D] -> (x', gap [T] float32): `layer_hinted` with no hint (the
    interface `serve_decode_arch` drives)."""
    return layer_states(x, w, model)[:2]


def head(x, lnf_g, table, model, precision=None):
    """Logits in x's dtype; `table` is the embedding table (tied head), or
    a block of its rows."""
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, lnf_g.astype(x.dtype), float(model["norm_eps"]),
                        precision), table.astype(x.dtype).T, precision) \
            * jnp.asarray(_mult(model, "lm_head_multiplier"), x.dtype)


def forward(state, tokens, model, states=False):
    """tokens [T] int32 -> (logits [T, vocab], gaps [T, n_layers]); with
    `states` also {"conv": [ssm layers, K - 1, C], "ssm": [ssm layers, Hs,
    P, N], "k" / "v": [attention layers, T, Hkv * Dh]} as a cache would
    hold them after the LAST position.  logits[t] predicts token t + 1.
    `state` is the artifact's weight dict (in whatever dtype it is kept:
    widened here), `model` its meta."""
    table = jnp.asarray(state["embed"]).astype(jnp.float32)
    x = embed(table, tokens, model)
    gaps, kept = [], {"conv": [], "ssm": [], "k": [], "v": []}
    for i in range(int(model["n_layers"])):
        w = {n: jnp.asarray(state["l%d_%s" % (i, n)])
             for n in layer_names(model, i)}
        x, gap, _, _, mixer = layer_states(x, w, model)
        gaps.append(gap)
        for name, t in zip(("conv", "ssm") if "ssm_in" in w else ("k", "v"),
                           mixer):
            kept[name].append(t)
    out = (head(x, jnp.asarray(state["lnf_g"]), table, model),
           jnp.stack(gaps, axis=1))
    return out + (({n: jnp.stack(t) for n, t in kept.items()},) if states
                  else ())
