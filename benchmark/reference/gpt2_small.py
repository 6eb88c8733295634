"""Plain reference of the `gpt2_small` configuration: the GPT-2 decoder
(Radford et al. 2019; HF `gpt2` config.json: n_embd 768, n_head 12, n_layer
12, n_positions 1024, vocab_size 50257) — the FULL forward to logits over a
whole sequence, float32 `jax.numpy` at "highest" matmul precision.  No
cache, no kernels, no batching, no buckets: the serving driver holds prefill
plus decode-through-the-cache to this.

As published: token + learned position embeddings; per layer pre-LayerNorm
(eps 1e-5), causal multi-head self-attention (scores scaled by
1/sqrt(head_dim)), residual, pre-LayerNorm, 4x MLP, residual; final
LayerNorm; projection to the vocabulary.

Departures, inherited from the configuration under test (the one decoder
block `paddle_tpu.inference.decode` implements), so that both sides compute
the same function; each is listed under `assumed` in the configuration file:
  * ReLU in the MLP where GPT-2 has gelu_new;
  * an untied output head (`lm_head`) where GPT-2 reuses the embedding;
  * no bias on the q/k/v/o projections; MLP biases b1, b2 kept.
"""

import jax
import jax.numpy as jnp

EPS = 1e-5


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * g + b


def forward(state, tokens, n_layers, n_heads):
    """tokens [T] int32 -> logits [T, vocab]; logits[t] predicts token
    t + 1.  `state` is the artifact's weight dict."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        x = state["embed"][tokens] + state["pos"][:T]
        D = x.shape[-1]
        dh = D // n_heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(n_layers):
            p = "l%d_" % i
            h = _ln(x, state[p + "ln1_g"], state[p + "ln1_b"])
            q = (h @ state[p + "wq"]).reshape(T, n_heads, dh)
            k = (h @ state[p + "wk"]).reshape(T, n_heads, dh)
            v = (h @ state[p + "wv"]).reshape(T, n_heads, dh)
            s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(dh))
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(T, D) @ state[p + "wo"]
            h = _ln(x, state[p + "ln2_g"], state[p + "ln2_b"])
            m = jnp.maximum(h @ state[p + "w1"] + state[p + "b1"], 0.0)
            x = x + m @ state[p + "w2"] + state[p + "b2"]
        return _ln(x, state["lnf_g"], state["lnf_b"]) @ state["lm_head"]
