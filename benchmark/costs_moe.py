"""Operations and bytes a routed-expert FFN NEEDS, from its shapes and its
routing alone (`moe_ffn_roofline`).  A file of its own beside costs.py,
which a PR that adds a configuration may not edit; `costs.roofline_seconds`
turns the pair into the least possible time."""


def moe_ffn_cost(tokens, experts_touched, d_model, expert_width, n_experts,
                 experts_per_token, weight_bytes=4, act_bytes=4):
    """One layer's routed SwiGLU FFN over `tokens` rows, `experts_touched`
    distinct experts of which received at least one.

    FLOPs: gate, up and down projections, 2 * D * F multiply-adds each, per
    (token, expert) pair; the router's 2 * D * E per token.  The SiLU, the
    product, the softmax and the top-k are lower-order and not counted.
    Bytes: the three matrices (3 * D * F) of every TOUCHED expert read
    once - an expert no token chose need not be read, and one that several
    tokens chose need be read once; the router's matrix; each token's row
    read once and its result written once.  A form that reads every expert,
    or a touched expert once per pair, is charged for it by its time."""
    pairs = float(tokens) * experts_per_token
    flops = (pairs * 3.0 * 2.0 * d_model * expert_width
             + float(tokens) * 2.0 * d_model * n_experts)
    bytes_ = (float(experts_touched) * 3.0 * d_model * expert_width
              * weight_bytes
              + d_model * n_experts * weight_bytes
              + float(tokens) * d_model * 2.0 * act_bytes)
    return flops, bytes_
