"""Operations a latent-attention + held-experts decoder requires of
the chip that serves it, from its shapes and from what the window
really computed.

Only matrix multiplications count and a multiply-add is two operations
(as in `flops.py`).  What is counted is what the REAL tokens need:
a prompt token served from the prefix cache, a padding column of a
step, and a pick that lands on an expert another chip holds count
nothing.  Attention is counted in the absorbed form the serving step
runs (per query-key pair: the score over the 576-wide cache row and the
context over its 512-wide latent part, for every head); the expanded
form would count 2 x heads x (192 + 128) a pair plus the expansion of
every key by W_kvb (PERF.md section 7).
"""

from __future__ import annotations


def linear_flops_per_token(model):
    """Everything a computed token passes through whatever it routes
    to: the attention projections of every layer (W_q, W_kva, the
    absorption of W_kvb's key half into the query and of its value
    half out of the context, W_o), the dense layers' SwiGLU, and in
    every expert layer the router and the shared expert."""
    h, nh = model["hidden_size"], model["num_heads"]
    r, dn = model["kv_lora_rank"], model["qk_nope_head_dim"]
    dr, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    attention = h * nh * (dn + dr) + h * (r + dr) + nh * dn * r \
        + nh * r * dv + nh * dv * h
    dense = model["first_k_dense_replace"]
    moe = model["num_layers"] - dense
    per_expert_layer = h * model["router_experts"] \
        + 3 * h * model["moe_intermediate_size"] \
        * model["num_shared_experts"]
    return 2 * (model["num_layers"] * attention
                + dense * 3 * h * model["intermediate_size"]
                + moe * per_expert_layer)


def attention_flops_per_pair(model):
    """One query token against one cached key, all heads, all layers:
    the score over ``[latent | rope]`` and the context over the latent
    part."""
    r, dr = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return 2 * model["num_layers"] * model["num_heads"] * ((r + dr) + r)


def expert_flops_per_row(model):
    """One row of the grouped product: a token through one held
    expert's SwiGLU."""
    return 2 * 3 * model["hidden_size"] * model["moe_intermediate_size"]


def head_flops_per_row(model):
    return 2 * model["hidden_size"] * model["vocab_size"]


def window_flops(model, *, computed_tokens, attn_context_tokens,
                 expert_rows, tokens_out):
    """What a window's real work required: `computed_tokens` through
    the linear parts, `attn_context_tokens` query-key pairs (each
    computed token against every key up to its own position),
    `expert_rows` rows of the grouped product (all expert layers
    together) and one head row a token sampled."""
    return (computed_tokens * linear_flops_per_token(model)
            + attn_context_tokens * attention_flops_per_pair(model)
            + expert_rows * expert_flops_per_row(model)
            + tokens_out * head_flops_per_row(model))
