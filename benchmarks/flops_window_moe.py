"""Operations a sliding-window + full attention decoder with routed
experts in every layer requires of the chip that serves it, from its
shapes and from what the window really computed.

Only matrix multiplications count and a multiply-add is two operations
(as in `flops.py`, `flops_latent_moe.py` and `flops_hybrid.py`).  What
is counted is what the REAL tokens need: a prompt token served from the
prefix cache and a padding column of a step count nothing.  Attention
is counted over the keys each computed token really admits: every key
up to its own position in a full layer, at most `sliding_window` of
them in a sliding layer (the engine counts both:
`attn_context_tokens`, `attn_window_context_tokens`).  A token passes
through `num_experts_per_tok` experts a layer: the rows of the grouped
product (`expert_rows`), which for this model, every expert held, are
the picks themselves.  Norms, rotary and the gates are elementwise and
count nothing.
"""

from __future__ import annotations


def layer_counts(model):
    """``(sliding layers, full layers)`` among the layers held."""
    kinds = model["layer_types"][:model["num_layers"]]
    return (sum(1 for k in kinds if k == "sliding_attention"),
            sum(1 for k in kinds if k == "full_attention"))


def attention_weights(model):
    """Multiply-adds a token makes in one layer's attention
    projections, either kind: W_q, W_k, W_v and W_o."""
    h, d = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_heads"], model["num_kv_heads"]
    return h * (nh + 2 * nkv) * d + nh * d * h


def router_weights(model):
    return model["hidden_size"] * model["num_experts"]


def linear_flops_per_token(model):
    """Everything a computed token passes through whatever it routes
    to and whatever its context: every layer's attention projections
    and router."""
    return 2 * model["num_layers"] * (attention_weights(model)
                                      + router_weights(model))


def attention_flops_per_pair(model):
    """One query token against one admitted key, all query heads, ONE
    layer: the score and the context over the head size each."""
    return 2 * 2 * model["num_heads"] * model["head_dim"]


def expert_flops_per_row(model):
    """One row of the grouped product: a token through one expert's
    SwiGLU."""
    return 2 * 3 * model["hidden_size"] * model["moe_intermediate_size"]


def head_flops_per_row(model):
    return 2 * model["hidden_size"] * model["vocab_size"]


def window_flops(model, *, computed_tokens, attn_context_tokens,
                 attn_window_context_tokens, expert_rows, tokens_out):
    """What a window's real work required: `computed_tokens` through
    the projections and the routers; `attn_context_tokens` query-key
    pairs in each full layer (each computed token against every key up
    to its own position) and `attn_window_context_tokens` in each
    sliding layer (against at most `sliding_window` of them);
    `expert_rows` rows of the grouped product (all layers together) and
    one head row a token sampled."""
    sliding, full = layer_counts(model)
    return (computed_tokens * linear_flops_per_token(model)
            + (full * attn_context_tokens
               + sliding * attn_window_context_tokens)
            * attention_flops_per_pair(model)
            + expert_rows * expert_flops_per_row(model)
            + tokens_out * head_flops_per_row(model))
