"""Operations a hybrid linear-attention decoder requires of the chip
that serves it, from its shapes and from what the window really
computed.

Only matrix multiplications count and a multiply-add is two operations
(as in `flops.py` and `flops_latent_moe.py`).  What is counted is what
the REAL tokens need: a prompt token served from the prefix cache (its
blocks hit and its state restored from a snapshot) and a padding column
of a step count nothing.  The gated delta rule is counted in its
one-token form, three passes over a head's ``[d_k, d_v]`` state a token
(``k^T S``, the rank-one update, ``S^T q``); the chunked form the step
runs spends more (the intra-chunk triangular system), and that surplus
is not required work.  The depthwise filter, the norms and the gates
are elementwise and count nothing.
"""

from __future__ import annotations


def layer_counts(model):
    """``(linear layers, full layers)`` among the layers held."""
    kinds = model["layer_types"][:model["num_layers"]]
    return (sum(1 for k in kinds if k == "linear_attention"),
            sum(1 for k in kinds if k == "full_attention"))


def linear_mixer_weights(model):
    """Multiply-adds a token makes in one Gated DeltaNet layer's
    projections: W_in (q, k, v), W_gate, W_o and the two decay
    projections."""
    h, nh = model["hidden_size"], model["linear_num_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return h * nh * (2 * dk + dv) + h * nh * dv + nh * dv * h + h * 2 * nh


def full_mixer_weights(model):
    """The same of one full-attention layer: W_in (q, k, v) and W_o."""
    return 4 * model["hidden_size"] ** 2


def swiglu_weights(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def linear_flops_per_token(model):
    """Everything a computed token passes through whatever its
    context: every layer's projections and SwiGLU."""
    linear, full = layer_counts(model)
    return 2 * (linear * linear_mixer_weights(model)
                + full * full_mixer_weights(model)
                + (linear + full) * swiglu_weights(model))


def delta_rule_flops_per_token(model):
    """The recurrence itself, every linear layer: three passes over
    ``heads x d_k x d_v``."""
    linear, _ = layer_counts(model)
    return linear * 2 * 3 * model["linear_num_heads"] \
        * model["linear_key_head_dim"] * model["linear_value_head_dim"]


def attention_flops_per_pair(model):
    """One query token against one cached key, all heads, every FULL
    layer: the score and the context, over the hidden width each."""
    _, full = layer_counts(model)
    return full * 2 * 2 * model["hidden_size"]


def head_flops_per_row(model):
    return 2 * model["hidden_size"] * model["vocab_size"]


def window_flops(model, *, computed_tokens, attn_context_tokens,
                 tokens_out):
    """What a window's real work required: `computed_tokens` through
    the projections, the SwiGLUs and the delta rule,
    `attn_context_tokens` query-key pairs in the full layers (each
    computed token against every key up to its own position) and one
    head row a token sampled."""
    return (computed_tokens * (linear_flops_per_token(model)
                               + delta_rule_flops_per_token(model))
            + attn_context_tokens * attention_flops_per_pair(model)
            + tokens_out * head_flops_per_row(model))
