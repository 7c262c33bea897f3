"""Operations a transformer step requires, from its shapes alone.

Only matrix multiplications count (layer norms, softmax, activation
functions and the optimizer are under 1% at these widths), a multiply-
add is two operations, and nothing recomputed is counted: flash
attention and the fused LM-head loss both re-derive score tiles in
their backward passes, and that work is the kernels' cost, not the
model's.  So an MFU made from these counts understates how busy the
MXU is and moves only when the step really gets faster.
"""

from __future__ import annotations


def transformer_fwd_flops_per_token(*, hidden, layers, ffn, vocab, seq,
                                    head_dense=0, causal=False):
    """Forward operations for one token of a `seq`-token sequence.

    Per layer: the four attention projections (4 h^2) and the two
    feed-forward matrices (2 h f), then the score and value products
    (2 s h each; half of that under a causal mask).  The head is the
    vocabulary projection at every position plus `head_dense` h x h
    transforms in front of it (ERNIE/BERT have one)."""
    attn = 4 * seq * hidden
    if causal:
        attn //= 2
    per_layer = 2 * (4 * hidden * hidden + 2 * hidden * ffn) + attn
    head = 2 * hidden * vocab + head_dense * 2 * hidden * hidden
    return layers * per_layer + head


def transformer_train_flops_per_token(**shape):
    """Forward plus backward (twice the forward: one product for the
    activations' gradient, one for the weights')."""
    return 3 * transformer_fwd_flops_per_token(**shape)


def mfu_percent(flops_per_step, step_seconds, peak_flops, chips=1):
    """Share of the peak the required operations reach, in percent."""
    return 100.0 * flops_per_step / (step_seconds * peak_flops * chips)
