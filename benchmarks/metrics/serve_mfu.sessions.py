"""Layer `serve_step`: operations the sending window's REAL tokens
required (`flops_hybrid.window_flops`: prompt tokens computed, not
resumed from a snapshot, plus tokens fed back, each through every
layer's projections, SwiGLU and delta rule and, in the full layers,
against its own context; one head row a token sampled; padding columns
count nothing) over the window's seconds and the chip's bf16 peak.
End to end: idle gaps and padding lower it."""

from benchmarks import flops_hybrid


def read(run):
    window = run["facts"].get("window")
    if not window or not window.get("seconds") or not run["peaks"]:
        return None
    need = flops_hybrid.window_flops(
        run["config"]["model"],
        computed_tokens=window["computed_tokens"],
        attn_context_tokens=window["attn_context_tokens"],
        tokens_out=window["tokens_out"])
    return 100.0 * need / (window["seconds"]
                           * run["peaks"]["bf16_flops_per_s"]
                           * run["chips"])
