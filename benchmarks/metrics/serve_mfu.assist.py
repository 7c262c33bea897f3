"""Layer `serve_step`: operations the sending window's REAL tokens
required (`flops_window_moe.window_flops`: prompt tokens computed, not
hit, plus tokens fed back, each against the keys its layer kind admits,
at most 1,024 in a sliding layer; 8 expert rows a token a layer; one
head row a token sampled; padding columns count nothing) over the
window's seconds and the chip's bf16 peak.  End to end: idle gaps and
padding lower it."""

from benchmarks import flops_window_moe

COUNTERS = ("computed_tokens", "attn_context_tokens",
            "attn_window_context_tokens", "expert_rows", "tokens_out")


def read(run):
    window = run["facts"].get("window")
    if not window or not window.get("seconds") or not run["peaks"] \
            or any(window.get(k) is None for k in COUNTERS):
        return None
    need = flops_window_moe.window_flops(
        run["config"]["model"], **{k: window[k] for k in COUNTERS})
    return 100.0 * need / (window["seconds"]
                           * run["peaks"]["bf16_flops_per_s"]
                           * run["chips"])
