"""Layer `serve_step`: median of the `ServingMetrics` series `decode`,
one engine step from dispatch to the logits on the host."""

from benchmarks.stats import median


def read(run):
    steps = run["facts"].get("decode_step_s")
    if not steps:
        return None
    return 1e3 * median(steps)
