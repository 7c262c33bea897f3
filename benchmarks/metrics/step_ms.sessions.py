"""Layer `serve_step`: median of the `ServingMetrics` series `decode`
and `prefill` together, one engine step from dispatch to the picks on
the host (a step that both prefilled and decoded is in both), over the
window and its drain."""

from benchmarks.stats import median


def read(run):
    steps = run["facts"].get("step_s")
    if not steps:
        return None
    return 1e3 * median(steps)
