"""Layer `serve_step`: median of the `ServingMetrics` series `decode`
and `prefill` together over the window, one engine step as the host
sees it hold the device (a step that both prefilled and decoded is in
both)."""

from benchmarks.stats import median


def read(run):
    steps = run["facts"].get("step_s")
    if not steps:
        return None
    return 1e3 * median(steps)
