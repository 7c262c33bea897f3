"""Layer `admission`: 90th percentile of the `ServingMetrics` series
`queue` (arrival to a slot), over the requests of the window."""

from benchmarks.stats import percentile


def read(run):
    waits = run["facts"].get("queue_s")
    if not waits:
        return None
    return 1e3 * percentile(waits, 90)
