"""Layer `prefill`: median time from a request's arrival to its first
token (its spans `request.queue` + `request.prefill`), over the
requests of the window, both classes, drain included."""

from benchmarks import spans
from benchmarks.stats import percentile


def read(run):
    waits = spans.first_token_ms(run, "first_token_p50_ms.assist")
    return percentile(waits, 50) if waits else None
