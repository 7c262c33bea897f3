"""Layer `prefill`: 90th percentile of the time from a request's
arrival to its first token (its spans `request.queue` +
`request.prefill`), over the requests of the window, drain included."""

from benchmarks import spans
from benchmarks.stats import percentile


def read(run):
    waits = spans.first_token_ms(run, "first_token_p90_ms")
    return percentile(waits, 90) if waits else None
