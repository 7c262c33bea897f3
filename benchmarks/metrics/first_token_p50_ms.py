"""Layer `prefill`: median time from a request's arrival to its first
token (its spans `request.queue` + `request.prefill`), over the
requests of the window, drain included.  Answers come whole: the first
token's stamp is when a streaming front would have had it."""

from benchmarks import spans
from benchmarks.stats import percentile


def read(run):
    waits = spans.first_token_ms(run, "first_token_p50_ms")
    return percentile(waits, 50) if waits else None
