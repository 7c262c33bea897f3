"""Layer `prefill`: median time from a turn's arrival at the engine to
its first token (its spans `request.queue` + `request.prefill`), over
the turns of the window, drain included: what the snapshot restore and
the prefill of the turn's new tokens take."""

from benchmarks import spans
from benchmarks.stats import percentile


def read(run):
    waits = spans.first_token_ms(run, "first_token_p50_ms.sessions")
    return percentile(waits, 50) if waits else None
