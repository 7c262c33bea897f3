"""Layer `serve_step`: 99th percentile of the gap between consecutive
tokens of one request (the stamps on its `request.decode` span), over
every gap of every request of the window."""

from benchmarks import spans
from benchmarks.stats import percentile


def read(run):
    found = spans.window_requests(run, "token_gap_p99_ms")
    if found is None:
        return None
    gaps = []
    for request in found.values():
        stamps = request["request.decode"]["token_s"]
        gaps.extend(b - a for a, b in zip(stamps, stamps[1:]))
    return 1e3 * percentile(gaps, 99) if gaps else None
