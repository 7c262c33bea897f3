"""Layer `host_loop`: median of the span `step.dispatch` on the
driving thread of the capture: staging the step's host arrays and the
jit call, up to the moment the step is enqueued."""

from benchmarks import spans
from benchmarks.stats import median


def read(run):
    dispatches = spans.driving_events(run["capture"], "step.dispatch")
    if not dispatches:
        return None
    return median([dur for _, dur in dispatches]) / 1e6
