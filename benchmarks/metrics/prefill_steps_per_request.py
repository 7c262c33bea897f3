"""Layer `prefill`: engine steps from a request's admission to its
first token (the count on its `request.prefill` span), mean over the
requests of the window."""

from benchmarks import spans


def read(run):
    found = spans.window_requests(run, "prefill_steps_per_request")
    if found is None:
        return None
    steps = [request["request.prefill"]["steps"]
             for request in found.values()]
    return sum(steps) / len(steps)
