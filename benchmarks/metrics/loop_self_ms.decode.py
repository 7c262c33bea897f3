"""Layer `host_loop`: what the host does in one iteration of the
serving loop while the device has nothing queued: each `serving.loop`
span of the capture less the `serving.step` inside it (admission,
sampling, the post-step slot loop), mean over the iterations."""

from benchmarks import spans


def read(run):
    loops = spans.driving_events(run["capture"], "serving.loop")
    if not loops:
        return None
    steps = spans.driving_events(run["capture"], "serving.step")
    own = spans.self_times_ns(loops, steps)
    return sum(own) / len(own) / 1e6
