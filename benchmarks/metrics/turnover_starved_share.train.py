"""Layer `device`: of the window's loader turnovers, the share whose
first `step.device-step` after `input.spawn` was dispatched with
`in_flight` 0: the step queued behind the turnover ran out before the
new epoch's first batch was there, and the device waited for the host."""

from benchmarks import train_spans


def read(run):
    found = train_spans.read_turnovers(run, "turnover_starved_share.train")
    if found is None:
        return None
    flying = [turn["step"].get("in_flight") for turn in found[1]]
    if None in flying:
        return None
    return 100.0 * sum(1 for n in flying if n == 0) / len(flying)
