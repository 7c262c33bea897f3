"""Layer `input`: the span `input.close` of the window's loader
turnovers, mean: the half of a turnover that runs inside the `next()`
that returns an epoch's last batch, straight after a loss read in the
pre-training cell, so with nothing queued on the device."""

from statistics import fmean

from benchmarks import train_spans


def read(run):
    found = train_spans.read_turnovers(run, "loader_close_ms.train")
    if found is None:
        return None
    return fmean(turn["close"]["dur"] / 1e3 for turn in found[1])
