"""Layer `input`: host time of one loader turnover, mean over the
window's turnovers: the span `input.close` (the old epoch's workers
joined, inside the `next()` that returns its last batch) plus, behind
that batch's step, `input.spawn`, `input.first_batch` and the
`input.convert`s and the take before the new epoch's first batch is
yielded.  From the program's span ring, over the whole window."""

from statistics import fmean

from benchmarks import train_spans


def read(run):
    found = train_spans.read_turnovers(run, "loader_turnover_ms.train")
    if found is None:
        return None
    return fmean(sum(e["dur"] for e in turn["spans"]) / 1e3
                 for turn in found[1])
