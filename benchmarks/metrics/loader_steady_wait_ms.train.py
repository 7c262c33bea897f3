"""Layer `input`: host time a step spends on the loader outside a
turnover: the window's spans `input.wait` (a steady-state take) and
`input.convert` that belong to no turnover, over the window's steps."""

from benchmarks import train_spans


def read(run):
    found = train_spans.read_turnovers(run, "loader_steady_wait_ms.train")
    if found is None:
        return None
    win, turns = found
    turning = {id(e) for turn in turns for e in turn["spans"]}
    steady = sum(e["dur"] for e in win["input"]
                 if e["name"] in train_spans.STEADY
                 and id(e) not in turning)
    return steady / 1e3 / len(win["steps"])
