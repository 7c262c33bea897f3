"""What the readers of the training loop's own spans share: the
measured window cut out of the program's span ring, and the loader's
epoch turnovers inside it.

The ring (`paddle_tpu.profiler.events()`) holds, for the whole window
and not only the traced seconds, every `step.device-step` of the
training thread (the enqueue of one compiled step, with `step` and
`in_flight`: the steps dispatched before it that the device had not
finished) and every span of the DataLoader's consumer: `input.close`
(an epoch's workers joined), `input.spawn` (the next epoch's started),
`input.first_batch` (the first take from them), `input.wait` (a
steady-state take) and `input.convert` (host batch to `Tensor`, and the
prefetcher's `device_put`); times in microseconds.

A turnover is what the loader does between an epoch's last batch and
the next epoch's first: its `input.close`, which runs inside the
`next()` that returns the LAST batch, then, behind that batch's step,
the `input.spawn` and every `input.*` span up to the first
`step.device-step` after it (the prefetcher takes and converts two
batches before it yields one).

A program without these spans (the parent of the PR that brought them)
has no `input.close`, and every reader here then finds nothing to
read: None, and no error.
"""

from __future__ import annotations

from benchmarks.harness import say

STEP = "step.device-step"
CLOSE, SPAWN = "input.close", "input.spawn"
STEADY = ("input.wait", "input.convert")


def window(run, metric):
    """{"steps": [event, ...], "input": [event, ...]} of the measured
    window, both in time order: the window's steps are the last
    `facts["steps"]` `step.device-step` events of the thread that
    dispatched the last one; the window opens where the step before
    them ended and closes where the last of them ended, so set-up's
    spawn and the runner's drain after the window fall outside.  None,
    with a line on stderr, when the ring holds fewer steps than that."""
    from paddle_tpu import profiler

    events = profiler.events()
    n = int(run["facts"].get("steps") or 0)
    dispatched = [e for e in events if e["name"] == STEP]
    if not n or not dispatched:
        return None
    tid = dispatched[-1]["tid"]
    dispatched = sorted((e for e in dispatched if e["tid"] == tid),
                        key=lambda e: e["ts"])
    if len(dispatched) < n + 1:
        say(f"metric {metric}: {len(dispatched)} step(s) of the driving "
            f"thread in the span ring, the window's {n} and the one "
            "before them wanted")
        return None
    before, steps = dispatched[-n - 1], dispatched[-n:]
    t0 = before["ts"] + before["dur"]
    t1 = steps[-1]["ts"] + steps[-1]["dur"]
    taken = sorted((e for e in events
                    if e["tid"] == tid and e["name"].startswith("input.")
                    and t0 <= e["ts"] < t1), key=lambda e: e["ts"])
    return {"steps": steps, "input": taken}


def turnovers(win):
    """[{"close": event, "spans": [event, ...], "step": event}, ...],
    one for every `input.close` of the window whose new epoch's first
    step was dispatched inside it: `spans` holds the close, the spawn
    and every `input.*` span from there to that step."""
    found = []
    taken = win["input"]
    for i, close in enumerate(taken):
        if close["name"] != CLOSE:
            continue
        spawn = next((e for e in taken[i + 1:] if e["name"] == SPAWN), None)
        if spawn is None:
            continue
        step = next((s for s in win["steps"] if s["ts"] > spawn["ts"]), None)
        if step is None:
            continue
        found.append({
            "close": close, "step": step,
            "spans": [close] + [e for e in taken
                                if spawn["ts"] <= e["ts"] < step["ts"]]})
    return found


def read_turnovers(run, metric):
    """(window, turnovers) of a run, or None where the window holds no
    whole turnover: a program without the spans, or a window that ended
    before an epoch did."""
    win = window(run, metric)
    if win is None:
        return None
    found = turnovers(win)
    say(f"metric {metric}: {len(found)} loader turnover(s) in a window of "
        f"{len(win['steps'])} step(s)")
    if not found:
        return None
    return win, found
