"""What the readers of the program's own spans share.

Two sources.  The profiler capture (`run["capture"]`) holds the serving
loop's spans, `serving.loop`, `serving.step`, `step.dispatch` and the
rest, as host events on the device trace's clock, for the traced
seconds only.  The program's span ring (`paddle_tpu.profiler.events()`)
holds every finished request's three spans for the whole window and the
drain: `request.queue` (with `queue_s`, the very float that went into
the series `queue`), `request.prefill` (with `steps`, the engine steps
from admission to the first token) and `request.decode` (with
`token_s`, the stamp of every token), all three under the request's
`id`, times in microseconds.

A program without these spans (the parent of the PR that brought them)
gives every reader here nothing to read: None, and no error.
"""

from __future__ import annotations

from collections import Counter

from benchmarks import xplane
from benchmarks.harness import say

REQUEST_SPANS = ("request.queue", "request.prefill", "request.decode")


def window_requests(run, metric):
    """{id: {span name: event}} of the requests that took a slot inside
    the measured window and were answered: the population
    `request_p50_ms` is over.  The pinned prompt that set-up steps
    through the engine, and whatever an earlier window of the same
    process served, are told apart by their queue wait: the runner's
    `facts["queue_s"]` holds the window's samples of the series `queue`,
    and a request's `request.queue` span carries the float it added.
    None, with a line on stderr, when fewer are found than the runner
    counted as answered."""
    from paddle_tpu import profiler

    due = Counter(run["facts"].get("queue_s") or ())
    by_id = {}
    for event in profiler.events():
        if event["name"] in REQUEST_SPANS:
            by_id.setdefault(event["id"], {})[event["name"]] = event
    found = {}
    for rid, spans in by_id.items():
        if len(spans) < len(REQUEST_SPANS):
            continue            # the ring dropped part of this request
        wait = spans["request.queue"]["queue_s"]
        if due[wait] > 0:
            due[wait] -= 1
            found[rid] = spans
    answered = int(run["facts"].get("answered", 0))
    say(f"metric {metric}: {len(found)} request(s) of the window in the "
        f"span ring, {answered} answered")
    if not found or len(found) < answered:
        return None
    return found


def first_token_ms(run, metric):
    """Arrival to first token of every request of the window, in ms:
    its `request.queue` plus its `request.prefill`."""
    found = window_requests(run, metric)
    if found is None:
        return None
    return [(s["request.queue"]["dur"] + s["request.prefill"]["dur"]) / 1e3
            for s in found.values()]


def driving_events(capture, name):
    """[(start_ns, dur_ns), ...] of the spans called `name` on the
    thread that drives the device (the one with most `serving.step`),
    in time order."""
    line = xplane.driver_line(capture, "serving.step") if capture else None
    if line is None:
        return []
    names = capture["names"]
    return sorted((s, d) for n, s, d in line["events"] if names[n] == name)


def self_times_ns(outer, inner):
    """For each (start, dur) of `outer`, its duration less the `inner`
    spans that lie inside it; both in time order."""
    out, i = [], 0
    for start, dur in outer:
        end = start + dur
        while i < len(inner) and inner[i][0] < start:
            i += 1
        covered = 0.0
        while i < len(inner) and inner[i][0] + inner[i][1] <= end:
            covered += inner[i][1]
            i += 1
        out.append(dur - covered)
    return out
