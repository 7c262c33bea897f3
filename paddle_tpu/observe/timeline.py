"""Step timeline: bounded per-phase aggregates + device-time attribution.

The runtime's engines wrap each stage of a step in a named phase::

    host-prep            batch normalization, fault points, key/lr
    h2d                  host->device batch placement (+ offload moves)
    compile              a step call that traces/compiles a new program
    device-step          training: the compiled step's dispatch (the
                         enqueue; closing it would need a sync). Its
                         event carries `step` and `in_flight`, the steps
                         dispatched before it that the device had not
                         finished (their losses' `is_ready()`, no sync):
                         0 is an enqueue onto a device with nothing to
                         do, so the events say where the device ran dry
                         though none of them times a step.
                         serving: the time a step held the device as
                         the host sees it, later of (its dispatch, the
                         previous step's landing) -> its picks on the
                         host, the interval the `decode`/`prefill`
                         series get (folded in with `add`, it has no
                         span of its own)
    anomaly-readback     the guard's host sync at step boundaries
    checkpoint-snapshot  device->host state copy on the step thread
    checkpoint-write     synchronous checkpoint serialization + commit
    checkpoint-write-async  the same, on the background writer thread
    checkpoint-restore   checkpoint load/verify

and the serving loop's thread spans every part of an iteration::

    serving.loop         one working iteration: admit + step
    loop.idle            the wait for a request when no slot is live
    admit                queue -> slots, prefix match, block staging
    sample               host-side token sampling + slot bookkeeping
    draft                the speculative draft phase
    dispatch             staging the step's host arrays + the jit call
    readback             the wait for a step's picks (`device_get`):
                         the step just dispatched, or the one before it
                         when the loop keeps a step in flight
    commit               the post-step slot loop + the metrics calls

and the input pipeline's consumer (parent process only)::

    input.close          an epoch's end: the sentinels to its workers
                         and their joins
    input.spawn          starting one epoch's fork workers: their
                         queues, the forks, the first index lists
    input.first_batch    an iterator's first take, from workers that
                         have only just started (`ready`, as below)
    input.wait           a steady-state take from the worker queue;
                         `ready` = batches the workers had put and the
                         consumer not yet yielded (at 0 the take waits
                         for a worker)
    input.convert        a host batch to `Tensor`s, and the prefetcher's
                         `device_put`

A `phase(name)` context emits the `profiler.RecordEvent` span
`step.<name>`, a `span(name)` context the span `<name>` as it stands;
both land in the chrome trace and, being `TraceAnnotation`s, in a
profiler capture on the device trace's clock. Keyword `fields` ride on
the span's ring event and are the annotation's stats. Both fold the
duration into an O(1) aggregate under `name` here — the aggregate is what
`goodput()` and the Prometheus export read, so the timeline stays
bounded no matter how long the run is.

`attribute(logdir)` closes the loop ROADMAP item 4 asks for: parse the
xplane capture with `profiler.device_op_table` and classify device time
into matmul / attention / collective / elementwise / other buckets —
`Engine.attribute_step()` is the one-call front for it.
"""

from __future__ import annotations

import re
import threading
import time

__all__ = ["StepTimeline", "timeline", "phase", "span", "BUCKETS",
           "classify_op", "attribute", "attribute_rows", "overlap_stats",
           "overlap_report"]


class _Timed:
    """One `RecordEvent` span whose duration is folded into a timeline
    aggregate on exit."""

    __slots__ = ("_timeline", "_name", "_event", "_t0")

    def __init__(self, timeline, name, span, cat, fields):
        from .. import profiler

        self._timeline = timeline
        self._name = name
        self._event = profiler.RecordEvent(span, cat=cat, **fields)

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._event.__enter__()
        return self

    def __exit__(self, *exc):
        self._event.__exit__(*exc)
        self._timeline.add(self._name, time.perf_counter() - self._t0)
        return False


class StepTimeline:
    """Thread-safe phase aggregator: name -> calls/total/max seconds."""

    def __init__(self):
        self._lock = threading.Lock()
        self._agg: dict = {}  # name -> [calls, total_s, max_s]

    def phase(self, name, cat="phase", **fields):
        """Span `step.<name>`, aggregate `name`; `fields` ride on the
        span's ring event and its trace annotation."""
        return _Timed(self, name, f"step.{name}", cat, fields)

    def span(self, name, cat="phase", **fields):
        """Span and aggregate both `name`: for what is no stage of a
        step (the serving loop's iteration, its idle wait, the input
        pipeline's waits)."""
        return _Timed(self, name, name, cat, fields)

    def add(self, name, seconds):
        """Fold an externally-timed duration into a phase aggregate."""
        with self._lock:
            c = self._agg.setdefault(name, [0, 0.0, 0.0])
            c[0] += 1
            c[1] += seconds
            c[2] = max(c[2], seconds)

    def aggregates(self):
        with self._lock:
            return {
                name: {"calls": c[0], "total_s": c[1],
                       "avg_s": c[1] / c[0] if c[0] else 0.0,
                       "max_s": c[2]}
                for name, c in self._agg.items()
            }

    def total(self, name):
        with self._lock:
            c = self._agg.get(name)
            return c[1] if c else 0.0

    def reset(self):
        with self._lock:
            self._agg.clear()


#: process-global timeline every engine reports into
timeline = StepTimeline()
phase = timeline.phase
span = timeline.span


# ---------------------------------------------------------------------------
# device-time attribution (ROADMAP item 4)
# ---------------------------------------------------------------------------

BUCKETS = ("matmul", "attention", "collective", "elementwise", "other")

# runtime-framework events on the xplane are bookkeeping, not ops —
# e.g. "TfrtCpuExecutable::Execute", "PjitFunction(f)", threadpool
# listeners, and our own "step.*" trace annotations
_FRAMEWORK_RE = re.compile(
    r"::|\(|^(ParseArguments|Thread|Thunk|Stream|Xla|TSL|jit_|Infeed|"
    r"Outfeed|program|shard_args|DevicePut|device_put|BufferFrom|"
    r"TransferTo|CopyTo|H2D|D2H|step\.|serving\.|checkpoint\.|train\.)")

# HLO control-flow wrappers: a `call.3` / `while.2` row's duration
# encloses its children, which appear as their own rows — counting the
# wrapper double-counts the body (seen with the remat'd block scan)
_WRAPPER_RE = re.compile(r"^(call|while|conditional)(\.\d+)?$")

# ordered: the first matching bucket wins (softmax -> attention even
# though a fused name may also contain "multiply"; "convert" must not
# hit the matmul "conv" pattern). Collective names are separator-
# tolerant: fusion rows spell them with underscores (`all_gather_fusion`
# vs the plain op's `all-gather.3`)
_BUCKET_RES = (
    ("collective", re.compile(
        r"all[-_]reduce|all[-_]gather|all[-_]to[-_]all|"
        r"reduce[-_]scatter|collective|permute|psum|send|recv")),
    ("attention", re.compile(r"attention|flash|mha|softmax")),
    ("matmul", re.compile(r"dot|conv(?!ert)|gemm|einsum|matmul")),
    ("elementwise", re.compile(
        r"add|sub(?!scribe)|mul|div|max|min|exp|log|tanh|relu|sqrt|"
        r"select|compare|broadcast|reduce|convert|fusion|transpose|"
        r"copy|concat|slice|pad|iota|rng|scatter|gather|clamp|power|"
        r"neg|sign|floor|erf|bitcast|reshape|update|tuple|constant")),
)


def classify_op(name):
    """Bucket one xplane op name, or None for runtime-framework rows."""
    if name.startswith("$") or _FRAMEWORK_RE.search(name):
        return None
    low = name.lower()
    if _WRAPPER_RE.match(low):
        return None
    for bucket, rx in _BUCKET_RES:
        if rx.search(low):
            return bucket
    return "other"


def attribute_rows(rows, top=10):
    """Classify `profiler.device_op_table` rows into the buckets.

    Framework rows (executor/jit shells that enclose the real ops) are
    dropped so bucket totals do not double-count; the report carries
    the top per-op rows for drill-down."""
    buckets = {b: 0.0 for b in BUCKETS}
    ops = []
    for r in rows:
        b = classify_op(r["name"])
        if b is None:
            continue
        buckets[b] += r["total"]
        ops.append({**r, "bucket": b})
    ops.sort(key=lambda r: r["total"], reverse=True)
    total = sum(buckets.values())
    return {
        "buckets": buckets,
        "fractions": {b: (v / total if total else 0.0)
                      for b, v in buckets.items()},
        "total_us": total,
        "top_ops": ops[:top],
    }


def attribute(logdir, top=10):
    """Parse an xplane capture under `logdir` and bucket device time."""
    from .. import profiler

    _, rows = profiler.device_op_table(logdir)
    return attribute_rows(rows, top=top)


def _merge_intervals(ivs):
    """Union of (start, end) intervals, sorted and coalesced."""
    merged = []
    for s, e in sorted(ivs):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covered(iv, merged):
    """Length of interval `iv` covered by the merged union."""
    s, e = iv
    cov = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        cov += min(e, me) - max(s, ms)
    return cov


def overlap_stats(events):
    """Pair collective device time against CONCURRENTLY-RESIDENT compute.

    events: `profiler.device_op_events` rows (per-occurrence intervals
    on the capture's shared clock). Every collective interval is
    intersected with the union of matmul+attention intervals across all
    device lines: the covered part is collective time hidden behind
    compute somewhere on the chip set; the rest is exposed — time the
    interconnect serializes the step. `exposed_collective_frac` (exposed
    collective time over total classified device time) is the headline
    the FLAGS_mp_overlap ring schedule exists to push down."""
    comp, coll = [], []
    compute_us = collective_us = total_us = 0.0
    for e in events:
        b = classify_op(e["name"])
        if b is None:
            continue
        iv = (e["start_us"], e["start_us"] + e["dur_us"])
        total_us += e["dur_us"]
        if b == "collective":
            coll.append(iv)
            collective_us += e["dur_us"]
        elif b in ("matmul", "attention"):
            comp.append(iv)
            compute_us += e["dur_us"]
    merged = _merge_intervals(comp)
    hidden = sum(_covered(iv, merged) for iv in coll)
    exposed = max(collective_us - hidden, 0.0)
    return {
        "collective_us": collective_us,
        "compute_us": compute_us,
        "hidden_collective_us": hidden,
        "exposed_collective_us": exposed,
        "exposed_collective_frac": (exposed / total_us
                                    if total_us else 0.0),
        "collective_share": (collective_us / total_us
                             if total_us else 0.0),
        "total_us": total_us,
    }


def overlap_report(logdir):
    """Parse an xplane capture and report how much collective time hides
    behind concurrently-resident compute (see overlap_stats)."""
    from .. import profiler

    return overlap_stats(profiler.device_op_events(logdir))
