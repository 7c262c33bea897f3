"""Profiler: host-side event recording + device trace capture.

Ref parity: paddle/fluid/platform/profiler.h (RecordEvent RAII, event
aggregation), platform/device_tracer.cc (CUPTI device tracing),
python/paddle/fluid/profiler.py:190 (profiler context + summary table),
tools/timeline.py (chrome-trace export). TPU-native mapping:

- RecordEvent           -> host wall-clock spans (thread-aware), doubling
                           as jax.profiler.TraceAnnotation so annotations
                           show up inside XProf device traces
- DeviceTracer/CUPTI    -> jax.profiler.start_trace/stop_trace (XProf
                           xplane capture; the PJRT runtime records device
                           ops — no CUPTI analogue needed)
- profiler.profiler ctx -> profiler.profile(...)
- tools/timeline.py     -> export_chrome_tracing(path) from host events
- op-time table         -> summary() — per-op totals/avg/max/min, fed by
                           dispatch instrumentation (enable_op_profiling)
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time

__all__ = [
    "RecordEvent", "record_span", "RecordMemEvent", "enable_op_profiling",
    "disable_op_profiling", "is_op_profiling_enabled", "reset", "events",
    "mem_events", "record_device_memory", "summary", "percentiles",
    "export_chrome_tracing", "profile", "start_trace", "stop_trace",
    "device_op_table", "device_op_events",
]

# rolling windows: the always-on step timeline (paddle_tpu.observe)
# records a handful of spans per train/decode step, so an unbounded
# list would leak over a long run — keep the newest spans only (same
# policy as serving.metrics' latency windows)
_MAX_EVENTS = 100_000
_MAX_MEM_EVENTS = 10_000

_lock = threading.Lock()
_events: collections.deque = collections.deque(maxlen=_MAX_EVENTS)
_op_profiling = False
_tls = threading.local()


def _now_us():
    return time.perf_counter_ns() / 1000.0


class RecordEvent:
    """Named host-side span (ref platform/profiler.h RecordEvent).

    Context manager; nests. Also emits a jax TraceAnnotation so the name
    appears in XProf device timelines captured via start_trace.

    `fields` ride along on the ring event, as `record_span`'s do, and
    are the annotation's keyword stats, so a capture shows them on the
    device trace's clock."""

    def __init__(self, name, cat="host", **fields):
        self.name = name
        self.cat = cat
        self.fields = fields
        self._t0 = None
        self._jax_ann = None

    def __enter__(self):
        depth = getattr(_tls, "depth", 0)
        _tls.depth = depth + 1
        self._t0 = _now_us()
        try:
            import jax.profiler as jp

            self._jax_ann = jp.TraceAnnotation(self.name, **self.fields)
            self._jax_ann.__enter__()
        except Exception:
            self._jax_ann = None
        return self

    def __exit__(self, *exc):
        if self._jax_ann is not None:
            self._jax_ann.__exit__(*exc)
        dur = _now_us() - self._t0
        _tls.depth -= 1
        ev = {"name": self.name, "cat": self.cat, "ts": self._t0,
              "dur": dur, "tid": threading.get_ident(),
              "depth": _tls.depth}
        ev.update(self.fields)
        with _lock:
            _events.append(ev)
        return False


def record_span(name, start_s, dur_s, cat="host", **fields):
    """Put an interval known only after the fact straight into the
    ring: a request's queue wait, prefill and decode cross many engine
    steps and many other requests' lives, so they cannot be nested
    `RecordEvent`s (and get no TraceAnnotation). `start_s` is on
    `time.perf_counter()`'s clock, like every span's start. `fields`
    ride along on the event; spans that belong together share an
    `id`, and `export_chrome_tracing` pairs those up as async events."""
    ev = {"name": name, "cat": cat, "ts": start_s * 1e6,
          "dur": dur_s * 1e6, "tid": threading.get_ident(), "depth": 0}
    ev.update(fields)
    with _lock:
        _events.append(ev)


_mem_events: collections.deque = collections.deque(maxlen=_MAX_MEM_EVENTS)


class RecordMemEvent:
    """Memory event (ref platform/profiler.proto:38 MemEvent): a named
    allocation/deallocation or snapshot with byte counts and place.
    Usable directly (`RecordMemEvent("alloc", bytes=..., place=...)`)
    or via record_device_memory() snapshots."""

    def __init__(self, annotation, *, bytes=0, place=None, kind="alloc",
                 extra=None):
        ev = {
            "annotation": annotation, "kind": kind,
            "bytes": int(bytes), "place": str(place or "device:0"),
            "ts": _now_us(), "tid": threading.get_ident(),
        }
        if extra:
            ev.update(extra)
        with _lock:
            _mem_events.append(ev)


def record_device_memory(annotation="snapshot", device=None):
    """Snapshot the device's MEASURED memory (device.memory_stats) as a
    MemEvent and roll the high-watermark into framework.monitor
    (STAT_ADD analogue of the reference's GPU mem stat)."""
    from ..device import memory_stats
    from ..framework import monitor

    stats = memory_stats(device)
    in_use = int(stats.get("bytes_in_use", 0))
    peak = int(stats.get("peak_bytes_in_use", -1))
    RecordMemEvent(annotation, bytes=in_use, kind="snapshot",
                   place="device", extra={
                       "peak_bytes_in_use": peak,
                       "host_bytes_in_use":
                           int(stats.get("host_bytes_in_use", 0)),
                   })
    monitor.stat_max("device_mem_bytes_in_use_peak",
                     peak if peak >= 0 else in_use)
    return stats


def reset():
    with _lock:
        _events.clear()
        _mem_events.clear()


def events():
    with _lock:
        return list(_events)


def mem_events():
    with _lock:
        return list(_mem_events)


def enable_op_profiling():
    """Record a span per dispatched op (ref imperative/profiler.cc)."""
    global _op_profiling
    _op_profiling = True


def disable_op_profiling():
    global _op_profiling
    _op_profiling = False


def is_op_profiling_enabled():
    return _op_profiling


@contextlib.contextmanager
def profile(*, op_detail=True, trace_dir=None):
    """Profiler scope (ref fluid/profiler.py:257 profiler ctx).

    op_detail: record per-op dispatch spans for summary().
    trace_dir: also capture an XProf device trace there."""
    reset()
    if op_detail:
        enable_op_profiling()
    if trace_dir:
        start_trace(trace_dir)
    try:
        yield
    finally:
        if trace_dir:
            stop_trace()
        if op_detail:
            disable_op_profiling()


def summary(sorted_by="total", limit=None):
    """Aggregate events by name into the reference's op-time table
    (fluid/profiler.py:190 print_profiler). Returns the table string."""
    agg: dict[str, list[float]] = {}
    for e in events():
        agg.setdefault(e["name"], []).append(e["dur"])
    rows = []
    for name, durs in agg.items():
        rows.append({
            "name": name, "calls": len(durs), "total": sum(durs),
            "avg": sum(durs) / len(durs), "max": max(durs),
            "min": min(durs),
        })
    key = {"total": "total", "calls": "calls", "avg": "avg",
           "max": "max", "min": "min"}.get(sorted_by, "total")
    rows.sort(key=lambda r: r[key], reverse=True)
    if limit:
        rows = rows[:limit]
    lines = [
        f"{'Event':<40}{'Calls':>8}{'Total(us)':>14}{'Avg(us)':>12}"
        f"{'Max(us)':>12}{'Min(us)':>12}"
    ]
    lines.append("-" * len(lines[0]))
    for r in rows:
        lines.append(
            f"{r['name'][:39]:<40}{r['calls']:>8}{r['total']:>14.1f}"
            f"{r['avg']:>12.1f}{r['max']:>12.1f}{r['min']:>12.1f}")
    mems = mem_events()
    if mems:
        # device-memory section (ref fluid/profiler.py mem table /
        # profiler.proto MemEvent): measured snapshots, peak first
        lines.append("")
        lines.append("Device memory (measured)")
        lines.append(f"{'Annotation':<32}{'Kind':>10}{'Bytes':>16}"
                     f"{'Peak':>16}{'HostBytes':>14}")
        lines.append("-" * 88)
        peak_all = max((m.get("peak_bytes_in_use", -1) for m in mems),
                       default=-1)
        in_use_max = max((m["bytes"] for m in mems), default=0)
        host_max = max((m.get("host_bytes_in_use", 0) for m in mems),
                       default=0)
        for m in mems[-20:]:
            lines.append(
                f"{m['annotation'][:31]:<32}{m['kind']:>10}"
                f"{m['bytes']:>16}"
                f"{m.get('peak_bytes_in_use', -1):>16}"
                f"{m.get('host_bytes_in_use', 0):>14}")
        lines.append(
            f"{'== high watermark ==':<32}{'':>10}{in_use_max:>16}"
            f"{peak_all:>16}{host_max:>14}")
    return "\n".join(lines)


def percentiles(name, ps=(50, 95, 99)):
    """Latency percentiles (microseconds) over the recorded host spans
    named `name` — {p: duration_us} with linear interpolation (numpy's
    'linear' method). The serving runtime computes its p50/p95/p99
    through this over its per-request/per-step RecordEvent spans."""
    from ..utils import stats as _stats

    durs = [e["dur"] for e in events() if e["name"] == name]
    if not durs:
        raise ValueError(f"no recorded events named {name!r}")
    return _stats.percentiles(durs, ps)


def export_chrome_tracing(path):
    """Write host events as a chrome://tracing JSON file
    (ref tools/timeline.py).

    Spans are sorted by start time and carry their recorded nesting
    `depth` (spans land in `_events` at EXIT, so inner spans precede
    their parents in recording order — the sort restores enclosure
    order so chrome stacks nested spans correctly). `record_span`
    intervals that carry an `id` become async pairs. Memory events are
    emitted as counter (``ph:"C"``) rows so the measured
    bytes-in-use/peak series renders as a track under the spans."""
    pid = os.getpid()
    trace_events = []
    for e in sorted(events(), key=lambda e: (e["tid"], e["ts"])):
        row = {"name": e["name"], "cat": e["cat"], "ts": e["ts"],
               "pid": pid, "tid": e["tid"]}
        if "id" in e:
            # `record_span` intervals sharing an id (one request's
            # life) overlap other ids' on the recording thread: chrome
            # draws async begin/end pairs on a track per id
            trace_events.append(dict(row, ph="b", id=e["id"]))
            trace_events.append(dict(row, ph="e", id=e["id"],
                                     ts=e["ts"] + e["dur"]))
        else:
            trace_events.append(dict(
                row, ph="X", dur=e["dur"],
                args={"depth": e.get("depth", 0)}))
    for m in mem_events():
        args = {"bytes_in_use": m["bytes"]}
        if "host_bytes_in_use" in m:
            args["host_bytes_in_use"] = m["host_bytes_in_use"]
        if m.get("peak_bytes_in_use", -1) >= 0:
            args["peak_bytes_in_use"] = m["peak_bytes_in_use"]
        trace_events.append({
            "name": f"memory ({m['place']})", "cat": "memory", "ph": "C",
            "ts": m["ts"], "pid": pid, "tid": 0, "args": args,
        })
    trace = {"traceEvents": trace_events, "displayTimeUnit": "ms"}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


# -- device (XProf) trace ----------------------------------------------------


def device_op_table(logdir, top=None, sorted_by="total"):
    """Per-op DEVICE-TIME table from an XProf capture (ref
    platform/device_tracer.cc — the reference correlates CUPTI device
    spans per op; here the xplane.pb the PJRT runtime wrote is parsed
    directly with the wire-format reader, no tensorboard needed).

    Aggregates every event on the device planes ("/device:..." when an
    accelerator recorded; "/host:CPU" as the fallback on the host
    backend) by op name: calls / total / avg / max (microseconds).
    Returns (table_string, rows)."""
    import glob as _glob

    from ..utils.protowire import fields

    paths = sorted(_glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    agg: dict[str, list[float]] = {}

    def plane_name(buf):
        for f, w, v in fields(buf):
            if f == 2 and w == 2:
                return v.decode(errors="replace")
        return ""

    def walk_plane(buf):
        meta = {}
        for f, w, v in fields(buf):
            if f == 4 and w == 2:          # event_metadata map entry
                mid, name = None, None
                for f2, w2, v2 in fields(v):
                    if f2 == 1 and w2 == 0:
                        mid = v2
                    elif f2 == 2 and w2 == 2:  # XEventMetadata
                        for f3, w3, v3 in fields(v2):
                            if f3 == 1 and w3 == 0:
                                mid = v3
                            elif f3 == 2 and w3 == 2:
                                name = v3.decode(errors="replace")
                if mid is not None and name:
                    meta[mid] = name
        for f, w, v in fields(buf):
            if f != 3 or w != 2:           # XLine
                continue
            for f2, w2, v2 in fields(v):
                if f2 != 4 or w2 != 2:     # XEvent
                    continue
                mid, dur = None, 0
                for f3, w3, v3 in fields(v2):
                    if f3 == 1 and w3 == 0:
                        mid = v3
                    elif f3 == 3 and w3 == 0:
                        dur = v3               # picoseconds
                name = meta.get(mid)
                if name and not name.startswith("$"):
                    # "$file:line fn" entries are python-frame spans on
                    # the host plane, not ops
                    agg.setdefault(name, []).append(dur / 1e6)  # -> us

    for path in paths:
        with open(path, "rb") as f:
            space = f.read()
        planes = [v for fno, w, v in fields(space) if fno == 1 and w == 2]
        device = [p for p in planes if plane_name(p).startswith("/device:")]
        for p in device or [p for p in planes
                            if plane_name(p) == "/host:CPU"]:
            walk_plane(p)

    rows = [{"name": n, "calls": len(d), "total": sum(d),
             "avg": sum(d) / len(d), "max": max(d)}
            for n, d in agg.items()]
    key = {"total": "total", "calls": "calls", "avg": "avg",
           "max": "max"}.get(sorted_by, "total")
    rows.sort(key=lambda r: r[key], reverse=True)
    if top:
        rows = rows[:top]
    lines = [f"{'Device op':<52}{'Calls':>8}{'Total(us)':>14}"
             f"{'Avg(us)':>12}{'Max(us)':>12}"]
    lines.append("-" * len(lines[0]))
    for r in rows:
        lines.append(
            f"{r['name'][:51]:<52}{r['calls']:>8}{r['total']:>14.1f}"
            f"{r['avg']:>12.1f}{r['max']:>12.1f}")
    return "\n".join(lines), rows


def device_op_events(logdir):
    """Per-event DEVICE intervals from an XProf capture: a flat list of
    ``{name, line, start_us, dur_us}`` rows with start times absolute
    within the capture (XLine.timestamp_ns + XEvent.offset_ps — one
    shared clock across the capture's lines). Where `device_op_table`
    aggregates totals per op name, this keeps every occurrence so the
    overlap report can intersect collective intervals with the
    concurrently-resident compute intervals."""
    import glob as _glob

    from ..utils.protowire import fields

    paths = sorted(_glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    out = []

    def plane_name(buf):
        for f, w, v in fields(buf):
            if f == 2 and w == 2:
                return v.decode(errors="replace")
        return ""

    def walk_plane(buf):
        meta = {}
        for f, w, v in fields(buf):
            if f == 4 and w == 2:          # event_metadata map entry
                mid, name = None, None
                for f2, w2, v2 in fields(v):
                    if f2 == 1 and w2 == 0:
                        mid = v2
                    elif f2 == 2 and w2 == 2:  # XEventMetadata
                        for f3, w3, v3 in fields(v2):
                            if f3 == 1 and w3 == 0:
                                mid = v3
                            elif f3 == 2 and w3 == 2:
                                name = v3.decode(errors="replace")
                if mid is not None and name:
                    meta[mid] = name
        for f, w, v in fields(buf):
            if f != 3 or w != 2:           # XLine
                continue
            line_name, ts_ns = "", 0
            evs = []
            for f2, w2, v2 in fields(v):
                if f2 == 2 and w2 == 2:
                    line_name = v2.decode(errors="replace")
                elif f2 == 3 and w2 == 0:
                    ts_ns = v2
                elif f2 == 4 and w2 == 2:  # XEvent
                    evs.append(v2)
            for ev in evs:
                mid, off_ps, dur_ps = None, 0, 0
                for f3, w3, v3 in fields(ev):
                    if f3 == 1 and w3 == 0:
                        mid = v3
                    elif f3 == 2 and w3 == 0:
                        off_ps = v3            # picoseconds
                    elif f3 == 3 and w3 == 0:
                        dur_ps = v3            # picoseconds
                name = meta.get(mid)
                if name and not name.startswith("$"):
                    out.append({
                        "name": name, "line": line_name,
                        "start_us": ts_ns / 1e3 + off_ps / 1e6,
                        "dur_us": dur_ps / 1e6,
                    })

    for path in paths:
        with open(path, "rb") as f:
            space = f.read()
        planes = [v for fno, w, v in fields(space) if fno == 1 and w == 2]
        device = [p for p in planes if plane_name(p).startswith("/device:")]
        for p in device or [p for p in planes
                            if plane_name(p) == "/host:CPU"]:
            walk_plane(p)
    return out


def start_trace(logdir):
    """Capture an XProf/xplane device trace (ref device_tracer.cc — here
    the PJRT runtime does the recording)."""
    import jax.profiler as jp

    jp.start_trace(logdir)


def stop_trace():
    import jax.profiler as jp

    jp.stop_trace()
