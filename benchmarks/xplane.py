"""From a profiler capture to numbers: device busy time, the train
step's device time, op groups, and idle gaps named by what the host
was doing.

`load_capture` turns the profiler's `.xplane.pb` into a plain dict (the
"capture"); every reduction below works on that dict, so the arithmetic
is tested on the CPU against a cut-down piece of two real v5e captures
(tests/benchmark/fixtures).

    {"names": [str, ...],
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[name_id, start_ns, dur_ns], ...],      # "XLA Ops"
                  "modules": [[name_id, start_ns, dur_ns], ...]}],  # "XLA Modules"
     "host": [{"name": "python3", "events": [[name_id, start_ns, dur_ns], ...]}]}

All lines of one capture share one clock.  On the chip an op's name is
its whole HLO line (hundreds of characters); `op_label` cuts it to
opcode and output shape.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from .stats import median

# the tracer thread's own span: its interval is the traced window
WINDOW_SPAN = "bench.trace_window"
MIN_GAP_NS = 20_000
LABEL_MAX = 80


def load_capture(logdir):
    """Read the newest `.xplane.pb` under `logdir`, or None if there is
    none.  Python-tracer events (names starting with `$`) are dropped:
    host spans are the program's and JAX's own annotations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    names, index = [], {}

    def nid(name):
        if name not in index:
            index[name] = len(names)
            names.append(name)
        return index[name]

    def events(line, keep=lambda name: True):
        return [[nid(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events if keep(e.name)]

    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = events(line)
                elif line.name == "XLA Modules":
                    dev["modules"] = events(line)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = events(line, lambda n: not n.startswith("$"))
                if evs:
                    host.append({"name": line.name, "events": evs})
    return {"names": names, "devices": devices, "host": host}


# -- the window and device busy time ---------------------------------------


def window(capture):
    """(start_ns, end_ns) of the traced window: the tracer thread's
    `bench.trace_window` span, or the extent of the device ops when a
    capture has none."""
    names = capture["names"]
    for line in capture["host"]:
        for n, s, d in line["events"]:
            if names[n] == WINDOW_SPAN:
                return s, s + d
    starts = [s for dev in capture["devices"] for _, s, _ in dev["ops"]]
    ends = [s + d for dev in capture["devices"] for _, s, d in dev["ops"]]
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def busy_intervals(device, t0, t1):
    """Union of the device's op intervals, clipped to [t0, t1]."""
    spans = sorted((max(s, t0), min(s + d, t1))
                   for _, s, d in device["ops"] if s < t1 and s + d > t0)
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def device_summary(capture):
    """{"busy_s", "window_s"}: seconds in which an op ran, averaged
    over the devices that ran any, and the traced window's length."""
    t0, t1 = window(capture)
    busy = []
    for dev in capture["devices"]:
        total = sum(b - a for a, b in busy_intervals(dev, t0, t1))
        if total > 0:
            busy.append(total)
    if not busy or t1 <= t0:
        return None
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (t1 - t0) / 1e9}


def idle_share_percent(capture):
    dev = device_summary(capture) if capture else None
    if dev is None:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


# -- ops --------------------------------------------------------------------

_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(hlo):
    """`%copy.422 = bf16[16,16,128]{2,1,0:T(8,128)} copy(...)` ->
    `copy bf16[16,16,128]`; a Mosaic kernel (a `custom-call` whose
    target is `tpu_custom_call`) is named `tpu_custom_call`.  A name
    that is no HLO line is kept, cut to length."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:LABEL_MAX]
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):            # tuple-shaped output
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.split("(", 1)[0].strip() or "op"
    if is_mosaic(hlo):
        opcode = "tpu_custom_call"
    return f"{opcode} {shape}"[:LABEL_MAX]


def is_mosaic(hlo):
    return "tpu_custom_call" in hlo


def op_seconds(capture, keep=lambda hlo: True):
    """Device seconds of the ops `keep` accepts, inside the window,
    summed over devices."""
    t0, t1 = window(capture)
    names = capture["names"]
    keep_id = {}
    total = 0.0
    for dev in capture["devices"]:
        for n, s, d in dev["ops"]:
            if s >= t1 or s + d <= t0:
                continue
            if n not in keep_id:
                keep_id[n] = keep(names[n])
            if keep_id[n]:
                total += d
    return total / 1e9


def op_groups(capture, top=10):
    """[[label, seconds], ...], longest first: ops merged by opcode and
    output shape, ` xN` counting the distinct instructions merged."""
    t0, t1 = window(capture)
    names = capture["names"]
    secs, members = defaultdict(float), defaultdict(set)
    labels = {}
    for dev in capture["devices"]:
        for n, s, d in dev["ops"]:
            if s >= t1 or s + d <= t0:
                continue
            if n not in labels:
                labels[n] = op_label(names[n])
            secs[labels[n]] += d
            members[labels[n]].add(n)
    rows = sorted(secs.items(), key=lambda kv: -kv[1])[:top]
    out = []
    for label, ns in rows:
        count = len(members[label])
        if count > 1:
            suffix = f" x{count}"
            label = label[:LABEL_MAX - len(suffix)] + suffix
        out.append([label, ns / 1e9])
    return out


def main_module_seconds(capture):
    """Device durations (seconds) of the module that took most of the
    device's time: the train step, not the small programs around it."""
    t0, t1 = window(capture)
    per = defaultdict(list)
    for dev in capture["devices"]:
        for n, s, d in dev["modules"]:
            if s >= t0 and s + d <= t1:
                per[n].append(d / 1e9)
    if not per:
        return []
    return max(per.values(), key=sum)


def main_module_median_s(capture):
    durs = main_module_seconds(capture)
    return median(durs) if durs else None


# -- idle gaps --------------------------------------------------------------


def driver_line(capture, span_name):
    """The host line (thread) that drives the device: the one with
    most events named `span_name`."""
    names = capture["names"]
    best, best_n = None, 0
    for line in capture["host"]:
        n = sum(1 for e in line["events"] if names[e[0]] == span_name)
        if n > best_n:
            best, best_n = line, n
    return best


def innermost_segments(events, names):
    """Flatten nested spans of one thread into non-overlapping
    [start, end, name] segments, each named by the innermost span."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [end, name]

    def emit(a, b, name):
        if b > a:
            out.append([a, b, name])

    cursor = None
    for n, s, d in evs:
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            emit(cursor, end, name)
            cursor = end
        if stack:
            emit(cursor, s, stack[-1][1])
        cursor = s
        stack.append([s + d, names[n]])
    while stack:
        end, name = stack.pop()
        emit(cursor, end, name)
        cursor = max(cursor, end)
    return out


def idle_gaps(capture, span_name, top=10):
    """[[name, seconds], ...]: the device's idle time inside the window
    by the innermost span the driving thread was in; time under no
    span is `unattributed`.  Gaps under 20 us are pooled."""
    t0, t1 = window(capture)
    devs = [d for d in capture["devices"] if d["ops"]]
    if not devs or t1 <= t0:
        return []
    busy = busy_intervals(devs[0], t0, t1)
    gaps, edge = [], t0
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if t1 > edge:
        gaps.append((edge, t1))
    line = driver_line(capture, span_name)
    segs = innermost_segments(line["events"], capture["names"]) \
        if line else []
    totals = defaultdict(float)
    i = 0
    for a, b in gaps:
        if b - a < MIN_GAP_NS:
            totals["gaps under 20 us"] += b - a
            continue
        covered = 0.0
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            lo, hi = max(segs[j][0], a), min(segs[j][1], b)
            if hi > lo:
                totals[segs[j][2][:LABEL_MAX]] += hi - lo
                covered += hi - lo
            j += 1
        totals["unattributed"] += (b - a) - covered
    rows = sorted(((k, v / 1e9) for k, v in totals.items() if v > 0),
                  key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in rows]


def breakdown(capture, span_name):
    return {"device_ops": op_groups(capture),
            "idle_gaps": idle_gaps(capture, span_name)}
