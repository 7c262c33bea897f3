"""What every cell shares: the manifest, the device and its peaks, the
traced window, the per-layer readers and the result line.

A runner (`runners/<name>.py`) exposes `run(cell, tracer) -> dict`; it
calls `tracer.open()` at the first measured step or request and
`tracer.close()` after the window:

    attempted, failed   steps (train) or requests due (serve)
    checks              [(name, ok, message), ...] - all must hold for
                        `correct`
    window_start        `time.perf_counter()` at the first measured step
                        or request; set-up ends here
    end_to_end          {metric name: value} the runner measured
    facts               counters, series and spans for the readers
    memory_peak_bytes   peak on the fullest chip
    driver_span         the span that marks the thread driving the device
    capture             what `tracer.close()` returned

A reader (`metrics/<metric>.py`) exposes `read(run) -> float | None`,
where `run` holds `facts`, `capture` (the reduced profiler capture, None
without one), `peaks`, `config` and `mix`.  None = nothing to read, and
the metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from . import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)            # holds BENCHMARK.json
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(msg):
    """Progress goes to stderr: stdout carries the result line."""
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """`runners/<name>.py` or `metrics/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind}/{name}.py for {name!r}")
    mod_name = "benchmarks_%s_%s" % (
        kind, "".join(c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads` with everything it names, loaded."""

    def __init__(self, name, seed, seconds, trace, rehearse):
        """`seconds` None = the manifest's `run_seconds`."""
        self.manifest = load_json(ROOT, "BENCHMARK.json")
        found = [w for w in self.manifest["workloads"]
                 if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = [c for c in self.manifest["configs"]
                 if c["name"] == self.workload["config"]]
        if not entry:
            raise SystemExit(f"workload {name!r} names no known config")
        self.config = load_json(ROOT, entry[0]["file"])
        self.mix = load_json(HERE, "traffic",
                             self.workload["traffic"] + ".json")
        self.seed = int(seed)
        self.seconds = float(self.manifest["run_seconds"]
                             if seconds is None else seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        if rehearse:
            # toy sizes for walking the code on the CPU: every override
            # sits in the data files, under "rehearsal"
            self.config = _overlay(self.config,
                                   self.config.get("rehearsal", {}))
            self.mix = _overlay(self.mix, self.mix.get("rehearsal", {}))
        self.peaks = None
        self.device = None

    def metrics(self, group):
        """The entries of `end_to_end` or `per_layer` this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def _overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) \
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


# -- the device --------------------------------------------------------------


def claim_device(cell):
    """Refuse anything but the chips the cell asks for, on a device
    kind with peaks on record.  A rehearsal takes whatever JAX has and
    says so."""
    import jax

    # every program, small ones too, goes to the persistent cache, so
    # that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    cell.device = {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}
    say(f"devices: {cell.device}; cell {cell.name} on {cell.chips} chip(s)")
    table = load_json(HERE, "peaks.json")
    if cell.rehearse:
        if cell.device["platform"] == "tpu":
            raise SystemExit("--rehearse-cpu is for machines without a TPU")
        return      # no peaks: nothing read off a CPU is a device metric
    if cell.device["platform"] != "tpu":
        raise SystemExit(f"the benchmark measures the chip; JAX found "
                         f"platform {cell.device['platform']!r}")
    if cell.device["kind"] not in table:
        raise SystemExit(f"no peaks on record for device kind "
                         f"{cell.device['kind']!r}")
    if len(devs) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s), "
                         f"JAX found {len(devs)}")
    cell.peaks = table[cell.device["kind"]]


# -- the traced window -------------------------------------------------------


class TraceWindow:
    """Profile `length_s` seconds, starting `start_s` into the measured
    window, from a thread of its own: starting and stopping the
    profiler takes seconds and must stall neither the load generator
    nor the step loop.  The python tracer is off (it slows the host and
    bloats the capture); JAX's and the program's annotations stay."""

    def __init__(self, enabled, start_s, length_s):
        self.enabled = enabled
        self.start_s = float(start_s)
        self.length_s = float(length_s)
        self._thread = None
        self._stop = threading.Event()

    def open(self):
        """Call at the first measured step or request."""
        if not self.enabled:
            return
        self._thread = threading.Thread(target=self._run,
                                        name="bench-tracer", daemon=True)
        self._thread.start()

    def _run(self):
        import jax

        if self._stop.wait(self.start_s):
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                self._stop.wait(self.length_s)
        finally:
            jax.profiler.stop_trace()

    def close(self):
        """Call after the measured window; returns the capture or None."""
        if self._thread is None:
            return None
        self._stop.set()
        self._thread.join()
        t0 = time.perf_counter()
        capture = xplane.load_capture(TRACE_DIR)
        say(f"trace: capture read in {time.perf_counter() - t0:.1f} s")
        return capture


# -- the result --------------------------------------------------------------


def read_per_layer(cell, outcome, capture):
    run = {"facts": outcome["facts"], "capture": capture,
           "peaks": cell.peaks, "config": cell.config, "mix": cell.mix,
           "chips": cell.chips}
    values = {}
    for m in cell.metrics("per_layer"):
        value = load_module("metrics", m["name"]).read(run)
        if value is None:
            say(f"metric {m['name']}: nothing to read, left out")
            continue
        values[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return values


def result(cell, outcome, capture, t_process):
    """The contract's object: end-to-end metrics without a trace, the
    per-layer ones with it."""
    for name, ok, msg in outcome["checks"]:
        say(f"check {name}: {'ok' if ok else 'FAILED'} {msg}")
    correct = all(ok for _, ok, _ in outcome["checks"])
    measured = dict(outcome["end_to_end"])
    measured["setup_s"] = outcome["window_start"] - t_process
    say("end to end: " + json.dumps(measured))
    device = dict(cell.device)
    device["memory_peak_bytes"] = int(outcome["memory_peak_bytes"])
    out = {"correct": correct, "attempted": int(outcome["attempted"]),
           "failed": int(outcome["failed"])}
    if cell.trace:
        out["metrics"] = read_per_layer(cell, outcome, capture)
        summary = xplane.device_summary(capture) if capture else None
        if summary is not None:
            device.update(summary)
            out["breakdown"] = xplane.breakdown(capture,
                                                outcome["driver_span"])
        elif not cell.rehearse:
            raise SystemExit("traced run: no operation ran on the device "
                             "inside the traced window")
    else:
        out["metrics"] = {
            m["name"]: {"value": float(measured[m["name"]]),
                        "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
    out["device"] = device
    return out
