"""paddle_tpu.observe — unified runtime telemetry.

Four coupled pieces, one import:

* `timeline` / `phase(name)` / `span(name)` — nested spans with
  bounded aggregates, plus `attribute(logdir)` device-time bucketing
  (matmul/attention/collective/elementwise/other).
* `retrace` — global compile-event registry; `no_retrace()` raises on
  any unexpected recompilation, `suppress()` mutes deliberate ones.
* `flight` / `flight_guard()` — always-on bounded black box of recent
  step records, dumped to JSON on crash/preemption/SIGTERM/rollback.
* `snapshot()` / `dump()` / `prometheus_text()` — one export across
  monitor counters, serving metrics, phase aggregates, and goodput.
"""

from .timeline import (BUCKETS, StepTimeline, attribute, attribute_rows,  # noqa: F401
                       classify_op, overlap_report, overlap_stats, phase,
                       span, timeline)
from .retrace import (RetraceError, annotate, compile_events, no_retrace,  # noqa: F401
                      record_compile, signature_of, suppress)
from . import retrace  # noqa: F401
from .recorder import (FlightRecorder, flight, flight_guard,  # noqa: F401
                       install_signal_handler)
from . import recorder  # noqa: F401
from .export import dump, goodput, prometheus_text, snapshot  # noqa: F401

__all__ = [
    "BUCKETS", "StepTimeline", "attribute", "attribute_rows", "classify_op",
    "overlap_report", "overlap_stats", "phase", "span", "timeline",
    "RetraceError", "annotate", "compile_events", "no_retrace",
    "record_compile", "signature_of", "suppress", "retrace",
    "FlightRecorder", "flight", "flight_guard", "install_signal_handler",
    "recorder",
    "dump", "goodput", "prometheus_text", "snapshot",
]


def reset():
    """Reset every observe registry (tests)."""
    timeline.reset()
    retrace.reset()
    flight.reset()
