"""Retrace audit: a global registry of every jit trace the runtime takes.

XLA compiles one program per (function, abstract signature). A shape
that drifts step-to-step silently recompiles every step and the run
crawls; on TPU pods a single stray retrace can cost minutes. The
engines call `record_compile(name, *tracers)` from inside their traced
bodies — trace-time python runs exactly once per compilation, so each
registry entry IS one compile. `annotate(name, ...)` backfills wall
time and `memory_analysis` peak once the lowering is in hand.

`no_retrace()` turns the audit into a tripwire: any compile recorded
inside the context (beyond an allow-list) raises `RetraceError` with
the offending signature, which is how the tier-1 smoke test pins the
steady-state "3 steps, 1 trace" contract. `suppress()` mutes recording
for deliberate re-lowerings (e.g. `Engine.memory_analysis`)."""

from __future__ import annotations

import contextlib
import threading
import time

__all__ = ["RetraceError", "record_compile", "annotate", "compile_events",
           "signature_of", "no_retrace", "suppress", "reset"]


class RetraceError(RuntimeError):
    """An unexpected recompilation happened inside `no_retrace()`."""


_lock = threading.Lock()
_events: list = []          # [{name, signature, time, wall_s?, peak_bytes?}]
_guards: list = []          # stack of active no_retrace allow-lists
_suppressed = 0             # >0: record_compile is a no-op


def signature_of(*args):
    """Abstract (shape, dtype) signature of tracer/array pytree leaves."""
    import jax

    sig = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None:
            sig.append(repr(leaf))
        else:
            sig.append(f"{getattr(dtype, 'name', dtype)}{list(shape)}")
    return "(" + ", ".join(sig) + ")"


def record_compile(name, *args, signature=None):
    """Log one compilation. Call from inside the traced function body.

    Raises RetraceError when a `no_retrace()` guard is active and
    `name` is not on its allow-list."""
    if signature is None:
        signature = signature_of(*args)
    with _lock:
        if _suppressed:
            return
        ev = {"name": name, "signature": signature, "time": time.time()}
        _events.append(ev)
        if len(_events) > 4096:
            del _events[:-4096]
        guard = _guards[-1] if _guards else None
    if guard is not None and name not in guard:
        raise RetraceError(
            f"unexpected recompilation of {name!r} with signature "
            f"{signature} inside no_retrace() — steady-state step shapes "
            f"changed (pad batches / bucket sequence lengths)")


def annotate(name, wall_s=None, peak_bytes=None, **counts):
    """Attach wall time / memory peak / trace-time counts (ints, e.g.
    the train step's `dropout_masks`) to the most recent `name` event."""
    with _lock:
        if _suppressed:
            return
        for ev in reversed(_events):
            if ev["name"] == name:
                if wall_s is not None:
                    ev["wall_s"] = wall_s
                if peak_bytes is not None:
                    ev["peak_bytes"] = int(peak_bytes)
                ev.update({k: int(v) for k, v in counts.items()})
                return


def compile_events(name=None):
    with _lock:
        return [dict(e) for e in _events
                if name is None or e["name"] == name]


@contextlib.contextmanager
def no_retrace(allow=()):
    """Raise RetraceError on any compile recorded inside the context."""
    allow = frozenset(allow)
    with _lock:
        _guards.append(allow)
    try:
        yield
    finally:
        with _lock:
            _guards.pop()


@contextlib.contextmanager
def suppress():
    """Mute the audit for a deliberate re-lowering (no event, no guard
    trip) — e.g. `Engine.memory_analysis` re-lowers the same step."""
    global _suppressed
    with _lock:
        _suppressed += 1
    try:
        yield
    finally:
        with _lock:
            _suppressed -= 1


def reset():
    global _suppressed
    with _lock:
        _events.clear()
        _guards.clear()
        _suppressed = 0
