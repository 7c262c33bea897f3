"""`Engine.train_batch` says on every dispatch how much work the device
still had: the `step.device-step` event carries `step` and `in_flight`,
the steps dispatched before it whose losses report not ready, found by
`is_ready()` alone; and the fields a `RecordEvent` is given ride on its
ring event."""

import threading

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observe, profiler
from paddle_tpu.engine import Engine
from paddle_tpu.framework import monitor

STEPS = ("step.device-step", "step.compile")


class _Loss:
    """Stands in for a dispatched step's loss: ready when the test says."""

    def __init__(self, ready=False):
        self.ready = ready
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


@pytest.fixture
def engine():
    paddle.seed(7)
    model = paddle.nn.Linear(4, 1)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    return Engine(model, opt, lambda out, y: ((out[:, 0] - y) ** 2).mean())


def _batch():
    return np.ones((8, 4), np.float32), np.zeros((8,), np.float32)


def _step(engine):
    """One train step; -> (its ring event, its loss)."""
    mark = len(profiler.events())
    loss = engine.train_batch(*_batch())
    mine = [e for e in profiler.events()[mark:] if e["name"] in STEPS]
    assert len(mine) == 1 and mine[0]["tid"] == threading.get_ident()
    return mine[0], loss


def test_nothing_is_in_flight_behind_a_block_until_ready(engine):
    first, loss = _step(engine)
    assert first["name"] == "step.compile"
    assert (first["step"], first["in_flight"]) == (1, 0)
    for n in (2, 3, 4):
        jax.block_until_ready(loss._value)
        event, loss = _step(engine)
        assert event["name"] == "step.device-step"
        assert (event["step"], event["in_flight"]) == (n, 0)
        assert event["step"] == engine.state.step
        # the finished step's loss was dropped at this dispatch
        assert len(engine._in_flight) == 1


@pytest.mark.parametrize("unfinished", [1, 2, 3])
def test_in_flight_counts_the_steps_that_report_not_ready(engine,
                                                          unfinished):
    _, loss = _step(engine)
    jax.block_until_ready(loss._value)
    flying = [_Loss() for _ in range(unfinished)]
    engine._in_flight.extend(flying)
    event, loss = _step(engine)
    assert event["in_flight"] == unfinished
    assert observe.flight.last()["in_flight"] == unfinished
    assert list(engine._in_flight)[:unfinished] == flying
    # steps finish in the order they were dispatched: the oldest is
    # asked, and nobody behind one that is not ready
    assert [f.asked for f in flying] == [1] + [0] * (unfinished - 1)
    # the oldest finishes: the next dispatch drops it and counts the rest
    flying[0].ready = True
    jax.block_until_ready(loss._value)
    event, loss = _step(engine)
    assert flying[0] not in engine._in_flight
    # the real step dispatched above sits behind the stand-ins that are
    # still not ready, so it is counted with them; with none left it is
    # asked itself, and has finished
    left = unfinished - 1
    assert event["in_flight"] == (left + 1 if left else 0)


def test_the_deque_holds_only_what_is_unfinished(engine):
    for _ in range(20):
        _, loss = _step(engine)
        jax.block_until_ready(loss._value)
        assert len(engine._in_flight) == 1
    flying = [_Loss() for _ in range(3)]
    engine._in_flight.clear()
    engine._in_flight.extend(flying)
    for f in flying:
        f.ready = True
    event, _ = _step(engine)
    assert event["in_flight"] == 0
    assert not any(f in engine._in_flight for f in flying)
    assert len(engine._in_flight) == 1


def test_the_counters_follow_the_dispatches(engine):
    counted = {n: monitor.stat_get(n)
               for n in ("train_steps", "train_dispatches_device_idle")}
    _, loss = _step(engine)                         # idle: nothing before
    jax.block_until_ready(loss._value)
    engine._in_flight.append(_Loss())
    _step(engine)                                   # one step queued
    _step(engine)                                   # still there
    engine._in_flight.clear()
    _step(engine)                                   # idle again
    assert monitor.stat_get("train_steps") - counted["train_steps"] == 4
    assert monitor.stat_get("train_dispatches_device_idle") \
        - counted["train_dispatches_device_idle"] == 2


@pytest.mark.parametrize("open_span", [
    lambda **f: profiler.RecordEvent("test.fields", cat="test", **f),
    lambda **f: observe.span("test.fields", cat="test", **f),
    lambda **f: observe.phase("test.fields", cat="test", **f),
], ids=["RecordEvent", "span", "phase"])
def test_a_spans_fields_ride_on_its_one_ring_event(open_span):
    mark = len(profiler.events())
    with open_span(step=5, in_flight=2):
        pass
    mine = [e for e in profiler.events()[mark:]
            if e["name"].endswith("test.fields")]
    assert len(mine) == 1
    event = mine[0]
    assert (event["step"], event["in_flight"]) == (5, 2)
    assert event["cat"] == "test" and event["dur"] >= 0
    plain = profiler.RecordEvent("test.plain")
    with plain:
        pass
    assert set(profiler.events()[-1]) == {"name", "cat", "ts", "dur", "tid",
                                          "depth"}
