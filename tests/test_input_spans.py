"""The DataLoader's consumer spans, in the parent process, as spans in
the ring and aggregates in the timeline: `input.spawn` round starting an
epoch's fork workers and handing them their first index lists,
`input.first_batch` round the first take from
them, `input.wait` round every later (steady-state) take, each take
with the batches its workers were ahead by as `ready`, `input.convert`
round host batch -> `Tensor` and the prefetcher's `device_put`,
`input.close` round an epoch's sentinels and joins."""

import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observe, profiler
from paddle_tpu.framework import monitor

SPANS = ("input.close", "input.spawn", "input.first_batch", "input.wait",
         "input.convert")
COUNTERS = ("input_epochs", "input_batches", "input_batches_waited")


class _Rows(paddle.io.Dataset):
    def __len__(self):
        return 48

    def __getitem__(self, i):
        return np.full((4,), i, np.float32), np.int64(os.getpid())


def _aggregate(name):
    return observe.timeline.aggregates().get(
        name, {"calls": 0, "total_s": 0.0})


def test_each_epoch_spawns_once_and_every_take_is_a_wait():
    loader = paddle.io.DataLoader(_Rows(), batch_size=8, num_workers=2)
    before = {n: _aggregate(n) for n in ("input.spawn", "input.wait")}
    mark = len([e for e in profiler.events() if e["name"] == "input.spawn"])
    seen, pids = [], set()
    for _ in range(2):
        for rows, pid in loader:
            seen.append(np.asarray(rows._value)[:, 0])
            pids.update(int(p) for p in np.asarray(pid._value))
    # the rows were made in four workers (2 an epoch); the spans below
    # were all recorded here, by the consumer
    assert os.getpid() not in pids and len(pids) == 4
    assert np.concatenate(seen).tolist() == list(range(48)) * 2
    spawn, wait = _aggregate("input.spawn"), _aggregate("input.wait")
    assert spawn["calls"] - before["input.spawn"]["calls"] == 2
    assert spawn["total_s"] > before["input.spawn"]["total_s"]
    taken = wait["calls"] - before["input.wait"]["calls"]
    # a steady-state take a batch: every one of an epoch's 6 but its
    # first, which is `input.first_batch`
    assert taken == 10
    assert wait["total_s"] > before["input.wait"]["total_s"]
    mine = [e for e in profiler.events() if e["name"] == "input.spawn"]
    assert len(mine) - mark == 2 and mine[-1]["cat"] == "input"
    assert mine[-1]["tid"] == threading.get_ident()
    gp = observe.goodput()              # an input stall is host time
    assert gp["categories_s"]["host"] >= wait["total_s"] + spawn["total_s"]


@pytest.mark.parametrize("buffered", [True, False],
                         ids=["buffer_reader", "no_buffer_reader"])
@pytest.mark.parametrize("workers", [1, 2])
def test_an_epoch_is_one_spawn_one_first_batch_and_one_close(workers,
                                                             buffered):
    loader = paddle.io.DataLoader(_Rows(), batch_size=8, num_workers=workers,
                                  use_buffer_reader=buffered)
    before = {n: _aggregate(n) for n in SPANS}
    counted = {n: monitor.stat_get(n) for n in COUNTERS}
    mark = len(profiler.events())
    for _ in range(2):
        assert sum(1 for _ in loader) == 6
    mine = [e for e in profiler.events()[mark:] if e["name"] in SPANS]
    assert {e["tid"] for e in mine} == {threading.get_ident()}
    assert {e["cat"] for e in mine} == {"input"}
    mine.sort(key=lambda e: e["ts"])
    turns = [e["name"] for e in mine
             if e["name"] not in ("input.wait", "input.convert")]
    assert turns == ["input.spawn", "input.first_batch", "input.close"] * 2
    takes = [e for e in mine
             if e["name"] in ("input.first_batch", "input.wait")]
    assert len(takes) == 12
    # every take says how many batches the workers had put and the
    # consumer not yet yielded: never more than the loader keeps in
    # flight
    in_flight = max(2, loader.prefetch_factor * workers)
    assert all(isinstance(e["ready"], int) and 0 <= e["ready"] <= in_flight
               for e in takes)
    # host batch -> Tensor once a batch, and the prefetcher's device_put
    converts = sum(1 for e in mine if e["name"] == "input.convert")
    assert converts == (24 if buffered else 12)
    delta = {n: monitor.stat_get(n) - counted[n] for n in COUNTERS}
    assert delta["input_epochs"] == 2 and delta["input_batches"] == 12
    # a take that found its workers not ahead at all waited for one
    assert delta["input_batches_waited"] \
        == sum(1 for e in takes if e["ready"] == 0)
    spent = 0.0
    for name in SPANS:
        took = _aggregate(name)["total_s"] - before[name]["total_s"]
        assert took > 0, name
        spent += took
    assert observe.goodput()["categories_s"]["host"] >= spent
