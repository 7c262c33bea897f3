"""The DataLoader's consumer spans: `input.spawn` round starting an
epoch's fork workers, `input.wait` round the blocking take from their
queue, in the parent process, as spans in the ring and aggregates in
the timeline."""

import os
import threading

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import observe, profiler


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
    assert 1 <= taken <= 12             # at most one wait a batch
    assert wait["total_s"] > before["input.wait"]["total_s"]
    mine = [e for e in profiler.events() if e["name"] == "input.spawn"]
    assert len(mine) - mark == 2 and mine[-1]["cat"] == "input"
    assert mine[-1]["tid"] == threading.get_ident()
    gp = observe.goodput()              # an input stall is host time
    assert gp["categories_s"]["host"] >= wait["total_s"] + spawn["total_s"]
