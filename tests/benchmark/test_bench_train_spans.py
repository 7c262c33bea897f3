"""The four readers of the training loop's own spans: each on a
hand-written ring with known answers (two loader turnovers, one that
starved the device and one that did not), the window cut out of the
ring by `facts["steps"]`, a ring without the spans, and all four at the
end of the pre-training cell's rehearsal on the CPU."""

import json
import os
import re
import threading

import pytest

from benchmarks import harness, train_spans
from benchmarks import manifest as rules
from benchmarks import run as bench_run
from paddle_tpu import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ernie-base.pretrain"
READERS = {
    "loader_turnover_ms.train": ("ms", "input"),
    "loader_close_ms.train": ("ms", "input"),
    "loader_steady_wait_ms.train": ("ms/step", "input"),
    "turnover_starved_share.train": ("%", "device"),
}
STEP_S = 0.1
STEADY_TAKE_S = (0.0005, 0.0003, 0.0001)    # wait, convert, convert


def _read(metric, steps):
    return harness.load_module("metrics", metric).read(
        {"facts": {"steps": steps}, "capture": None})


def _spans(at, *named):
    """Spans back to back from `at`; -> where the last one ended."""
    for name, dur, fields in named:
        profiler.record_span(name, at, dur, cat="input", **fields)
        at += dur
    return at


def _steady_take(at):
    wait, to_tensor, put = STEADY_TAKE_S
    return _spans(at, ("input.wait", wait, {"ready": 3}),
                  ("input.convert", to_tensor, {}),
                  ("input.convert", put, {}))


def _new_epoch(at, spawn, first, wait):
    """What the prefetcher does before it yields a new epoch's first
    batch; 0.006 s of converts beside the three given."""
    return _spans(at, ("input.spawn", spawn, {}),
                  ("input.first_batch", first, {"ready": 0}),
                  ("input.convert", 0.002, {}), ("input.convert", 0.001, {}),
                  ("input.wait", wait, {"ready": 1}),
                  ("input.convert", 0.002, {}), ("input.convert", 0.001, {}))


def _step(n, at, in_flight):
    profiler.record_span("step.device-step", at, 0.001, cat="phase",
                         step=n, in_flight=in_flight)


@pytest.fixture
def ring():
    """Set-up, a window of 12 steps 0.1 s apart, the runner's drain.

    Steps 0-2, 5-7, 10 and 11 follow a steady take of 0.9 ms.  Step 3
    takes an epoch's last batch: its `next()` is the close, 20 ms; step
    4 follows 266 ms of spawn, first batch, take and converts and finds
    the device empty.  Steps 8 and 9 the same with 40 ms and 140 ms, and
    step 8 still running when step 9 is dispatched."""
    profiler.reset()
    other = threading.Thread(target=_step, args=(99, 0.1, 0))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    _new_epoch(0.2, 0.300, 0.080, 0.004)            # set-up's
    _step(-1, 0.8, 0)
    _steady_take(0.85)
    _step(0, 0.9, 0)                                # the last warm step
    turn = {3: 0.020, 8: 0.040}
    new_epoch = {4: (0.200, 0.050, 0.010, 0), 9: (0.100, 0.030, 0.004, 1)}
    at = 1.0
    for k in range(12):
        if k in turn:
            at = _spans(at, ("input.close", turn[k], {}))
            in_flight = 0
        elif k in new_epoch:
            spawn, first, wait, in_flight = new_epoch[k]
            at = _new_epoch(at, spawn, first, wait)
        else:
            at = _steady_take(at)
            in_flight = k % 4
        _step(k + 1, at, in_flight)
        at += STEP_S
    # the drain behind the window: the rest of the epoch, and its close
    _spans(_steady_take(at), ("input.close", 0.5, {}))
    yield
    profiler.reset()


def test_the_four_readers_on_two_turnovers_one_of_them_starved(ring, capfd):
    assert _read("loader_turnover_ms.train", 12) \
        == pytest.approx((20 + 266 + 40 + 140) / 2)
    assert _read("loader_close_ms.train", 12) == pytest.approx(30.0)
    assert _read("loader_steady_wait_ms.train", 12) \
        == pytest.approx(8 * 0.9 / 12)
    assert _read("turnover_starved_share.train", 12) == pytest.approx(50.0)
    err = capfd.readouterr().err
    assert err.count("2 loader turnover(s) in a window of 12 step(s)") == 4


def test_a_turnover_is_the_close_and_everything_up_to_the_next_step(ring):
    win = train_spans.window({"facts": {"steps": 12}}, "test")
    first, second = train_spans.turnovers(win)
    assert [e["name"] for e in first["spans"]] == [
        "input.close", "input.spawn", "input.first_batch", "input.convert",
        "input.convert", "input.wait", "input.convert", "input.convert"]
    assert (first["step"]["step"], second["step"]["step"]) == (5, 10)
    assert first["close"]["dur"] == pytest.approx(20e3)
    # the step that took the old epoch's last batch lies between the
    # close and the spawn, and is none of the turnover's spans
    assert first["close"]["ts"] < win["steps"][3]["ts"] \
        < first["spans"][1]["ts"]


def test_the_window_is_cut_by_the_steps_the_runner_counted(ring):
    win = train_spans.window({"facts": {"steps": 12}}, "test")
    assert [e["step"] for e in win["steps"]] == list(range(1, 13))
    assert {e["tid"] for e in win["steps"]} == {threading.get_ident()}
    # set-up's spawn, the take before the last warm step and the
    # runner's drain fall outside
    assert sum(1 for e in win["input"] if e["name"] == "input.spawn") == 2
    assert sum(1 for e in win["input"] if e["name"] == "input.close") == 2
    assert sum(1 for e in win["input"] if e["name"] == "input.wait") == 8 + 2
    # a window of the last seven steps holds the second turnover alone
    assert _read("loader_turnover_ms.train", 7) == pytest.approx(180.0)
    assert _read("loader_close_ms.train", 7) == pytest.approx(40.0)
    assert _read("loader_steady_wait_ms.train", 7) \
        == pytest.approx(5 * 0.9 / 7)
    assert _read("turnover_starved_share.train", 7) == 0.0


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_window_without_a_whole_turnover_is_nothing_to_read(
        ring, capfd, metric):
    # the last three steps: the spawn is inside, its close is not
    assert _read(metric, 3) is None
    assert "0 loader turnover(s) in a window of 3 step(s)" \
        in capfd.readouterr().err
    # more steps than the ring holds on the driving thread
    assert _read(metric, 14) is None
    assert "14 and the one before them wanted" in capfd.readouterr().err
    assert _read(metric, 0) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_program_without_the_spans_gives_nothing_and_no_error(metric):
    profiler.reset()
    try:
        assert _read(metric, 4) is None             # an empty ring
        # the parent's ring: a spawn, a wait only where a take blocked,
        # steps without `in_flight`
        profiler.record_span("input.spawn", 0.1, 0.3, cat="input")
        for k in range(6):
            if k == 3:
                profiler.record_span("input.spawn", 0.95 + STEP_S * k,
                                     0.03, cat="input")
            profiler.record_span("input.wait", 0.99 + STEP_S * k, 0.004,
                                 cat="input")
            profiler.record_span("step.device-step", 1.0 + STEP_S * k,
                                 0.001, cat="phase")
        assert _read(metric, 4) is None
    finally:
        profiler.reset()


def test_the_rehearsal_of_the_pretraining_cell_emits_all_four(tmp_path,
                                                              capfd):
    out_file = tmp_path / "would_be.json"
    rc = bench_run.main(["--workload", CELL, "--seed", "3800000001",
                         "--seconds", "2", "--trace", "1",
                         "--rehearse-cpu", str(out_file)])
    assert rc == 0
    err = capfd.readouterr().err
    result = json.loads(out_file.read_text())["result"]
    assert result["correct"] is True and result["failed"] == 0
    m = {name: result["metrics"][name]["value"] for name in READERS}
    for name, (unit, _) in READERS.items():
        assert result["metrics"][name]["unit"] == unit
    # an epoch of 8 toy batches turns over many times in two seconds
    found = re.search(r"metric loader_turnover_ms.train: (\d+) loader "
                      r"turnover\(s\) in a window of (\d+) step\(s\)", err)
    turns, steps = int(found.group(1)), int(found.group(2))
    assert steps == result["attempted"]
    assert turns >= max(1, steps // 8 - 1)
    assert m["loader_turnover_ms.train"] > m["loader_close_ms.train"] > 0
    assert 0 <= m["turnover_starved_share.train"] <= 100
    # the spans lie inside the runner's own clock round `next(loader)`
    waited = result["metrics"]["input_wait_ms"]["value"]
    inside = m["loader_steady_wait_ms.train"] \
        + turns * m["loader_turnover_ms.train"] / steps
    assert 0 < m["loader_steady_wait_ms.train"] < inside <= waited


def test_the_four_entries_by_name_and_the_manifest_holds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert rules.problems(manifest, ROOT) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, layer) in READERS.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "train_tokens_per_s_chip", "workloads": [CELL]}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py"))
    # the runner's own clock stays beside them
    assert by_name["input_wait_ms"]["source"] == "host_clock"
