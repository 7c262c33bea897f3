"""The six readers of the program's own spans: each on a hand-built
ring or capture with known answers, the two capture readers on a piece
of a real TPU v5 lite capture, and all of them at the end of the serve
runner's rehearsal on the CPU.

`fixtures/decode_spans_cut.json.gz` is cut from the 3 s capture of this
PR's traced run of `gpt3-1.3b.serve-decode` (seed 2700000001): the
driving thread's line whole, so the readers see every span the chip run
saw, and the device's ops and modules of 0.25 s of it, three steps,
with the traced window's span set to those 0.25 s."""

import gzip
import json
import os
import re

import pytest

from benchmarks import harness, spans, xplane
from benchmarks import manifest as rules
from benchmarks import run as bench_run
from paddle_tpu import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REQUEST_LEVEL = ("first_token_p50_ms", "first_token_p90_ms",
                 "token_gap_p99_ms", "prefill_steps_per_request")
FROM_CAPTURE = ("dispatch_ms.decode", "loop_self_ms.decode")


def _read(metric, run):
    return harness.load_module("metrics", metric).read(run)


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------


def _request(rid, arrival_s, queue_s, prefill_s, gaps_s, steps):
    """One finished request in the ring, as the engine folds it."""
    admitted = arrival_s + queue_s
    stamps = [admitted + prefill_s]
    for g in gaps_s:
        stamps.append(stamps[-1] + g)
    profiler.record_span("request.queue", arrival_s, queue_s,
                         cat="request", id=rid, queue_s=queue_s)
    profiler.record_span("request.prefill", admitted, prefill_s,
                         cat="request", id=rid, steps=steps,
                         prefix_hit_tokens=0)
    profiler.record_span("request.decode", stamps[0], stamps[-1] - stamps[0],
                         cat="request", id=rid, token_s=stamps)


@pytest.fixture
def ring():
    """Eleven requests of the window, first token after 100, 200, ...
    1100 ms, and the pinned prompt of set-up, which is none of them."""
    profiler.reset()
    with profiler.RecordEvent("serving.step"):      # not a request span
        pass
    # the pinned prompt: stepped by hand, it waited 0.25 ms and took a
    # whole second to its first token
    _request(1, 5.0, 0.00025, 1.0, [0.5], steps=99)
    queue_s = []
    for i in range(11):
        wait = 0.010 + 0.001 * i
        queue_s.append(wait)
        first = 0.1 * (i + 1)
        _request(10 + i, 20.0 + i, wait, first - wait,
                 [0.080] * 9 + [0.080 + 0.001 * i], steps=2 * i)
    yield {"facts": {"queue_s": queue_s, "answered": 11}, "capture": None}
    profiler.reset()


def test_first_token_percentiles_over_the_windows_requests(ring):
    # 100 .. 1100 ms in steps of 100: linear interpolation as numpy's
    assert _read("first_token_p50_ms", ring) == pytest.approx(600.0)
    assert _read("first_token_p90_ms", ring) == pytest.approx(1000.0)


def test_token_gap_p99_is_over_every_gap_of_every_request(ring):
    # 110 gaps: 99 of 80 ms, then 80, 81, ... 90 ms, one a request;
    # rank 0.99 * 109 = 107.91 lies between 88 and 89 ms
    assert _read("token_gap_p99_ms", ring) == pytest.approx(88.91)


def test_prefill_steps_are_averaged_over_requests(ring):
    # 0, 2, ... 20 steps; the pinned prompt's 99 are not among them
    assert _read("prefill_steps_per_request", ring) == pytest.approx(10.0)


def test_the_pinned_prompt_is_left_out(ring, capfd):
    found = spans.window_requests(ring, "a_metric")
    assert sorted(found) == list(range(10, 21))
    assert "11 request(s) of the window in the span ring, 11 answered" \
        in capfd.readouterr().err
    # counted in, its 1000.25 ms to the first token would move both
    ring["facts"]["queue_s"].append(0.00025)
    assert len(spans.window_requests(ring, "a_metric")) == 12
    assert _read("first_token_p50_ms", ring) == pytest.approx(650.0)
    assert _read("first_token_p90_ms", ring) == pytest.approx(1000.225)


def test_two_requests_with_one_queue_wait_are_both_counted(ring):
    _request(40, 50.0, 0.010, 0.090, [0.08], steps=1)   # request 10's wait
    ring["facts"]["queue_s"].append(0.010)
    ring["facts"]["answered"] = 12
    assert sorted(spans.window_requests(ring, "a_metric")) == \
        list(range(10, 21)) + [40]


@pytest.mark.parametrize("metric", REQUEST_LEVEL)
def test_fewer_requests_than_answers_is_nothing_to_read(ring, capfd, metric):
    ring["facts"]["answered"] = 12          # one answer has no spans
    assert _read(metric, ring) is None
    assert f"metric {metric}: 11 request(s) of the window in the span " \
        "ring, 12 answered" in capfd.readouterr().err


@pytest.mark.parametrize("metric", REQUEST_LEVEL + FROM_CAPTURE)
def test_a_program_without_the_spans_gives_nothing_and_no_error(metric):
    profiler.reset()
    run = {"facts": {"queue_s": [0.01, 0.02], "answered": 2},
           "capture": {"names": ["serving.step"], "devices": [],
                       "host": [{"name": "python3",
                                 "events": [[0, 0.0, 5.0]]}]}}
    assert _read(metric, run) is None
    assert _read(metric, dict(run, capture=None)) is None


# ---------------------------------------------------------------------------
# the capture
# ---------------------------------------------------------------------------


def _capture(driver, other=()):
    names, index = [], {}

    def nid(n):
        if n not in index:
            index[n] = len(names)
            names.append(n)
        return index[n]
    return {
        "names": names, "devices": [],
        "host": [
            {"name": "main", "events": [[nid(n), s, d] for n, s, d in other]},
            {"name": "serving-engine",
             "events": [[nid(n), s, d] for n, s, d in driver]},
        ]}


MS = 1_000_000


def _iteration(t0, admit, sample, dispatch, readback, commit):
    """The spans of one working iteration starting at `t0`, in ns."""
    step0 = t0 + admit + sample
    step = dispatch + readback
    return [
        ("serving.loop", t0, admit + sample + step + commit),
        ("step.admit", t0, admit),
        ("step.sample", t0 + admit, sample),
        ("serving.step", step0, step),
        ("step.dispatch", step0, dispatch),
        ("step.readback", step0 + dispatch, readback),
        ("step.commit", step0 + step, commit),
    ]


@pytest.fixture
def capture():
    driver = []
    # three iterations; the host's own part is 1.0, 1.5 and 3.5 ms
    driver += _iteration(0, 0.2 * MS, 0.5 * MS, 4 * MS, 76 * MS, 0.3 * MS)
    driver += _iteration(100 * MS, 0.5 * MS, 0.5 * MS, 5 * MS, 75 * MS,
                         0.5 * MS)
    driver += _iteration(200 * MS, 2.0 * MS, 1.0 * MS, 9 * MS, 71 * MS,
                         0.5 * MS)
    driver.append(("loop.idle", 300 * MS, 20 * MS))
    # set-up stepped the pinned prompt from the main thread, slowly
    other = [("serving.step", -500 * MS, 300 * MS),
             ("step.dispatch", -500 * MS, 250 * MS)]
    return _capture(driver, other)


def test_dispatch_is_the_median_on_the_driving_thread(capture):
    run = {"facts": {}, "capture": capture}
    assert _read("dispatch_ms.decode", run) == pytest.approx(5.0)


def test_loop_self_time_is_the_iteration_less_the_step_inside(capture):
    run = {"facts": {}, "capture": capture}
    assert _read("loop_self_ms.decode", run) == pytest.approx(
        (1.0 + 1.5 + 3.5) / 3)
    loops = spans.driving_events(capture, "serving.loop")
    steps = spans.driving_events(capture, "serving.step")
    assert spans.self_times_ns(loops, steps) == pytest.approx(
        [1.0 * MS, 1.5 * MS, 3.5 * MS])


def test_self_time_takes_only_the_spans_inside():
    # a step that began before its loop was captured belongs to no loop;
    # an iteration that made no step is all its own
    outer = [(100, 50), (200, 50), (300, 50)]
    inner = [(90, 30), (205, 40), (400, 10)]
    assert spans.self_times_ns(outer, inner) == [50, 10, 50]


# ---------------------------------------------------------------------------
# a real capture taken with the spans
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def decode_spans():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "decode_spans_cut.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_capture_readers_give_what_the_chip_run_printed(decode_spans):
    run = {"facts": {}, "capture": decode_spans}
    assert _read("dispatch_ms.decode", run) == pytest.approx(
        4.583689, rel=1e-9)
    assert _read("loop_self_ms.decode", run) == pytest.approx(
        0.6814998333333334, rel=1e-9)
    # 36 whole iterations in the 3 s, each with one step inside it
    loops = spans.driving_events(decode_spans, "serving.loop")
    steps = spans.driving_events(decode_spans, "serving.step")
    assert len(loops) == len(steps) == 36
    own = spans.self_times_ns(loops, steps)
    assert 0 < min(own) and max(own) < 2e6      # under 2 ms of 82
    assert not spans.driving_events(decode_spans, "loop.idle")


def test_the_idle_gaps_of_a_real_capture_are_named_by_the_new_spans(
        decode_spans):
    gaps = dict(xplane.idle_gaps(decode_spans, "serving.step", top=100))
    idle = xplane.device_summary(decode_spans)
    assert sum(gaps.values()) == pytest.approx(
        idle["window_s"] - idle["busy_s"], rel=1e-6)
    assert gaps["np.asarray(jax.Array)"] > 0.009     # inside step.readback
    for name in ("step.dispatch", "step.sample", "step.commit",
                 "serving.loop", "PjitFunction(serving_step)"):
        assert gaps[name] > 0, name
    assert "step.device-step" not in gaps
    # between spans the driving thread leaves 0.1 ms of 26 unnamed
    assert gaps["unattributed"] < 0.005 * sum(gaps.values())
    modules = {decode_spans["names"][n].split("(")[0]
               for n, _, _ in decode_spans["devices"][0]["modules"]}
    assert modules == {"jit_serving_step"}


# ---------------------------------------------------------------------------
# the runner's rehearsal, and the manifest
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell,want", [
    ("gpt3-1.3b.serve-decode", REQUEST_LEVEL + FROM_CAPTURE),
    ("gpt3-1.3b.serve-chat", REQUEST_LEVEL),
])
def test_a_traced_rehearsal_ends_with_every_span_reader_reporting(
        tmp_path, capfd, cell, want):
    out_file = tmp_path / "would_be.json"
    rc = bench_run.main(["--workload", cell, "--seed", "3000000019",
                         "--seconds", "2", "--trace", "1",
                         "--rehearse-cpu", str(out_file)])
    assert rc == 0
    err = capfd.readouterr().err
    result = json.loads(out_file.read_text())["result"]
    assert result["correct"] is True and result["failed"] == 0
    for metric in want:
        assert result["metrics"][metric]["value"] >= 0, metric
    for metric in set(FROM_CAPTURE) - set(want):
        assert metric not in result["metrics"]
    # every request-level reader took exactly the requests the runner
    # counted as answered, the pinned prompt not among them
    answered = int(re.search(r"(\d+) answered, 0 failed", err).group(1))
    assert answered == result["attempted"] > 0
    for metric in REQUEST_LEVEL:
        assert f"metric {metric}: {answered} request(s) of the window in " \
            f"the span ring, {answered} answered" in err
    m = result["metrics"]
    assert m["first_token_p50_ms"]["value"] \
        <= m["first_token_p90_ms"]["value"]
    assert m["prefill_steps_per_request"]["value"] >= 1.0


def test_the_six_metrics_are_appended_and_the_manifest_holds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert rules.problems(manifest, ROOT) == []
    tail = manifest["per_layer"][-6:]
    assert tuple(m["name"] for m in tail) == REQUEST_LEVEL + FROM_CAPTURE
    serve = ["gpt3-1.3b.serve-decode", "gpt3-1.3b.serve-chat"]
    for m in tail:
        assert m["workloads"] == (serve[:1] if m["name"] in FROM_CAPTURE
                                  else serve)
        assert m["source"] in ("program_span", "program_counter")
    assert {m["layer"] for m in tail} == {"prefill", "serve_step",
                                          "host_loop"}
