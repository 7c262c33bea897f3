"""The serving loop and a request's life, measured from inside: the
stamps the engine puts on a `Request`, the series and ring spans they
are folded into, the spans of one loop iteration, the timeline's
`device-step` closed after the read-back, the windowed getter of
`ServingMetrics`, and the names the benchmark reads, pinned.

Everything runs on the CPU at toy size; engines are stepped by hand
where the test needs to know which step did what, and through their own
thread where the loop's spans are the subject.
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observe, profiler, serving
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.serving import ServingMetrics
from paddle_tpu.serving import metrics as metrics_mod
from paddle_tpu.serving.engine import _RING_CLOCK_OFFSET

VOCAB = 97
REQUEST_SPANS = ("request.queue", "request.prefill", "request.decode")


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def _engine(gpt, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 4)
    eng = serving.SlotEngine(gpt, **kw)
    eng.warmup()
    return eng


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(np.int32)


def _drive(eng):
    """Step an engine that has no thread until nothing is live or
    queued, as its loop would."""
    eng._admit()
    while eng.active:
        eng._step()
        eng._admit()


def _request_spans(rid):
    return {e["name"]: e for e in profiler.events()
            if e["name"] in REQUEST_SPANS and e["id"] == rid}


# ---------------------------------------------------------------------------
# stamps
# ---------------------------------------------------------------------------


def test_stamps_are_ordered_and_one_per_token(gpt):
    eng = _engine(gpt)
    futs = [eng.submit(_prompt(s, 9 + s), max_new_tokens=5 + s)
            for s in range(3)]       # three requests over two slots
    _drive(eng)
    for s, req in enumerate(futs):
        out = req.result(0)
        stamps = list(req.token_times)
        assert len(stamps) == 5 + s == out.size - (9 + s)
        life = [req.arrival, req.admitted] + stamps + [req.finished]
        assert life == sorted(life)
        assert req.queue_wait == req.admitted - req.arrival
    # the third waited for a slot: its wait holds the others' steps
    assert futs[2].queue_wait > futs[0].queue_wait


def test_timings_reads_the_stamps_back(gpt):
    eng = _engine(gpt)
    req = eng.submit(_prompt(1, 10), max_new_tokens=6)
    assert req.timings() is None        # nothing happened yet
    _drive(eng)
    t = req.timings()
    assert set(t) == {"queue_s", "prefill_s", "first_token_s",
                      "token_gaps_s", "total_s", "prefix_hit_tokens",
                      "prefill_steps"}
    assert t["queue_s"] == req.queue_wait
    assert t["first_token_s"] == pytest.approx(
        t["queue_s"] + t["prefill_s"])
    assert len(t["token_gaps_s"]) == 5
    assert all(g >= 0 for g in t["token_gaps_s"])
    assert t["total_s"] >= t["first_token_s"] + sum(t["token_gaps_s"])
    # 10 prompt tokens, nothing cached, 4 a step
    assert t["prefill_steps"] == 3 and t["prefix_hit_tokens"] == 0


def test_a_prefix_hit_lowers_the_prefill_step_count(gpt):
    eng = _engine(gpt)
    prompt = _prompt(2, 26)
    cold = eng.submit(prompt, max_new_tokens=2)
    _drive(eng)
    warm = eng.submit(prompt, max_new_tokens=2)
    _drive(eng)
    assert cold.prefix_hit_tokens == 0 and cold.prefill_steps == 7
    # three whole blocks of 8 come from the cache; 2 tokens are left
    assert warm.prefix_hit_tokens == 24
    assert warm.prefill_steps == 1 < cold.prefill_steps
    assert (warm.result(0) == cold.result(0)).all()


def _cancel(eng, req):
    req.cancel()


def _time_out(eng, req):
    time.sleep(0.03)        # past the request's deadline


def _step_error(eng, req):
    # what the loop does with an exception out of `_step`
    eng._fail_all_active(RuntimeError("injected step error"))


@pytest.mark.parametrize("how,timeout", [
    (_cancel, None), (_time_out, 0.02), (_step_error, None)])
def test_a_request_that_fails_folds_nothing_and_leaks_nothing(
        gpt, how, timeout):
    eng = _engine(gpt)
    req = eng.submit(_prompt(3, 6), max_new_tokens=20, timeout=timeout)
    rid = req.id
    eng._admit()
    for _ in range(4):              # prefill and a first token or two
        eng._step()
    assert len(req.token_times) >= 1
    how(eng, req)
    _drive(eng)
    assert req.done() and req.exception(0) is not None
    snap = eng.metrics.snapshot()
    assert snap["counters"].get("completed", 0) == 0
    for kind in ("ttft", "itl", "prefill_req", "e2e"):
        assert kind not in snap["latency_s"], kind
    assert _request_spans(rid) == {}
    # nothing of the engine's holds the request, or its stamps, still
    assert eng.active == 0
    ref = weakref.ref(req)
    del req
    gc.collect()
    assert ref() is None


@pytest.mark.parametrize("do_sample", [False, True])
def test_the_speculative_path_stamps_every_committed_token(gpt, do_sample):
    eng = _engine(gpt, spec_len=3, prefill_chunk=8)
    req = eng.submit(_prompt(4, 9), max_new_tokens=11,
                     do_sample=do_sample, temperature=0.8, seed=5)
    _drive(eng)
    stamps = list(req.token_times)
    assert len(stamps) == 11 == req.result(0).size - 9
    assert [req.admitted] + stamps + [req.finished] == sorted(
        [req.admitted] + stamps + [req.finished])
    # a self-drafting engine has proposals accepted: the tokens one
    # verify step commits share that step's stamp
    assert eng.metrics.get("spec_accepted_tokens") > 0
    assert len(set(stamps)) < len(stamps)
    assert eng.metrics.snapshot()["latency_s"]["itl"]["count"] == 10


# ---------------------------------------------------------------------------
# what the stamps are folded into
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(gpt):
    """One started server after a little traffic, two of the prompts
    sharing a prefix; everything the contract tests read."""
    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    base = _prompt(5, 24)
    prompts = [base, np.concatenate([base, _prompt(6, 5)]), _prompt(7, 7),
               _prompt(8, 12)]
    futs = [srv.submit(p, max_new_tokens=6) for p in prompts[:1]]
    futs[0].result(60)
    futs += [srv.submit(p, max_new_tokens=6) for p in prompts[1:]]
    for f in futs:
        f.result(60)
    yield srv, futs
    srv.shutdown(drain=True)


def test_request_series_are_exported_like_queue_and_e2e(served):
    srv, futs = served
    lat = srv.metrics.snapshot()["latency_s"]
    assert lat["ttft"]["count"] == lat["prefill_req"]["count"] \
        == lat["e2e"]["count"] == len(futs)
    assert lat["itl"]["count"] == sum(len(f.token_times) - 1 for f in futs)
    assert lat["ttft"]["max"] == pytest.approx(
        max(f.timings()["first_token_s"] for f in futs))
    assert lat["prefill_req"]["p50"] <= lat["ttft"]["p50"] \
        <= lat["e2e"]["p50"]
    text = observe.prometheus_text(serving=srv.metrics)
    for kind in ("ttft", "itl", "prefill_req", "queue", "e2e"):
        assert f'kind="{kind}"' in text, kind


def test_request_spans_share_an_id_and_tile_the_life(served):
    _, futs = served
    for req in futs:
        spans = _request_spans(req.id)
        assert set(spans) == set(REQUEST_SPANS)
        q, p, d = (spans[n] for n in REQUEST_SPANS)
        assert q["ts"] == pytest.approx(
            (req.arrival + _RING_CLOCK_OFFSET) * 1e6)
        # microseconds on a clock that reads hundreds of seconds: the
        # three tile arrival -> finished to well under a microsecond
        assert q["ts"] + q["dur"] == pytest.approx(p["ts"], abs=1e-2)
        assert p["ts"] + p["dur"] == pytest.approx(d["ts"], abs=1e-2)
        assert d["ts"] + d["dur"] == pytest.approx(
            (req.finished + _RING_CLOCK_OFFSET) * 1e6, abs=1e-2)
        assert min(q["dur"], p["dur"], d["dur"]) >= 0
        assert q["queue_s"] == req.queue_wait
        assert p["steps"] == req.prefill_steps >= 1
        assert p["prefix_hit_tokens"] == req.prefix_hit_tokens
        assert list(d["token_s"]) == list(req.token_times)
    assert futs[1].prefix_hit_tokens == 24      # the shared prefix


def test_chrome_trace_pairs_a_requests_spans_by_id(served, tmp_path):
    _, futs = served
    path = profiler.export_chrome_tracing(str(tmp_path / "trace.json"))
    with open(path) as f:
        rows = json.load(f)["traceEvents"]
    mine = [r for r in rows if r.get("id") == futs[0].id]
    assert sorted((r["name"], r["ph"]) for r in mine) == sorted(
        (n, ph) for n in REQUEST_SPANS for ph in ("b", "e"))
    # the spans of the loop's thread stay complete events
    assert any(r["name"] == "serving.loop" and r["ph"] == "X"
               for r in rows)


# ---------------------------------------------------------------------------
# the anatomy of a step, and device-step closed after the read-back
# ---------------------------------------------------------------------------


def _totals():
    return {name: (a["calls"], a["total_s"])
            for name, a in observe.timeline.aggregates().items()}


def test_device_step_covers_the_decode_series(gpt):
    eng = _engine(gpt)
    before = _totals()
    for s in range(3):
        eng.submit(_prompt(20 + s, 11), max_new_tokens=7)
    _drive(eng)
    after = _totals()
    calls, total = {}, {}
    for name in ("device-step", "dispatch", "readback", "commit"):
        was = before.get(name, (0, 0.0))
        calls[name] = after[name][0] - was[0]
        total[name] = after[name][1] - was[1]
    steps = eng.metrics.get("steps")
    assert calls["device-step"] == steps > 0
    assert calls["readback"] == calls["commit"] == steps
    decode = eng.metrics.latency_since({}, "decode")
    # every step's dispatch -> read-back interval is in device-step;
    # the decode series holds those of the steps that decoded
    assert total["device-step"] >= sum(decode) > 0
    # and it is the two halves, not the enqueue alone
    assert total["device-step"] >= total["dispatch"] + total["readback"] > 0
    assert total["readback"] > 0


def test_a_step_by_hand_is_one_span_round_its_dispatch_and_its_readback(
        gpt):
    """`_step()` is one whole step: its `serving.step` holds that
    step's dispatch and that step's read-back, and is the decode
    series' interval (nothing was in flight before it)."""
    eng = _engine(gpt)
    mine, since = threading.get_ident(), time.perf_counter() * 1e6
    eng.submit(_prompt(40, 6), max_new_tokens=5)
    _drive(eng)
    seen = [e for e in profiler.events()
            if e["tid"] == mine and e["ts"] >= since]
    steps = [e for e in seen if e["name"] == "serving.step"]
    assert len(steps) == eng.metrics.get("steps") > 0
    assert eng.metrics.get("steps_launched_ahead") == 0
    for st in steps:
        inside = sorted((e for e in seen if e["depth"] == st["depth"] + 1
                         and _contains(st, e)), key=lambda e: e["ts"])
        assert [e["name"] for e in inside][:1] == ["step.dispatch"]
        assert [e["name"] for e in inside][-1:] == ["step.readback"]
    # one request: a step is a prefill sample or a decode sample, and
    # each sample opens just before its span and closes just behind it
    sampled = 1e6 * sum(eng.metrics.latency_since({}, "decode")
                        + eng.metrics.latency_since({}, "prefill"))
    assert 0.8 * sampled <= sum(e["dur"] for e in steps) <= sampled


def test_a_decode_sample_is_the_period_with_a_step_in_flight(gpt):
    """With a step in flight a step's sample runs from the landing of
    the step before it to its own landing: the samples tile the time
    the loop worked, where dispatch -> read-back of each would count
    every moment twice. The loop's two counters are exported."""
    eng = _engine(gpt)
    real = eng._decode

    def slow(*args):
        out = real(*args)
        time.sleep(0.01)          # a device step of 10 ms
        return out

    eng._decode = slow
    eng.start()
    try:
        t0 = time.monotonic()
        eng.submit(_prompt(41, 4), max_new_tokens=40,
                   eos_token_id=None).result(60)
        wall = time.monotonic() - t0
        decode = eng.metrics.latency_since({}, "decode")
        steps = eng.metrics.get("steps")
        ahead = eng.metrics.get("steps_launched_ahead")
        assert eng.metrics.get("columns_wasted") == 0
        # one cancelled mid-answer: its last column was in flight
        gone = eng.submit(_prompt(42, 4), max_new_tokens=50)
        until = time.monotonic() + 30
        while len(gone.token_times) < 3:
            assert time.monotonic() < until
            time.sleep(0.002)
        gone.cancel()
        with pytest.raises(serving.RequestCancelled):
            gone.result(60)
    finally:
        eng.shutdown(drain=True, timeout=30)
    assert len(decode) >= 39
    assert 0.8 * wall < sum(decode) <= wall
    assert 0.9 * steps <= ahead < steps
    counters = eng.metrics.snapshot()["counters"]
    assert counters["steps_launched_ahead"] > ahead
    assert counters["columns_wasted"] == 1
    text = observe.prometheus_text(serving=eng.metrics)
    for name in ("steps_launched_ahead", "columns_wasted"):
        assert f"paddle_serving_{name}_total {counters[name]}" in text


def _contains(outer, inner):
    return outer["tid"] == inner["tid"] and outer["ts"] <= inner["ts"] \
        and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_an_idle_server_waits_under_loop_idle_a_busy_one_under_serving_loop(
        gpt):
    since = time.perf_counter() * 1e6   # an earlier thread's id may be mine
    eng = _engine(gpt).start()
    mine = eng._thread.ident

    def events():
        return [e for e in profiler.events()
                if e["tid"] == mine and e["ts"] >= since]

    try:
        time.sleep(0.1)
        names = [e["name"] for e in events()]
        assert names.count("loop.idle") >= 2
        assert "serving.loop" not in names and "serving.step" not in names
        req = eng.submit(_prompt(30, 9), max_new_tokens=4)
        req.result(60)
        time.sleep(0.05)
        seen = events()
    finally:
        eng.shutdown(drain=True, timeout=30)
    loops = [e for e in seen if e["name"] == "serving.loop"]
    steps = [e for e in seen if e["name"] == "serving.step"]
    assert steps and all(any(_contains(lp, st) for lp in loops)
                         for st in steps)
    for name in ("step.admit", "step.sample", "step.commit"):
        inside = [e for e in seen if e["name"] == name]
        assert inside and all(any(_contains(lp, e) for lp in loops)
                              for e in inside), name
    # the loop keeps a step in flight: an iteration's `serving.step` is
    # round the dispatch of one step and the read-back of the step
    # before it (the first iteration has nothing to read, the last ones
    # nothing to dispatch), each step dispatched and read exactly once
    made = eng.metrics.get("steps")
    for name in ("step.dispatch", "step.readback"):
        inside = [e for e in seen if e["name"] == name]
        assert len(inside) == made
        assert all(sum(_contains(st, e) for st in steps) == 1
                   for e in inside), name
    assert made <= len(steps) <= made + 1
    ahead = [st for st in steps
             if [e["name"] for e in sorted(
                 (e for e in seen if e["depth"] == st["depth"] + 1
                  and _contains(st, e)), key=lambda e: e["ts"])]
             == ["step.dispatch", "step.readback"]]
    assert len(ahead) == eng.metrics.get("steps_launched_ahead") > 0
    # the wait after the answer is idle again, and no idle wait lies
    # inside an iteration
    idle = [e for e in seen if e["name"] == "loop.idle"]
    assert idle[-1]["ts"] > loops[-1]["ts"]
    assert not any(_contains(lp, e) for lp in loops for e in idle)


def test_goodput_counts_a_serving_replicas_seconds_once():
    gp = observe.goodput({
        name: {"total_s": s} for name, s in {
            "serving.loop": 10.0, "dispatch": 1.0, "readback": 8.0,
            "device-step": 9.0, "sample": 0.25, "admit": 0.25,
            "commit": 0.5, "loop.idle": 2.0}.items()})
    assert gp["categories_s"]["productive"] == 9.0
    assert gp["categories_s"]["host"] == 1.0
    assert gp["categories_s"]["idle"] == 2.0
    assert gp["accounted_s"] == 12.0
    assert gp["goodput"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# the windowed getter
# ---------------------------------------------------------------------------


def test_latency_since_returns_exactly_the_samples_after_its_mark():
    m = ServingMetrics()
    writers, per = 8, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def write(base):
            for i in range(per):
                m.observe_latency("queue", base + i)
                m.observe_latency("decode", -1.0)

        first = [threading.Thread(target=write, args=(1e6 * (w + 1),))
                 for w in range(writers)]
        for t in first:
            t.start()
        for t in first:
            t.join(30)
        mark = m.latency_mark()
        assert mark == {"queue": writers * per, "decode": writers * per}
        second = [threading.Thread(target=write, args=(1e3 * (w + 1),))
                  for w in range(writers)]
        for t in second:
            t.start()
        seen = 0
        while any(t.is_alive() for t in second):
            got = m.latency_since(mark, "queue")
            assert len(got) >= seen and all(v < 1e6 for v in got)
            seen = len(got)
        for t in second:
            t.join(30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    got = m.latency_since(mark, "queue")
    assert sorted(got) == sorted(1e3 * (w + 1) + i for w in range(writers)
                                 for i in range(per))
    # each writer's own samples kept their order
    assert [v for v in got if 1e3 <= v < 2e3] == \
        [1e3 + i for i in range(per)]
    assert m.latency_since(mark, "e2e") == []
    assert m.latency_since(m.latency_mark(), "queue") == []


def test_latency_since_survives_the_fifo_trim(monkeypatch):
    monkeypatch.setattr(metrics_mod, "_MAX_SAMPLES", 100)
    m = ServingMetrics()
    for i in range(90):
        m.observe_latency("queue", float(i))
    mark = m.latency_mark()
    m.observe_latencies("queue", [1000.0 + i for i in range(60)])
    # 150 seen, the window keeps the last 100: all 60 are in it
    assert m.latency_since(mark, "queue") == \
        [1000.0 + i for i in range(60)]
    m.observe_latencies("queue", [2000.0 + i for i in range(90)])
    # 150 since the mark, 100 kept: the oldest 50 are gone, and said so
    got = m.latency_since(mark, "queue")
    assert got == [1000.0 + i for i in range(50, 60)] + \
        [2000.0 + i for i in range(90)]


# ---------------------------------------------------------------------------
# the benchmark's contract, pinned
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["queue", "decode", "prefill", "e2e"])
def test_series_the_benchmark_reads_keep_their_names(served, kind):
    srv, futs = served
    with srv.metrics._lock:         # as benchmarks/runners/serve.py does
        series = list(srv.metrics._latency.get(kind, ()))
    assert series and all(s >= 0 for s in series)
    if kind in ("queue", "e2e"):
        assert len(series) == len(futs)
    assert series == srv.metrics.latency_since({}, kind)


@pytest.mark.parametrize("counter", [
    "tokens_out", "prompt_tokens", "prefix_hit_tokens", "steps",
    "completed"])
def test_counters_the_benchmark_reads_count(served, counter):
    srv, futs = served
    want = {
        "tokens_out": sum(len(f.token_times) for f in futs),
        "prompt_tokens": sum(f.payload.size for f in futs),
        "prefix_hit_tokens": sum(f.prefix_hit_tokens for f in futs),
        "completed": len(futs),
    }
    got = srv.metrics.get(counter)
    assert got > 0
    if counter in want:
        assert got == want[counter]
    for silent in ("failed", "timeouts", "step_errors"):
        assert srv.metrics.get(silent) == 0


def test_spans_and_phases_the_benchmark_reads_keep_their_names(served):
    srv, _ = served
    steps = srv.metrics.get("steps")
    mine = srv.engine._thread.ident
    events = [e for e in profiler.events() if e["tid"] == mine]
    stepping = [e for e in events if e["name"] == "serving.step"]
    # every step of this server was made by its one thread: the
    # benchmark finds the driving thread by this span, which is round
    # each step's dispatch and each step's read-back, once each
    for half in ("step.dispatch", "step.readback"):
        halves = [e for e in events if e["name"] == half]
        assert len(halves) == steps
        assert all(sum(_contains(st, e) for st in stepping) == 1
                   for e in halves), half
    # one an iteration that launched or landed a step: a burst of n
    # steps takes n + 1 iterations with a step in flight
    assert steps <= len(stepping) <= steps + len(srv.metrics.latency_since(
        {}, "queue"))
    assert sum(1 for e in events if e["name"] == "step.sample") >= steps
    assert observe.timeline.total("sample") > 0
    assert srv.engine.compile_counts == {"decode": 1, "cow": 1}


# ---------------------------------------------------------------------------
# names of programs and kernels
# ---------------------------------------------------------------------------


def test_jitted_programs_say_which_program_ran(gpt):
    eng = _engine(gpt, spec_len=2, prefill_chunk=8)
    assert eng._decode.__name__ == "serving_step"
    assert eng._cow.__name__ == "serving_cow"
    assert eng._spec._draft.__name__ == "serving_draft"

    from paddle_tpu import nn
    from paddle_tpu.engine import Engine

    model = nn.Sequential(nn.Linear(4, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    train = Engine(model, opt, lambda out, y: ((out - y) ** 2).mean())
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    train.train_batch(x, x)
    assert train._step_fn.__name__ == "train_step"
    assert "train_step" in {e["name"] for e in observe.compile_events()}


def _kernel_names(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _flash_fwd():
    from paddle_tpu.ops import fused_ops

    q = jnp.zeros((4, 128, 64), jnp.bfloat16)
    return _kernel_names(
        lambda q, k, v, s: fused_ops._flash_fwd_pallas(
            q, k, v, s, 0.125, False, 0.0),
        q, q, q, jnp.zeros((), jnp.int32))


def _flash_bwd():
    from paddle_tpu.ops import fused_ops

    q = jnp.zeros((4, 128, 64), jnp.bfloat16)
    return _kernel_names(
        lambda q, k, v, o, lse, do, s: fused_ops._flash_bwd_pallas(
            q, k, v, o, lse, do, s, 0.125, False, 0.0),
        q, q, q, q, jnp.zeros((4, 128), jnp.float32), q,
        jnp.zeros((), jnp.int32))


def _loss(backward):
    from paddle_tpu.ops import fused_loss

    x = jnp.zeros((64, 128), jnp.float32)
    w = jnp.zeros((512, 128), jnp.float32)
    labels = jnp.zeros((64,), jnp.int32)
    if not backward:
        return _kernel_names(
            lambda x, w, l: fused_loss._fwd_pallas(x, w, l, 256),
            x, w, labels)
    rows = jnp.zeros((64,), jnp.float32)
    return _kernel_names(
        lambda x, w, l, lse, g: fused_loss._bwd_pallas(x, w, l, lse, g, 256),
        x, w, labels, rows, rows)


def _lowp(w8a8):
    from paddle_tpu.ops import lowp

    a = jnp.zeros((16, 128), jnp.float32)
    b = jnp.zeros((128, 128), jnp.float32)
    one = jnp.ones((), jnp.float32)
    if w8a8:
        return _kernel_names(
            lambda a, qb: lowp._w8a8_pallas(a, qb, one, one),
            a, b.astype(jnp.int8))
    return _kernel_names(
        lambda a, b: lowp._smm_pallas(a, b, one, one, jnp.int8), a, b)


def _dequant():
    from paddle_tpu.ops import quant_ops

    return _kernel_names(
        quant_ops._dq_mm_pallas, jnp.zeros((16, 128), jnp.float32),
        jnp.zeros((64, 128), jnp.int8), jnp.ones((64,), jnp.float32))


@pytest.mark.parametrize("trace,names", [
    (_flash_fwd, ["flash_fwd"]),
    (_flash_bwd, ["flash_dq", "flash_dkv"]),
    (lambda: _loss(False), ["lm_loss_fwd"]),
    (lambda: _loss(True), ["lm_loss_dx", "lm_loss_dw"]),
    (lambda: _lowp(False), ["lowp_scaled_matmul"]),
    (lambda: _lowp(True), ["w8a8_matmul"]),
    (_dequant, ["dequant_matmul"]),
], ids=["flash_fwd", "flash_bwd", "lm_loss_fwd", "lm_loss_bwd",
        "lowp_scaled_matmul", "w8a8_matmul", "dequant_matmul"])
def test_pallas_kernels_carry_a_name(trace, names):
    assert trace() == names
