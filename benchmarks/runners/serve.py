"""Runner `serve`: one `serving.Server` on one chip under a traffic mix.

Set-up builds the model from the seed, warms the engine's two programs
(the unified step and the copy-on-write copy), and sends one pinned
prompt through the compiled step and through the eager forward of the
same weights: `chip_smoke.py`'s server leg.  The window then sends the
mix's requests from this one thread (completions come back through
`Request.add_done_callback`, so there is no thread per client), stops
sending when it ends, and lets requests in flight finish for at most
the mix's `drain_s`; what is still unfinished then has failed.

Latency is the whole answer's, from the moment the request was due
(open loop) or sent (closed loop): the server has no streaming, so that
is what its user waits for.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from benchmarks import traffic
from benchmarks.harness import say
from benchmarks.stats import percentile

# compiled step against eager forward: max |logit gap| as a share of
# the logits' standard deviation.  The eager forward keeps keys and
# values in float32; the served configuration keeps them in a bf16
# cache, so the two may differ by bf16's rounding of the cached rows.
# On the v5e, over six seeds, the gap was 0.0 for four and 1.9 % and
# 2.3 % of the standard deviation for two, while a prompt one position
# off measured 230-260 % (my chip runs, PR 25).  5 % leaves the
# rounding twice its measured room and a slip fifty times too far.
LOGIT_TOL = 0.05
COMPILE_COUNTS = {"decode": 1, "cow": 1}


def _build(cell):
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining

    if cell.config["family"] != "gpt":
        raise SystemExit(f"runner serve has no builder for family "
                         f"{cell.config['family']!r}")
    cfg = GPTConfig(use_parallel=False, **cell.config["model"])
    dep = cell.config["serving"]
    paddle.seed(cell.seed % (2 ** 31 - 1))
    model = GPTForPretraining(cfg)
    srv = serving.Server(model, max_slots=dep["max_slots"],
                         max_seq_len=dep["max_seq_len"],
                         num_blocks=dep["num_blocks"] or None,
                         cache_dtype=dep["cache_dtype"],
                         queue_cap=dep["queue_cap"])
    return cfg, model, srv


def _prefill_logits(eng, prompt):
    """One request through an IDLE engine, step by step from this
    thread; returns the logits its last prefill step handed to
    sampling (the compiled step's answer for the next token)."""
    fut = eng.submit(np.asarray(prompt, np.int32), max_new_tokens=2,
                     timeout=None)
    eng._admit()
    first = None
    while eng.active:
        eng._step()
        if first is None:
            for s in eng._slots:
                if s is not None and s.state == "decode" \
                        and s.next_logits is not None:
                    first = np.asarray(s.next_logits).copy()
    fut.result(timeout=60)
    return first


def _pinned_checks(paddle, cell, cfg, model, eng):
    chunk = eng.prefill_chunk
    pinned = traffic.tokens(traffic.rng(cell.seed, 9), 2 * chunk + 7,
                             cfg.vocab_size)
    got = _prefill_logits(eng, pinned)
    eager = np.asarray(model(paddle.to_tensor(pinned[None, :]))._value)[0] \
        .astype(np.float32)
    want, spread = eager[-1], float(eager[-1].std())
    err = float(np.abs(got - want).max())
    slip = float(np.abs(got - eager[-2]).max())
    return [
        ("pinned_logits",
         bool(np.isfinite(got).all()) and err <= LOGIT_TOL * spread,
         f"max |compiled - eager| {err:.3e} against {LOGIT_TOL} x logit "
         f"std {spread:.3e}"),
        ("pinned_control", slip > 10 * LOGIT_TOL * spread,
         f"one position off measures {slip:.3e}"),
    ]


class _Sender:
    """Submits requests and keeps their records; completions arrive on
    `done` from the engine's thread."""

    def __init__(self, srv, timeout_s):
        self.srv = srv
        self.timeout_s = timeout_s
        self.done = queue.SimpleQueue()
        self.records = []

    def send(self, item, due=None):
        now = time.perf_counter()
        rec = {"item": item, "t_ref": now if due is None else due,
               "t_done": None, "fut": None, "error": None}
        self.records.append(rec)
        try:
            rec["fut"] = self.srv.submit(
                item.prompt, max_new_tokens=item.max_new,
                timeout=self.timeout_s)
        except Exception as e:  # noqa: BLE001 - a refusal is a failure
            rec["error"] = f"{type(e).__name__}: {e}"
            return rec

        def finished(_fut, rec=rec):
            rec["t_done"] = time.perf_counter()
            self.done.put(rec)

        rec["fut"].add_done_callback(finished)
        return rec

    def wait_all(self, until):
        """Wait for every accepted request, at most until `until`."""
        def pending():
            return any(r["fut"] is not None and r["t_done"] is None
                       for r in self.records)
        while pending() and time.perf_counter() < until:
            try:
                self.done.get(timeout=max(until - time.perf_counter(), 0))
            except queue.Empty:
                break


def _closed_loop(mix, seed, sender, vocab, deadline):
    gen = traffic.ClosedLoop(mix, seed, vocab)
    for c in range(gen.clients):
        sender.send(gen.next(c))
    while True:
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        try:
            rec = sender.done.get(timeout=left)
        except queue.Empty:
            return
        if time.perf_counter() < deadline:
            sender.send(gen.next(rec["item"].client))


def _open_loop(sender, window_start, deadline, items):
    late = []
    for item in items:
        due = window_start + item.due_s
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late.append(time.perf_counter() - due)
        sender.send(item, due=due)
    wait = deadline - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    # a starved generator must not read as a fast server
    say(f"serve: generator ran late by {1e3 * float(np.mean(late)):.3f} ms "
        f"on average, {1e3 * max(late):.3f} ms at most, over "
        f"{len(late)} arrivals")
    return {"late_mean_s": float(np.mean(late)), "late_max_s": max(late)}


def _series(metrics, kind):
    with metrics._lock:
        return list(metrics._latency.get(kind, ()))


def _snapshot(metrics, observe):
    return {
        "counters": {k: metrics.get(k) for k in (
            "tokens_out", "prompt_tokens", "prefix_hit_tokens", "steps",
            "completed", "failed", "timeouts", "step_errors")},
        "series_len": {k: len(_series(metrics, k))
                       for k in ("queue", "decode", "prefill", "e2e")},
        "sample_s": observe.timeline.total("sample"),
    }


def measure(cell, mix, cfg, srv, tracer, seconds):
    """One window of `mix` against a started server: send, stop at the
    end, let requests in flight finish for at most `drain_s`."""
    from paddle_tpu import observe

    items = None
    if mix["loop"] == "open":
        items = traffic.open_loop(mix, seconds, cell.seed, cfg.vocab_size)
    sender = _Sender(srv, cell.config["serving"]["request_timeout_s"])
    facts = {}
    before = _snapshot(srv.metrics, observe)
    window_start = time.perf_counter()
    tracer.open()
    deadline = window_start + seconds
    if mix["loop"] == "closed":
        _closed_loop(mix, cell.seed, sender, cfg.vocab_size, deadline)
    else:
        facts.update(_open_loop(sender, window_start, deadline, items))
    window_end = time.perf_counter()
    tokens_at_end = srv.metrics.get("tokens_out")
    in_flight_at_end = sum(1 for r in sender.records
                           if r["fut"] is not None and r["t_done"] is None)
    sender.wait_all(window_end + float(mix["drain_s"]))
    drained = time.perf_counter()
    after = _snapshot(srv.metrics, observe)
    capture = tracer.close()

    # -- the answers ---------------------------------------------------------
    latencies, failed, wrong = [], 0, []
    for rec in sender.records:
        item = rec["item"]
        if rec["fut"] is None or rec["t_done"] is None:
            failed += 1
            continue
        try:
            out = np.asarray(rec["fut"].result(0))
        except Exception as e:  # noqa: BLE001 - counted and shown
            failed += 1
            rec["error"] = f"{type(e).__name__}: {e}"
            continue
        if out.shape != (item.prompt.size + item.max_new,) \
                or not (out[:item.prompt.size] == item.prompt).all() \
                or not ((out >= 0) & (out < cfg.vocab_size)).all():
            wrong.append(rec)
        latencies.append(rec["t_done"] - rec["t_ref"])
    errors = sorted({r["error"] for r in sender.records if r["error"]})
    attempted = len(sender.records)
    elapsed = window_end - window_start
    say(f"serve {mix['loop']} loop: {attempted} requests due in "
        f"{elapsed:.3f} s, {len(latencies)} answered, {failed} failed, "
        f"{in_flight_at_end} in flight at the end; drain took "
        f"{drained - window_end:.3f} s"
        + (f"; errors {errors[:3]}" if errors else ""))
    delta = {k: after["counters"][k] - before["counters"][k]
             for k in before["counters"]}
    checks = [
        ("answers", not wrong and len(latencies) > 0,
         f"{len(latencies) - len(wrong)} of {len(latencies)} answers echo "
         "their prompt at the length asked"),
        ("no_engine_errors",
         not (delta["failed"] or delta["timeouts"] or delta["step_errors"]),
         f"failed {delta['failed']}, timeouts {delta['timeouts']}, "
         f"step errors {delta['step_errors']}"),
    ]

    def tail(kind):
        return _series(srv.metrics, kind)[before["series_len"][kind]:]

    facts.update({
        "delta": delta,
        "queue_s": tail("queue"),
        "decode_step_s": tail("decode"),
        "sample_s": after["sample_s"] - before["sample_s"],
        "answered": len(latencies),
        "in_flight_at_end": in_flight_at_end,
    })
    end_to_end = {
        "serve_tokens_per_s":
            (tokens_at_end - before["counters"]["tokens_out"]) / elapsed,
    }
    if latencies:
        end_to_end["request_p50_ms"] = 1e3 * percentile(latencies, 50)
        end_to_end["request_p90_ms"] = 1e3 * percentile(latencies, 90)
    return {
        "attempted": attempted, "failed": failed, "checks": checks,
        "window_start": window_start, "end_to_end": end_to_end,
        "facts": facts, "capture": capture,
    }


def set_up(cell):
    """Build, warm and check the server; returns it started."""
    import paddle_tpu as paddle

    cfg, model, srv = _build(cell)
    eng = srv.engine
    t0 = time.perf_counter()
    eng.warmup()
    say(f"serve: warm-up {time.perf_counter() - t0:.1f} s, compile counts "
        f"{eng.compile_counts}")
    checks = [("warmup_compile_counts",
               eng.compile_counts == COMPILE_COUNTS,
               str(eng.compile_counts))]
    t0 = time.perf_counter()
    checks += _pinned_checks(paddle, cell, cfg, model, eng)
    say(f"serve: pinned prompt checked in {time.perf_counter() - t0:.1f} s")
    srv.start()
    return cfg, srv, checks


def run(cell, tracer):
    import paddle_tpu as paddle

    cfg, srv, checks = set_up(cell)
    try:
        outcome = measure(cell, cell.mix, cfg, srv, tracer, cell.seconds)
    finally:
        srv.shutdown(drain=False)
    counts = srv.engine.compile_counts
    outcome["checks"] = checks + outcome["checks"] + [
        ("no_compile_in_window", counts == COMPILE_COUNTS,
         f"{counts} after the last request")]
    stats = paddle.device.memory_stats()
    outcome["memory_peak_bytes"] = max(stats.get("peak_bytes_in_use", -1),
                                       stats.get("bytes_in_use", 0))
    outcome["driver_span"] = "serving.step"
    return outcome
