"""Runner `serve_sessions`: one `serving.Server` of the hybrid
linear-attention family on one chip under multi-turn sessions.

A session is a document and then turn after turn over it, each turn's
prompt the WHOLE transcript so far, the engine's own earlier answers
included, plus some new tokens.  So the schedule cannot be drawn whole
in advance, as `traffic.open_loop` does: a turn's prompt exists only
once its predecessor has answered.  What is fixed in advance (from the
mix's `shape_seed`) is every length and every due time: turn `j` of
session `s` is due `offset_s + (j - 1) * turn_interval_s` into the
window, give or take `jitter` of an interval.  A turn whose predecessor
has not answered by then is sent when it has, and its latency still
counts from when it was due.  `--seed` draws the weights and the
tokens.  (A mix given `rate_rps`, as `sweep.py` gives, takes its
interval from it: `sessions / rate_rps`.)

Turn 0 of every session (the document) is sent and answered before the
window, through the same `submit`: it builds the session's K/V blocks
and its first state snapshot and is not measured.  Every window starts
from an empty prefix cache and fresh documents.

`correct` is decided in set-up by the benchmark's own float32 reference
(`reference_hybrid_linear.py`), through the engine's own compiled
programs at the cell's sizes, in two legs: a pinned prompt prefilled in
chunks and decoded through the cache; then a second turn over that
request's transcript, which has to resume from the state snapshot the
first left (not re-prefill), held to the reference's full forward over
the whole transcript.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from benchmarks import harness, reference_hybrid_linear, traffic
from benchmarks.harness import say
from benchmarks.stats import percentile

serve_latent = harness.load_module("runners", "serve_latent")
stepped_logits, logit_gaps = (serve_latent.stepped_logits,
                              serve_latent.logit_gaps)

COMPILE_COUNTS = {"decode": 1, "cow": 1, "snapshot": 1}
# The compiled step (bfloat16 weights, activations, K/V; float32
# recurrent state) against the float32 reference over the same weights
# cast up.  The gap at a position is the root mean square of the logit
# differences as a share of that position's logit standard deviation
# (`serve_latent.logit_gaps`); every compared position is held to the
# configuration's `check.logit_tol`.  With weights drawn from a seed
# the decay gates saturate (the mixers read the raw residual stream,
# whose size grows with depth, through a projection of standard
# deviation 0.02 and a gain of up to 16), so the rounding of bfloat16
# activations reaches the logits amplified: the logits tell a position
# off or a dropped layer, not the precision of the recurrent state.
# That is held by the second limit, `check.state_tol`, on the FIRST
# linear layer's state array itself after the second turn: its input is
# the embedding's rows, the same on both sides, so what reaches it is
# the filter, the products of the recurrence and the state's own
# precision.  PERF.md section 6 gives the readings both limits lie
# between: the program's over its seeds below them, and above, by one
# of them, the control: the reference with its recurrent state rounded
# to bfloat16 after every token (the nearest precision below the
# configuration's float32 state), which has to come out NOT correct.
# counters of the engine the readers need over the sending window
WINDOW_COUNTERS = ("computed_tokens", "attn_context_tokens", "tokens_out",
                   "steps")
DELTA_COUNTERS = ("tokens_out", "prompt_tokens", "prefix_hit_tokens",
                  "prefix_tokens_lost_to_state", "state_snapshot_hits",
                  "state_snapshots_taken", "state_snapshot_evictions",
                  "state_resets", "steps", "completed", "failed",
                  "timeouts", "step_errors")


def _build(cell):
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.nlp.transformers import (
        HybridLinearConfig, HybridLinearForCausalLM,
    )

    if cell.config["family"] != "hybrid_linear":
        raise SystemExit(f"runner serve_sessions has no builder for "
                         f"family {cell.config['family']!r}")
    cfg = HybridLinearConfig(**cell.config["model"])
    dep = cell.config["serving"]
    paddle.seed(cell.seed % (2 ** 31 - 1))
    was = paddle.get_default_dtype()
    paddle.set_default_dtype(dep["weight_dtype"])
    try:
        model = HybridLinearForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(was)
    srv = serving.Server(model, max_slots=dep["max_slots"],
                         max_seq_len=dep["max_seq_len"],
                         block_size=dep.get("block_size"),
                         num_blocks=dep["num_blocks"] or None,
                         prefill_chunk=dep["prefill_chunk"],
                         cache_dtype=dep["cache_dtype"],
                         snapshot_entries=dep["snapshot_entries"],
                         queue_cap=dep["queue_cap"])
    state = dict((name, dtype) for name, _, dtype
                 in srv.engine._layout.state)
    if state.get("S") != dep.get("state_dtype", "float32"):
        raise SystemExit(f"the configuration states a {dep['state_dtype']} "
                         f"recurrent state; the model keeps {state}")
    return cfg, model, srv


# -- what decides `correct` ---------------------------------------------------


def reference_logits(eng, cfg, tokens, rows, **kw):
    """The reference's full forward over `tokens`, on the weights the
    engine serves, at the positions `rows`."""
    import jax

    out = reference_hybrid_linear.forward(
        eng._values, vars(cfg), tokens, wrap=jax.jit, rows=rows, **kw)
    return np.asarray(out, np.float32)


def round_bf16(x):
    """`x` rounded to bfloat16's 8 bits of exponent and 7 of mantissa.
    By `lax.reduce_precision`: a conversion to bfloat16 and back does
    not survive the TPU compiler (it may keep the excess precision),
    and the control then reads a gap of exactly 0."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _worst(gaps, tol):
    return bool(np.isfinite(gaps).all() and gaps.max() <= tol), \
        f"worst {gaps.max():.4e}, mean {gaps.mean():.4e} against {tol}"


def state_gap(got, want):
    """The distance between two states of one layer ``[heads, d_k,
    d_v]`` as a share of the second's size (Frobenius norms)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _first_state(eng):
    """The first linear layer's recurrent state as the request that
    just left the engine left it (its slot is the last one freed, and
    a freed slot's rows stay as they were until the next admission)."""
    return np.asarray(eng._state[0][0][eng._free[-1]])


def _pinned_checks(cell, cfg, eng):
    check = cell.config["check"]
    n, steps, tol = (check[k] for k in ("prompt_tokens", "decode_steps",
                                        "logit_tol"))
    more, more_steps = check["turn_tokens"], check["turn_decode_steps"]
    state_tol = check["state_tol"]
    m = eng.metrics
    pinned = traffic.tokens(traffic.rng(cell.seed, 9), n, cfg.vocab_size)
    # leg (a): chunked prefill, then decode through the cache
    got, _, _, went = stepped_logits(eng, pinned, steps)
    rows = np.arange(n - 1, n + steps)
    want = reference_logits(eng, cfg, went[:-1], np.arange(n - 2, n + steps))
    ok_a, words_a = _worst(logit_gaps(got, want[1:]), tol)
    off_ok, off_words = _worst(logit_gaps(got, want[:-1]), tol)
    # leg (b): the same request's transcript plus new tokens; it has to
    # resume from the snapshot the first left at its last block boundary
    depth = (went.size - 1) // eng.block_size * eng.block_size
    before = {k: m.get(k) for k in ("state_snapshot_hits",
                                    "prefix_hit_tokens")}
    turn = np.concatenate([went, traffic.tokens(
        traffic.rng(cell.seed, 10), more, cfg.vocab_size)])
    got_b, _, _, went_b = stepped_logits(eng, turn, more_steps)
    hits = m.get("state_snapshot_hits") - before["state_snapshot_hits"]
    hit_depth = m.get("prefix_hit_tokens") - before["prefix_hit_tokens"]
    mine = _first_state(eng)
    rows_b = np.arange(turn.size - 1, turn.size + more_steps)
    theirs, low_states = [], []
    want_b = reference_logits(eng, cfg, went_b[:-1], rows_b, states=theirs)
    ok_b, words_b = _worst(logit_gaps(got_b, want_b), tol)
    gap = state_gap(mine, theirs[0])
    # the control: a recurrent state kept in bfloat16
    low = reference_logits(eng, cfg, went_b[:-1], rows_b,
                           state_round=round_bf16, states=low_states)
    low_ok, low_words = _worst(logit_gaps(low, want_b), tol)
    low_gap = state_gap(low_states[0], theirs[0])
    return [
        ("pinned_logits", ok_a,
         f"{rows.size} positions after {n} prompt tokens, rms |compiled - "
         f"reference| over the logit std: {words_a}"),
        ("pinned_control", not off_ok,
         f"one position off has to fail: {off_words}"),
        ("resumed_from_snapshot", hits == 1 and hit_depth == depth,
         f"the second turn hit {hits} snapshot(s), {hit_depth} tokens "
         f"deep, of a transcript written {went.size - 1} deep (snapshot "
         f"at {depth})"),
        ("resumed_logits", ok_b,
         f"{rows_b.size} positions of a second turn of {more} new tokens "
         f"over the transcript, rms |compiled - reference| over the logit "
         f"std: {words_b}"),
        ("resumed_state", bool(np.isfinite(gap) and gap <= state_tol),
         f"the first linear layer's state after the second turn "
         f"({went_b.size - 1} tokens; its input is the embedding's rows, "
         f"so nothing upstream reaches it), |compiled - reference| over "
         f"|reference|: {gap:.4e} against {state_tol}"),
        ("control_bf16_state", not (low_ok and low_gap <= state_tol),
         f"the reference with its recurrent state rounded to bfloat16 "
         f"every token has to fail one of the two limits: logits "
         f"{low_words}; state {low_gap:.4e} against {state_tol}"),
    ]


# -- the sessions -------------------------------------------------------------


class _Session:
    """One session's lengths and due times (from `shape_seed`), its
    token stream (from `--seed`) and its transcript."""

    def __init__(self, mix, seed, index, vocab, interval, seconds, offset):
        shape = traffic.rng(mix["shape_seed"], 11, index)
        self.index, self.vocab = index, vocab
        self._tok = traffic.rng(seed, 12, index)
        self.document_n = traffic._length(shape, mix["document_tokens"])
        self.first_answer_n = traffic._length(shape, mix["answer_tokens"])
        jitter = float(mix.get("jitter", 0.0))
        self.turns = []          # (due_s, new tokens, answer tokens)
        j = 0
        while offset + j * interval < seconds:
            due = offset + interval * (
                j + jitter * (2.0 * shape.random_sample() - 1.0))
            self.turns.append((min(max(due, 0.0), seconds),
                               traffic._length(shape, mix["turn_tokens"]),
                               traffic._length(shape, mix["answer_tokens"])))
            j += 1
        self.transcript = None
        self.sent = 0            # window turns sent so far
        self.busy = False

    def document(self):
        return traffic.tokens(self._tok, self.document_n, self.vocab)

    def next_prompt(self, new_n):
        return np.concatenate([self.transcript,
                               traffic.tokens(self._tok, new_n, self.vocab)])


def _sessions(mix, seed, vocab, interval, seconds):
    n = int(mix["sessions"])
    # the sessions' first turns spread over one interval
    order = traffic.rng(mix["shape_seed"], 13).permutation(n)
    return [_Session(mix, seed, s, vocab, interval, seconds,
                     (order[s] + 0.5) / n * interval) for s in range(n)]


def _interval(mix):
    if "rate_rps" in mix:       # a sweep's turn rate
        return int(mix["sessions"]) / float(mix["rate_rps"])
    return float(mix["turn_interval_s"])


def _open_sessions(srv, sessions, timeout_s):
    """Turn 0 of every session, all at once, answered before the
    window: the documents."""
    t0 = time.perf_counter()
    futs = [srv.submit(s.document(), max_new_tokens=s.first_answer_n,
                       timeout=timeout_s) for s in sessions]
    for s, fut in zip(sessions, futs):
        s.transcript = np.asarray(fut.result(timeout=timeout_s), np.int32)
    say(f"serve_sessions: {len(sessions)} documents of "
        f"{sum(s.document_n for s in sessions)} tokens answered in "
        f"{time.perf_counter() - t0:.1f} s")


class _Turns:
    """Sends each session's turns when they are due and their
    predecessor has answered; completions arrive on `done` from the
    engine's thread."""

    def __init__(self, srv, sessions, timeout_s):
        self.srv, self.sessions, self.timeout_s = srv, sessions, timeout_s
        self.done = queue.SimpleQueue()
        self.records = []

    def _send(self, s, start):
        due_s, new_n, answer_n = s.turns[s.sent]
        s.sent += 1
        now = time.perf_counter()
        rec = {"session": s, "t_due": start + due_s, "t_sent": now,
               "t_done": None, "fut": None, "error": None,
               "prompt": None, "max_new": answer_n}
        self.records.append(rec)
        try:
            rec["prompt"] = s.next_prompt(new_n)
            rec["fut"] = self.srv.submit(rec["prompt"],
                                         max_new_tokens=answer_n,
                                         timeout=self.timeout_s)
        except Exception as e:  # noqa: BLE001 - a refusal is a failure
            rec["error"] = f"{type(e).__name__}: {e}"
            return
        s.busy = True

        def finished(_fut, rec=rec):
            rec["t_done"] = time.perf_counter()
            self.done.put(rec)

        rec["fut"].add_done_callback(finished)

    def _answered(self, rec):
        s = rec["session"]
        s.busy = False
        try:
            s.transcript = np.asarray(rec["fut"].result(0), np.int32)
        except Exception as e:  # noqa: BLE001 - counted and shown
            rec["error"] = f"{type(e).__name__}: {e}"
            s.sent = len(s.turns)       # the session ends here

    def run(self, start, until):
        """Until every turn is sent and answered, or `until`."""
        while True:
            now = time.perf_counter()
            waiting = [s for s in self.sessions
                       if not s.busy and s.sent < len(s.turns)]
            for s in waiting:
                if start + s.turns[s.sent][0] <= now:
                    self._send(s, start)
            if not any(s.busy or s.sent < len(s.turns)
                       for s in self.sessions) or now >= until:
                return
            idle = [start + s.turns[s.sent][0] for s in self.sessions
                    if not s.busy and s.sent < len(s.turns)]
            wake = min(idle + [until])
            try:
                self._answered(self.done.get(
                    timeout=max(wake - time.perf_counter(), 0.0)))
            except queue.Empty:
                pass


def _read_window(eng):
    from paddle_tpu import observe

    return {"t": time.perf_counter(),
            "counters": {k: eng.metrics.get(k) for k in WINDOW_COUNTERS},
            "snapshot_s": observe.timeline.total("snapshot")}


def measure(cell, mix, cfg, srv, tracer, seconds):
    """One window of `mix` against a started server: fresh documents
    first (not measured), then the turns on their schedule; requests in
    flight at the end finish for at most `drain_s`."""
    eng, metrics = srv.engine, srv.metrics
    timeout_s = cell.config["serving"]["request_timeout_s"]
    interval = _interval(mix)
    # every window starts from an empty prefix cache, as a run of the
    # cell does (the loop is idle here: the server has just started, or
    # the window before has drained)
    eng.spill_cache()
    sessions = _sessions(mix, cell.seed, cfg.vocab_size, interval, seconds)
    _open_sessions(srv, sessions, timeout_s)
    turns = _Turns(srv, sessions, timeout_s)
    before = {k: metrics.get(k) for k in DELTA_COUNTERS}
    series = {k: len(serve_latent.serve._series(metrics, k))
              for k in ("queue", "decode", "prefill")}
    window_start = time.perf_counter()
    at_start = _read_window(eng)
    tracer.open()
    deadline = window_start + seconds
    turns.run(window_start, deadline)
    wait = deadline - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    window_end = time.perf_counter()
    at_end = _read_window(eng)
    in_flight_at_end = sum(1 for r in turns.records
                           if r["fut"] is not None and r["t_done"] is None)
    unsent = sum(len(s.turns) - s.sent for s in sessions)
    turns.run(window_start, window_end + float(mix["drain_s"]))
    drained = time.perf_counter()
    after = {k: metrics.get(k) for k in DELTA_COUNTERS}
    capture = tracer.close()

    # -- the answers ---------------------------------------------------------
    latencies, waits, wrong = [], [], []
    for rec in turns.records:
        if rec["fut"] is None or rec["t_done"] is None or rec["error"]:
            continue
        out = np.asarray(rec["fut"].result(0))
        prompt = rec["prompt"]
        if out.shape != (prompt.size + rec["max_new"],) \
                or not (out[:prompt.size] == prompt).all() \
                or not ((out >= 0) & (out < cfg.vocab_size)).all():
            wrong.append(rec)
        latencies.append(rec["t_done"] - rec["t_due"])
        waits.append(rec["t_sent"] - rec["t_due"]
                     + float(rec["fut"].queue_wait or 0.0))
    # a turn never sent (its predecessor failed, or was still out at
    # the end of the drain) was due all the same
    attempted = sum(len(s.turns) for s in sessions)
    failed = attempted - len(latencies)
    errors = sorted({r["error"] for r in turns.records if r["error"]})
    late = [r["t_sent"] - r["t_due"] for r in turns.records]
    elapsed = window_end - window_start
    wait_p90 = 1e3 * percentile(waits, 90) if waits else float("nan")
    say(f"serve sessions: {attempted} turns due in {elapsed:.3f} s at one "
        f"a session every {interval:.3f} s, {len(latencies)} answered, "
        f"{failed} failed, {in_flight_at_end} in flight and {unsent} "
        f"waiting for their predecessor at the end; drain took "
        f"{drained - window_end:.3f} s; (sent - due) + queue wait p90 "
        f"{wait_p90:.1f} ms; transcripts end at "
        f"{min(s.transcript.size for s in sessions)}-"
        f"{max(s.transcript.size for s in sessions)} tokens"
        + (f"; errors {errors[:3]}" if errors else ""))
    delta = {k: after[k] - before[k] for k in before}
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
        return serve_latent.serve._series(metrics, kind)[series[kind]:]

    window = {k: at_end["counters"][k] - at_start["counters"][k]
              for k in WINDOW_COUNTERS}
    window["seconds"] = at_end["t"] - at_start["t"]
    window["snapshot_s"] = at_end["snapshot_s"] - at_start["snapshot_s"]
    facts = {
        "delta": delta, "queue_s": tail("queue"),
        "step_s": tail("decode") + tail("prefill"),
        "answered": len(latencies), "in_flight_at_end": in_flight_at_end,
        "window": window,
        "late_mean_s": float(np.mean(late)) if late else 0.0,
        "late_max_s": max(late) if late else 0.0,
    }
    end_to_end = {"serve_tokens_per_s": window["tokens_out"] / elapsed}
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
    t0 = time.perf_counter()
    cfg, model, srv = _build(cell)
    eng = srv.engine
    weights = sum(int(v.nbytes) for v in eng._values.values())
    say(f"serve_sessions: built in {time.perf_counter() - t0:.1f} s: "
        f"{weights / 1e9:.3f} GB of weights, {eng.kv_pool_bytes / 1e9:.3f} "
        f"GB of K/V pool ({eng.num_blocks} blocks of {eng.block_size}), "
        f"{eng.state_bytes / 1e9:.3f} GB of state ({eng.max_slots} slots "
        f"and {eng.snapshot_entries} snapshot entries)")
    t0 = time.perf_counter()
    eng.warmup()
    say(f"serve_sessions: warm-up {time.perf_counter() - t0:.1f} s, "
        f"compile counts {eng.compile_counts}")
    checks = [("warmup_compile_counts",
               eng.compile_counts == COMPILE_COUNTS,
               str(eng.compile_counts))]
    t0 = time.perf_counter()
    checks += _pinned_checks(cell, cfg, eng)
    say(f"serve_sessions: both legs checked against the reference in "
        f"{time.perf_counter() - t0:.1f} s")
    srv.start()
    return cfg, srv, checks


def run(cell, tracer):
    import paddle_tpu as paddle

    cfg, srv, checks = set_up(cell)
    try:
        outcome = measure(cell, cell.mix, cfg, srv, tracer, cell.seconds)
    finally:
        srv.shutdown(drain=False)
    eng = srv.engine
    counts = eng.compile_counts
    steps = eng.metrics.get("steps")
    outcome["checks"] = checks + outcome["checks"] + [
        ("no_compile_in_window", counts == COMPILE_COUNTS,
         f"{counts} after the last request"),
        ("pools_in_place",
         eng.metrics.get("pool_inplace_steps") == steps,
         f"pool_inplace_steps {eng.metrics.get('pool_inplace_steps')} of "
         f"{steps} steps")]
    stats = paddle.device.memory_stats()
    outcome["memory_peak_bytes"] = max(stats.get("peak_bytes_in_use", -1),
                                       stats.get("bytes_in_use", 0))
    outcome["driver_span"] = "serving.step"
    return outcome
