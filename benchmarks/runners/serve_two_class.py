"""Runner `serve_two_class`: one `serving.Server` of the sliding-window +
full attention family on one chip under two request classes in ONE
queue.

The window's clock, the sender and the open loop are `runners/serve.py`'s
(`_Sender`, `_open_loop`, `_snapshot`, `_series`), unchanged.  What
differs is the model (built in the served dtype, so the engine serves
the very arrays drawn from the seed), the schedule, and what decides
`correct`.

**The schedule.**  The mix's `contexts` repository contexts (lengths
from `shape_seed`, tokens from `--seed`) are each asked once and
answered before the window, through the same `submit`, so that their
full-group chains and their window-group blocks are in the prefix cache
when the window opens; that is set-up and is not measured.  Then an
open loop at the mix's fixed rate (`traffic.arrival_times`): each
arrival is **long** with probability `long_share` (one of the contexts,
Zipf `zipf_s` over their ranks, plus a new tail of `tail_tokens`) or
**short** (an unshared prompt of `short_tokens`); both ask for
`answer_tokens`.  Every class, length, rank and arrival time comes from
`shape_seed`; `--seed` draws the tokens and deals the contexts to the
ranks.  Every window starts from an empty prefix cache and fresh
contexts.

**`correct`** is decided in set-up by the benchmark's own float32
reference (`reference_window_moe.py`), through the engine's own compiled
programs at the cell's sizes: a pinned prompt longer than two windows
is prefilled in chunks (blocks ARE freed behind the window) and decoded
through both block groups; the logits the step handed to sampling at
the last prompt position and at each decode step are held against the
reference's full forward over the same tokens, every position to
`check.logit_tol`.  Then the same prompt is asked
again: it has to hit both groups (`check.min_hit_tokens`) and agree as
well.

**Two scalings of one draw.**  The checks run on the weights as drawn
(every matrix at `initializer_range`): under them each layer's
attention moves the logits, so the comparison sees it.  Under the same
weights every position of a sequence comes to hold nearly one vector
(attention over random weights passes on what the hidden states have in
common, and amplifies it), picks nearly the same experts and answers
with the same few tokens, how nearly by seed; a step's time follows the
distinct experts its rows pick, so the cell spread 2.2-2.7 % over seeds
(PERF.md section 6).  Before the window opens `scale_weights` multiplies
the matrices the configuration's `timed_weights` names (the embedding
rows up, the attention output projections down): hidden states then
follow the token, the picks spread as a trained router's do, and a
step's time follows what its rows hold.  Same programs, same shapes and
dtypes; nothing compiles, and `measure` empties the prefix cache.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import harness, reference_window_moe, traffic
from benchmarks.harness import say
from benchmarks.stats import percentile

serve_latent = harness.load_module("runners", "serve_latent")
serve = serve_latent.serve
stepped_logits, logit_gaps, held_picks, round_e4m3 = (
    serve_latent.stepped_logits, serve_latent.logit_gaps,
    serve_latent.held_picks, serve_latent.round_e4m3)
COMPILE_COUNTS = serve.COMPILE_COUNTS

# The compiled step (bfloat16 weights, activations and K/V rows) against
# the float32 reference over the same weights cast up.  The gap at a
# position is the root mean square of the logit differences as a share
# of that position's logit standard deviation (`serve_latent.
# logit_gaps`), and EVERY compared position is held to the
# configuration's `check.logit_tol`.  Not judged by the picks, as the
# docqa cell is: with 12 expert layers of 64 experts a pick that ties
# within bfloat16's rounding falls the other way at 17-19 of the 25
# positions (the line says at how many, by `serve_latent.held_picks`),
# and a flipped pick of this model's softmax scores moves the logits
# little: such positions read 0.011-0.027 where the others read under
# 0.008 (my chip runs, PR 35).  PERF.md section 6 gives the readings
# the limit lies between: the program's over its seeds below it, and
# above it the two controls, which have to come out NOT correct: the
# logits one position off, and the reference with every weight matrix
# rounded to fp8 (e4m3, the nearest precision below the configuration's
# bfloat16), read against itself unrounded.
# counters of the engine the per-layer readers need over the window
WINDOW_COUNTERS = (
    "computed_tokens", "attn_context_tokens", "attn_window_context_tokens",
    "expert_rows", "tokens_out", "steps", "attn_key_tiles_full",
    "attn_key_tiles_window", "attn_key_tiles_max", "window_blocks_freed")
DELTA_COUNTERS = (
    "tokens_out", "prompt_tokens", "prefix_hit_tokens",
    "prefix_tokens_lost_to_window", "window_blocks_freed", "cow_splits",
    "steps", "completed", "failed", "timeouts", "step_errors")


def _build(cell):
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.nlp.transformers import (
        WindowMoEConfig, WindowMoEForCausalLM,
    )

    if cell.config["family"] != "window_moe":
        raise SystemExit(f"runner serve_two_class has no builder for "
                         f"family {cell.config['family']!r}")
    cfg = WindowMoEConfig(**cell.config["model"])
    dep = cell.config["serving"]
    paddle.seed(cell.seed % (2 ** 31 - 1))
    was = paddle.get_default_dtype()
    paddle.set_default_dtype(dep["weight_dtype"])
    try:
        model = WindowMoEForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(was)
    srv = serving.Server(model, max_slots=dep["max_slots"],
                         max_seq_len=dep["max_seq_len"],
                         block_size=dep.get("block_size"),
                         num_blocks=dict(dep["num_blocks"]),
                         prefill_chunk=dep["prefill_chunk"],
                         cache_dtype=dep["cache_dtype"],
                         queue_cap=dep["queue_cap"])
    return cfg, model, srv


# -- what decides `correct` ---------------------------------------------------


def reference_logits(eng, cfg, tokens, rows, **kw):
    """The reference's full forward over `tokens`, on the weights the
    engine serves, at the positions `rows`."""
    import jax

    out = reference_window_moe.forward(eng._values, vars(cfg), tokens,
                                       wrap=jax.jit, rows=rows, **kw)
    return np.asarray(out, np.float32)


def judge(gaps, mine, theirs, tol):
    """``(correct, words)``: every compared position's gap within
    `tol`. `mine` and `theirs` ``[positions, layers, experts]`` count
    the rows each expert computed at each position on either side: the
    words say at how many positions a pick differs, and the worst gap
    among those and among the rest."""
    flipped = np.abs(mine - theirs).sum(axis=(1, 2)) > 0
    worst = {kind: float(gaps[at].max()) if at.any() else 0.0
             for kind, at in (("same", ~flipped), ("flipped", flipped))}
    ok = bool(np.isfinite(gaps).all() and gaps.max() <= tol)
    return ok, (f"worst {gaps.max():.4e}, mean {gaps.mean():.4e} against "
                f"{tol} ({worst['same']:.4e} over the "
                f"{int((~flipped).sum())} positions whose expert picks are "
                f"the reference's, {worst['flipped']:.4e} over the "
                f"{int(flipped.sum())} where a pick differs)")


def _pinned_checks(cell, cfg, eng):
    check = cell.config["check"]
    n, steps, tol = (check[k] for k in ("prompt_tokens", "decode_steps",
                                        "logit_tol"))
    m = eng.metrics
    pinned = traffic.tokens(traffic.rng(cell.seed, 9), n, cfg.vocab_size)
    freed = m.get("window_blocks_freed")
    got, spans, held, went = stepped_logits(eng, pinned, steps)
    freed = m.get("window_blocks_freed") - freed
    rows = np.arange(n - 2, n + steps)
    picks, low_picks = [], []
    want = reference_logits(eng, cfg, went[:-1], rows, picks=picks)
    theirs = held_picks(picks, spans, cfg)
    ok, words = judge(logit_gaps(got, want[1:]), np.stack(held), theirs, tol)
    off_ok, off_words = judge(logit_gaps(got, want[:-1]), np.stack(held),
                              theirs, tol)
    # the same prompt again: it has to resume over both block groups
    hits = m.get("prefix_hit_tokens")
    got2, spans2, held2, went2 = stepped_logits(eng, pinned, steps)
    hits = m.get("prefix_hit_tokens") - hits
    # `stepped_logits` numbers positions from the first one computed
    spans2 = [(a + hits, b + hits) for a, b in spans2]
    if not np.array_equal(went2, went):
        # a tie between two tokens fell the other way: its own reference
        picks2 = []
        want2 = reference_logits(eng, cfg, went2[:-1], rows, picks=picks2)
        theirs2 = held_picks(picks2, spans2, cfg)
    else:
        want2, theirs2 = want, held_picks(picks, spans2, cfg)
    again_ok, again_words = judge(logit_gaps(got2, want2[1:]),
                                  np.stack(held2), theirs2, tol)
    # the control: every weight matrix in fp8
    low = reference_logits(eng, cfg, went[:-1], rows, picks=low_picks,
                           weight_round=round_e4m3)
    low_ok, low_words = judge(logit_gaps(low[1:], want[1:]),
                              held_picks(low_picks, spans, cfg), theirs, tol)
    return [
        ("pinned_logits", ok,
         f"{len(spans)} positions after {n} prompt tokens, rms |compiled - "
         f"reference| over the logit std: {words}"),
        ("pinned_control", not off_ok,
         f"one position off has to fail: {off_words}"),
        ("freed_behind_the_window", freed > 0,
         f"{freed} window-group blocks freed behind the window while the "
         f"pinned request ran"),
        ("asked_again_hits_both_groups", hits >= check["min_hit_tokens"],
         f"the second ask hit {hits} of {n} prompt tokens (at least "
         f"{check['min_hit_tokens']})"),
        ("asked_again_logits", again_ok,
         f"the second ask, resumed over both groups: {again_words}"),
        ("pinned_control_fp8", not low_ok,
         f"the reference with fp8 (e4m3) weights has to fail: {low_words}"),
    ]


def scale_weights(eng, factors):
    """Multiply, on the device and in its dtype, every weight the
    engine serves whose name ends in a key of `factors`."""
    for name, value in list(eng._values.items()):
        for suffix, factor in factors.items():
            if name.endswith(suffix):
                eng._values[name] = (value * float(factor)).astype(
                    value.dtype)


# -- the schedule -------------------------------------------------------------


def contexts_of(mix, seed, vocab):
    """The repository contexts of one window, by rank (0 the most
    asked): lengths from `shape_seed`, tokens from `--seed`."""
    shape = traffic.rng(mix["shape_seed"], 21)
    return [traffic.tokens(traffic.rng(seed, 22, rank),
                           traffic._length(shape, mix["context_tokens"]),
                           vocab)
            for rank in range(int(mix["contexts"]))]


def schedule(mix, seconds, seed, vocab, contexts):
    """The whole window, in order of time: `traffic.Item`s whose
    `client` is the context's rank for a long request and -1 for a
    short one."""
    shape = traffic.rng(mix["shape_seed"], 23)
    fresh = traffic.rng(seed, 24)
    p = np.arange(1, len(contexts) + 1, dtype=np.float64) \
        ** -float(mix["zipf_s"])
    p /= p.sum()
    items = []
    for t in traffic.arrival_times(mix, seconds):
        long_ = shape.random_sample() < float(mix["long_share"])
        rank = int(shape.choice(len(contexts), p=p))
        tail_n = traffic._length(shape, mix["tail_tokens"])
        short_n = traffic._length(shape, mix["short_tokens"])
        max_new = traffic._length(shape, mix["answer_tokens"])
        if long_:
            prompt = np.concatenate(
                [contexts[rank], traffic.tokens(fresh, tail_n, vocab)])
        else:
            prompt = traffic.tokens(fresh, short_n, vocab)
        items.append(traffic.Item(t, rank if long_ else -1, prompt,
                                  max_new))
    return items


def _ask_contexts(srv, mix, contexts, timeout_s):
    """Every context once, all at once, answered before the window."""
    shape = traffic.rng(mix["shape_seed"], 25)
    t0 = time.perf_counter()
    futs = [srv.submit(c, max_new_tokens=traffic._length(
        shape, mix["answer_tokens"]), timeout=timeout_s) for c in contexts]
    for fut in futs:
        fut.result(timeout=timeout_s)
    say(f"serve_two_class: {len(contexts)} contexts of "
        f"{sum(c.size for c in contexts)} tokens answered in "
        f"{time.perf_counter() - t0:.1f} s")


class _Window(serve_latent._Window):
    """`serve_latent`'s window (it stands where the tracer stands and
    reads the engine's counters when the window opens and, from a
    timer, when the sending window ends: the drain is not in it), over
    this cell's counters."""

    def _read(self):
        read = super()._read()
        read["counters"] = {k: self.eng.metrics.get(k)
                            for k in WINDOW_COUNTERS}
        return read

    def facts(self):
        out = super().facts()
        out.update({k: self.after["counters"][k] - self.before["counters"][k]
                    for k in WINDOW_COUNTERS})
        return out


def measure(cell, mix, cfg, srv, tracer, seconds):
    """One window of `mix` against a started server: fresh contexts
    first (not measured), then both classes on their schedule; requests
    in flight at the end finish for at most `drain_s`."""
    eng, metrics = srv.engine, srv.metrics
    timeout_s = cell.config["serving"]["request_timeout_s"]
    # every window starts from an empty prefix cache, as a run of the
    # cell does (the loop is idle here: the server has just started, or
    # the window before has drained)
    eng.spill_cache()
    contexts = contexts_of(mix, cell.seed, cfg.vocab_size)
    _ask_contexts(srv, mix, contexts, timeout_s)
    items = schedule(mix, seconds, cell.seed, cfg.vocab_size, contexts)
    sender = serve._Sender(srv, timeout_s)
    window = _Window(tracer, eng, seconds)
    before = {k: metrics.get(k) for k in DELTA_COUNTERS}
    series = {k: len(serve._series(metrics, k))
              for k in ("queue", "decode", "prefill")}
    window_start = time.perf_counter()
    window.open()
    deadline = window_start + seconds
    facts = serve._open_loop(sender, window_start, deadline, items)
    window_end = time.perf_counter()
    tokens_at_end = metrics.get("tokens_out")
    in_flight_at_end = sum(1 for r in sender.records
                           if r["fut"] is not None and r["t_done"] is None)
    sender.wait_all(window_end + float(mix["drain_s"]))
    drained = time.perf_counter()
    after = {k: metrics.get(k) for k in DELTA_COUNTERS}
    capture = window.close()

    # -- the answers ---------------------------------------------------------
    latencies, by_class, failed, wrong = [], {True: [], False: []}, 0, []
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
        by_class[item.client >= 0].append(latencies[-1])
    errors = sorted({r["error"] for r in sender.records if r["error"]})
    attempted = len(sender.records)
    elapsed = window_end - window_start

    def p50(values):
        return f"{1e3 * percentile(values, 50):.0f}" if values else "-"

    say(f"serve two classes: {attempted} requests due in {elapsed:.3f} s "
        f"({len(by_class[True])} long answered, p50 {p50(by_class[True])} "
        f"ms; {len(by_class[False])} short, p50 {p50(by_class[False])} ms), "
        f"{failed} failed, {in_flight_at_end} in flight at the end; drain "
        f"took {drained - window_end:.3f} s"
        + (f"; errors {errors[:3]}" if errors else ""))
    delta = {k: after[k] - before[k] for k in before}
    steps = serve._series(metrics, "decode")[series["decode"]:]
    if steps:
        say(f"serve two classes: a step with a decoding row took "
            f"{1e3 * percentile(steps, 50):.2f} ms at the median, "
            f"{1e3 * percentile(steps, 90):.2f} ms at the ninth decile, "
            f"over {len(steps)}")
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
        return serve._series(metrics, kind)[series[kind]:]

    facts.update({
        "delta": delta, "queue_s": tail("queue"),
        "step_s": tail("decode") + tail("prefill"),
        "answered": len(latencies), "in_flight_at_end": in_flight_at_end,
        "window": window.facts(),
        "long_s": by_class[True], "short_s": by_class[False],
    })
    end_to_end = {
        "serve_tokens_per_s":
            (tokens_at_end - before["tokens_out"]) / elapsed,
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
    t0 = time.perf_counter()
    cfg, model, srv = _build(cell)
    eng = srv.engine
    weights = sum(int(v.nbytes) for v in eng._values.values())
    say(f"serve_two_class: built in {time.perf_counter() - t0:.1f} s: "
        f"{weights / 1e9:.3f} GB of weights, {eng.kv_pool_bytes / 1e9:.3f} "
        f"GB of K/V pools ({eng._num_blocks} blocks of {eng.block_size}; "
        f"the window group's table {eng._window.entries} entries a slot)")
    t0 = time.perf_counter()
    eng.warmup()
    say(f"serve_two_class: warm-up {time.perf_counter() - t0:.1f} s, "
        f"compile counts {eng.compile_counts}")
    checks = [("warmup_compile_counts",
               eng.compile_counts == COMPILE_COUNTS,
               str(eng.compile_counts))]
    t0 = time.perf_counter()
    checks += _pinned_checks(cell, cfg, eng)
    say(f"serve_two_class: pinned prompt checked against the reference in "
        f"{time.perf_counter() - t0:.1f} s")
    scale_weights(eng, cell.config["timed_weights"])
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
