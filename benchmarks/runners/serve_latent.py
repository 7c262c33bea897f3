"""Runner `serve_latent`: one `serving.Server` of the latent-attention +
held-experts family on one chip under a traffic mix.

The window, the sender and the loops are `runners/serve.py`'s,
unchanged (`measure` here wraps that one).  What differs is the model
(built in the served dtype under `paddle.set_default_dtype`, so its
weights are drawn from the seed on the device once and the engine
serves those very arrays) and what decides `correct`: the benchmark's
own float32 reference (`reference_latent_moe.py`), not another path of
the same program.  A pinned prompt is prefilled in chunks through the
compiled step, then decoded through the cache; the logits the step
handed to sampling at the last prompt position and at each decode step
are held against the reference's full forward over the same tokens.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmarks import harness, reference_latent_moe, traffic
from benchmarks.harness import say
from benchmarks.stats import percentile

serve = harness.load_module("runners", "serve")
COMPILE_COUNTS = serve.COMPILE_COUNTS

# The compiled step (bfloat16 weights, activations and latent cache)
# against the float32 reference over the same weights cast up.  The gap
# at a position is the root mean square of the logit differences as a
# share of that position's logit standard deviation.  Readings on the
# v5e (my chip runs, PR 29; PERF.md section 6 names calls and seeds):
#
# - A router pick that ties within bfloat16's rounding of the hidden
#   state goes to another expert than the float32 reference's, and
#   that position then reads 0.07-0.31 where every other reads
#   0.018-0.025.  The step already returns the rows each held expert
#   computed, which for a step of one position are that position's
#   held picks, and the reference hands out its own: so a position
#   whose held picks are the reference's in every layer is held to
#   the configuration's `check.logit_tol` (0.04 for bfloat16; the
#   float32 rehearsal reads 2e-7 and is held to 1e-3), and only one
#   whose picks differ to LOGIT_FLIPPED_TOL (between the worst such
#   position and the 1.39-1.43 that the logits of the position before
#   read).
# - No more than MAX_FLIPPED_SHARE of the positions may differ in their
#   picks (2-8 of 25 did): a router that picks otherwise than the
#   reference's differs at every one.
# - The control: the reference with its latent cache rounded to fp8
#   (e4m3, the nearest precision below the configuration's bfloat16),
#   judged against itself unrounded by the same three limits, has to
#   come out NOT correct (it reads 0.107-0.132 where the picks agree).
LOGIT_FLIPPED_TOL = 0.7
MAX_FLIPPED_SHARE = 0.75
# counters of the engine the per-layer readers need over the window
WINDOW_COUNTERS = ("computed_tokens", "attn_context_tokens", "expert_rows",
                   "tokens_out", "steps")


def _build(cell):
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.nlp.transformers import (
        LatentMoEConfig, LatentMoEForCausalLM,
    )

    if cell.config["family"] != "latent_moe":
        raise SystemExit(f"runner serve_latent has no builder for family "
                         f"{cell.config['family']!r}")
    cfg = LatentMoEConfig(**cell.config["model"])
    dep = cell.config["serving"]
    paddle.seed(cell.seed % (2 ** 31 - 1))
    was = paddle.get_default_dtype()
    paddle.set_default_dtype(dep["weight_dtype"])
    try:
        model = LatentMoEForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(was)
    srv = serving.Server(model, max_slots=dep["max_slots"],
                         max_seq_len=dep["max_seq_len"],
                         num_blocks=dep["num_blocks"] or None,
                         prefill_chunk=dep["prefill_chunk"],
                         cache_dtype=dep["cache_dtype"],
                         queue_cap=dep["queue_cap"])
    return cfg, model, srv


def stepped_logits(eng, prompt, decode_steps):
    """One request through an IDLE engine, step by step from this
    thread. Returns the logits the compiled step handed to sampling
    after the last prefill step and after each of `decode_steps` decode
    steps (``[decode_steps + 1, V]``); for each of those steps the
    positions ``(first, end)`` it computed and the rows each held
    expert computed in it (``[expert layers, held]``: those positions'
    held picks, counted); and the tokens the request went through."""
    def counted():
        return (eng.metrics.get("computed_tokens"),
                np.array(eng.aux_totals.get("expert_rows", 0), np.int64))

    fut = eng.submit(np.asarray(prompt, np.int32),
                     max_new_tokens=decode_steps + 1, timeout=None)
    eng._admit()
    logits, spans, held, seen = [], [], [], None
    start = counted()[0]
    while eng.active:
        at, rows = counted()
        eng._step()
        end, rows_after = counted()
        for s in eng._slots:
            if s is not None and s.state == "decode" \
                    and s.next_logits is not None \
                    and s.next_logits is not seen:
                seen = s.next_logits
                logits.append(np.asarray(seen, np.float32).copy())
                spans.append((at - start, end - start))
                held.append(rows_after - rows)
    return (np.stack(logits), spans, held,
            np.asarray(fut.result(timeout=120)))


def logit_gaps(got, want):
    """Per compared position, the root mean square of the logit gap
    over that position's logit standard deviation."""
    return np.sqrt(((got - want) ** 2).mean(axis=-1)) / want.std(axis=-1)


def held_picks(picks, spans, cfg):
    """The reference's counterpart of `stepped_logits`' rows: of the
    `picks` it handed out (``[s, k]`` an expert layer), how often each
    held expert was picked at the positions of each span
    (``[spans, expert layers, held]``)."""
    first = cfg.ep_rank * cfg.num_experts
    out = np.zeros((len(spans), len(picks), cfg.num_experts), np.int64)
    for i, (a, b) in enumerate(spans):
        for j, sel in enumerate(picks):
            local = np.asarray(sel)[a:b].reshape(-1) - first
            local = local[(local >= 0) & (local < cfg.num_experts)]
            out[i, j] = np.bincount(local, minlength=cfg.num_experts)
    return out


def judge(gaps, differing, tol):
    """``(correct, words)`` of the gaps at the compared positions.
    `differing` ``[positions, expert layers]`` says of each how many
    rows of a held expert one side computed and the other did not;
    where there are none the gap may be `tol`."""
    flipped = differing.sum(axis=1) > 0
    worst = {kind: float(gaps[at].max()) if at.any() else 0.0
             for kind, at in (("same", ~flipped), ("flipped", flipped))}
    ok = bool(np.isfinite(gaps).all()
              and worst["same"] <= tol
              and worst["flipped"] <= LOGIT_FLIPPED_TOL
              and flipped.sum() <= MAX_FLIPPED_SHARE * gaps.size)
    shown = "; ".join(f"+{i} {gaps[i]:.3f} by layer "
                      f"{'/'.join(str(n) for n in differing[i])}"
                      for i in np.flatnonzero(flipped))
    return ok, (
        f"worst {worst['same']:.4e} against {tol} over the "
        f"{int((~flipped).sum())} positions whose held picks are the "
        f"reference's, {worst['flipped']:.4e} against {LOGIT_FLIPPED_TOL} "
        f"over the {int(flipped.sum())} (at most {MAX_FLIPPED_SHARE:.0%}) "
        f"whose picks differ" + (f": {shown}" if shown else ""))


def reference_logits(eng, cfg, tokens, **kw):
    """The reference's full forward over `tokens`, on the weights the
    engine serves, each leaf function compiled once."""
    import jax

    out = reference_latent_moe.forward(eng._values, vars(cfg), tokens,
                                       wrap=jax.jit, **kw)
    return np.asarray(out, np.float32)


def round_e4m3(x):
    """`x` rounded to what an fp8 (e4m3) cache scaled to its range
    would hand back: 3 mantissa bits, the largest magnitude at 448,
    subnormal step 2^-9. By arithmetic: the v5e has no fp8 unit, and
    a conversion to float8 and back does not survive its compiler."""
    import jax.numpy as jnp

    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    _, exponent = jnp.frexp(x * scale)
    step = jnp.exp2((jnp.maximum(exponent, -5) - 4).astype(x.dtype))
    return jnp.round(x * scale / step) * step / scale


def _pinned_checks(cell, cfg, eng):
    n, steps, tol = (cell.config["check"][k] for k in
                     ("prompt_tokens", "decode_steps", "logit_tol"))
    pinned = traffic.tokens(traffic.rng(cell.seed, 9), n, cfg.vocab_size)
    got, spans, held, went = stepped_logits(eng, pinned, steps)
    picks, low_picks = [], []
    want = reference_logits(eng, cfg, went[:-1], picks=picks)
    theirs = held_picks(picks, spans, cfg)
    # rows a held expert computed in the one and not in the other
    differing = np.abs(np.stack(held) - theirs).sum(axis=-1)
    ok, words = judge(logit_gaps(got, want[n - 1:]), differing, tol)
    off_ok, off_words = judge(logit_gaps(got, want[n - 2:-1]), differing,
                              tol)
    low = reference_logits(eng, cfg, went[:-1], picks=low_picks,
                           latent_round=round_e4m3)
    low_held = held_picks(low_picks, spans, cfg)
    low_ok, low_words = judge(
        logit_gaps(low[n - 1:], want[n - 1:]),
        np.abs(low_held - theirs).sum(axis=-1), tol)
    return [
        ("pinned_logits", ok,
         f"{len(spans)} positions after {n} prompt tokens, rms |compiled - "
         f"reference| over the logit std: {words}"),
        ("pinned_control", not off_ok,
         f"one position off has to fail: {off_words}"),
        ("pinned_control_cache", not low_ok,
         f"the reference with an fp8 (e4m3) cache has to fail: "
         f"{low_words}"),
    ]


class _Window:
    """Stands where the tracer stands in `serve.measure`, which opens
    it at the window's first moment and closes it after the drain: it
    reads the engine's counters then, and again from a timer when the
    sending window ends, so that the readers get what the window
    itself computed (the drain is not in it)."""

    def __init__(self, tracer, eng, seconds):
        self.tracer, self.eng, self.seconds = tracer, eng, seconds
        self.before = self.after = self._timer = None

    def _read(self):
        m = self.eng.metrics
        rows = self.eng.aux_totals.get("expert_rows")
        return {"t": time.perf_counter(),
                "counters": {k: m.get(k) for k in WINDOW_COUNTERS},
                "expert_rows": None if rows is None else np.array(rows)}

    def _end(self):
        if self.after is None:
            self.after = self._read()

    def open(self):
        self.before = self._read()
        self._timer = threading.Timer(self.seconds, self._end)
        self._timer.daemon = True
        self._timer.start()
        self.tracer.open()

    def close(self):
        self._timer.cancel()
        self._end()
        return self.tracer.close()

    def facts(self):
        a, b = self.before, self.after
        out = {k: b["counters"][k] - a["counters"][k]
               for k in WINDOW_COUNTERS}
        out["seconds"] = b["t"] - a["t"]
        if b["expert_rows"] is not None:
            was = 0 if a["expert_rows"] is None else a["expert_rows"]
            out["expert_rows_by_expert"] = \
                (b["expert_rows"] - was).tolist()
        return out


def measure(cell, mix, cfg, srv, tracer, seconds):
    """`serve.measure`'s window, plus what the readers of this cell's
    own metrics need: the engine's counters over the sending window and
    every step's duration."""
    # every window starts from an empty prefix cache, as a run of the
    # cell does: a sweep's later rates must not be served the documents
    # its earlier rates left cached (the loop is idle here: the server
    # has just started, or the window before has drained)
    srv.engine.spill_cache()
    window = _Window(tracer, srv.engine, seconds)
    mark = srv.metrics.latency_mark()
    outcome = serve.measure(cell, mix, cfg, srv, window, seconds)
    outcome["facts"]["window"] = window.facts()
    outcome["facts"]["step_s"] = \
        srv.metrics.latency_since(mark, "decode") \
        + srv.metrics.latency_since(mark, "prefill")
    return outcome


def set_up(cell):
    """Build, warm and check the server; returns it started."""
    t0 = time.perf_counter()
    cfg, model, srv = _build(cell)
    eng = srv.engine
    weights = sum(int(v.nbytes) for v in eng._values.values())
    say(f"serve_latent: built in {time.perf_counter() - t0:.1f} s: "
        f"{weights / 1e9:.3f} GB of weights, {eng.kv_pool_bytes / 1e9:.3f} "
        f"GB of latent pool ({eng.num_blocks} blocks of {eng.block_size})")
    t0 = time.perf_counter()
    eng.warmup()
    say(f"serve_latent: warm-up {time.perf_counter() - t0:.1f} s, compile "
        f"counts {eng.compile_counts}")
    checks = [("warmup_compile_counts",
               eng.compile_counts == COMPILE_COUNTS,
               str(eng.compile_counts))]
    t0 = time.perf_counter()
    checks += _pinned_checks(cell, cfg, eng)
    say(f"serve_latent: pinned prompt checked against the reference in "
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
