"""Fast decode (ISSUE 16): speculative decoding + the int8 weight path
in the unified SlotEngine step.

Tentpole teeth: speculative greedy decode is BITWISE identical to plain
greedy (every emitted token is an argmax over the same logits row the
plain engine would compute), self-draft acceptance is exactly 1.0, the
standalone rejection sampler reproduces the target distribution, the
verify step's bulk KV scatter writes the same pool rows the plain
engine's one-token steps write, and compile counters stay at one trace
per kind (`decode`/`draft`/`cow`) for an engine's whole life.

Satellites certified here: the `serving.draft` / `serving.verify` /
`serving.dequant` fault sites (a draft fault degrades the round to
plain decode — the slot survives with no lost or duplicated tokens),
quantized WeightVersion artifacts rolling out and bitwise rolling back
through the fleet, the `paddle_serving_spec_*` Prometheus family, and
the ``bench_serving.py --spec --smoke`` certification subprocess.
"""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observe, profiler, serving
from paddle_tpu.framework import faults
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.ops import quant_ops
from paddle_tpu.quantization import (
    SCALE_SUFFIX, dequantize_state, is_quantized_state,
    quantize_state_int8,
)
from paddle_tpu.serving import positions_to_rows
from paddle_tpu.serving.speculation import speculative_accept

REPO = Path(__file__).resolve().parent.parent
VOCAB = 97


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def draft_gpt():
    """A weaker, differently-shaped draft model over the same vocab —
    real rejection traffic for the draft/verify loop."""
    paddle.seed(5)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        1, VOCAB, (n,)).astype(np.int32)


def _drive(eng, prompt, max_new=6, snoop_first_logits=False, **gen):
    """Admit + step one request synchronously, mirroring `_loop`'s
    fail-all-on-step-error contract for deterministic fault tests."""
    fut = eng.submit(np.asarray(prompt, np.int32),
                     max_new_tokens=max_new, timeout=None, **gen)
    eng._admit()
    first = None
    while eng.active:
        try:
            eng._step()
        except Exception as e:  # noqa: BLE001 — _loop parity
            eng.metrics.inc("step_errors")
            eng._fail_all_active(e)
            eng._recover_pools(e)
        if snoop_first_logits and first is None:
            for s in eng._slots:
                if s is not None and s.state == "decode" \
                        and s.next_logits is not None:
                    first = np.asarray(s.next_logits).copy()
    return fut.result(10), first


def _engine(gpt, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    e = serving.SlotEngine(gpt, **kw)
    e.warmup()
    return e


# ---------------------------------------------------------------------------
# tentpole: bitwise greedy parity, acceptance, compile-once
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_greedy_parity_self_draft(gpt, k):
    """Speculative greedy == plain greedy BITWISE for spec_len 1/2/4
    (self-draft), across short and longer-than-chunk prompts — and the
    whole run costs exactly one decode, one draft, and one CoW trace."""
    plain = _engine(gpt)
    spec = _engine(gpt, spec_len=k)
    cases = [(_prompt(3, 5), 7), (_prompt(50, 29), 6), (_prompt(9, 12), 9)]
    for p, n in cases:
        want, _ = _drive(plain, p, max_new=n)
        got, _ = _drive(spec, p, max_new=n)
        np.testing.assert_array_equal(got, want)
    assert spec.compile_counts == {"decode": 1, "draft": 1, "cow": 1}
    assert plain.compile_counts == {"decode": 1, "cow": 1}
    # self-draft: q == p, so every proposal survives accept/reject
    snap = spec.metrics.snapshot()["speculative"]
    assert snap["acceptance_rate"] == 1.0
    assert snap["drafted_tokens"] > 0
    assert snap["rejected_tokens"] == 0


def test_spec_greedy_parity_weak_draft(gpt, draft_gpt):
    """Bitwise parity holds for a REAL (weaker, differently-shaped)
    draft model too: rejections cost speed, never tokens."""
    plain = _engine(gpt)
    spec = _engine(gpt, spec_len=3, draft_model=draft_gpt)
    for seed in (21, 22, 23):
        p = _prompt(seed, 7)
        want, _ = _drive(plain, p, max_new=8)
        got, _ = _drive(spec, p, max_new=8)
        np.testing.assert_array_equal(got, want)
    snap = spec.metrics.snapshot()["speculative"]
    # the weak draft must actually get rejected sometimes — otherwise
    # this test silently stopped exercising the rejection path
    assert 0.0 < snap["acceptance_rate"] < 1.0


def test_spec_sampling_self_draft_accepts_everything(gpt):
    """Leviathan accept on q == p: the ratio is 1, u < 1 always, so
    sampled self-draft acceptance is exactly 1.0 per slot."""
    spec = _engine(gpt, spec_len=2)
    out, _ = _drive(spec, _prompt(31, 6), max_new=8, do_sample=True,
                    top_k=20, seed=4)
    assert out.shape == (14,)
    snap = spec.metrics.snapshot()["speculative"]
    assert snap["acceptance_rate"] == 1.0
    assert all(v == 1.0 for v in snap["per_slot_acceptance"].values())


def test_what_a_verify_round_leaves_for_the_next_pick(gpt, draft_gpt):
    """After a step a decoding slot carries one of three things. The
    step's own pick with a handle on its row, where nothing was drafted
    for it (the step after its prefill). A host row of `verify` with no
    pick behind it, after a round with proposals: the next token is its
    argmax, taken on the host, and no `device_picks` is counted for it.
    Or nothing at all, after a sampling round's resample: the token is
    committed already and the next step only writes its KV."""
    spec = _engine(gpt, spec_len=3, draft_model=draft_gpt)
    futs = [spec.submit(_prompt(61, 7), max_new_tokens=12, timeout=None),
            spec.submit(_prompt(62, 5), max_new_tokens=12, timeout=None,
                        do_sample=True, seed=8, temperature=0.02)]
    spec._admit()
    seen = {"handle": 0, "host_row": 0, "none": 0}
    while spec.active:
        spec._step()
        for s in spec._slots:
            if s is None or s.state != "decode":
                continue
            if s.next_logits is None:
                assert s.req.gen.get("do_sample") and s.next_token is None
                seen["none"] += 1
            elif isinstance(s.next_logits, np.ndarray):
                assert s.next_token is None
                assert s.next_logits.shape == (VOCAB,)
                seen["host_row"] += 1
            else:
                row = np.asarray(s.next_logits)
                assert s.next_token == int(row.argmax())
                seen["handle"] += 1
    assert all(seen.values()), seen
    want, _ = _drive(_engine(gpt), _prompt(61, 7), max_new=12)
    np.testing.assert_array_equal(futs[0].result(5), want)
    assert futs[1].result(5).shape == (17,)
    # the greedy slot's first token is the only one a step picked
    assert spec.metrics.get("device_picks") == 1


def test_spec_len_widens_chunk_and_validates():
    paddle.seed(13)
    cfg = GPTConfig(vocab_size=31, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=16, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    e = serving.SlotEngine(m, max_slots=1, block_size=8, prefill_chunk=2,
                           spec_len=4)
    assert e.prefill_chunk >= 5          # room for [next, d_1..d_4]
    with pytest.raises(ValueError):
        serving.SlotEngine(m, max_slots=1, block_size=8, spec_len=16)


def test_speculative_accept_matches_target_distribution():
    """Rejection-sampling histogram: accepted-or-resampled tokens from
    (p, q) pairs distribute as p — the Leviathan et al. guarantee the
    engine's sampling path rides on."""
    v = 13
    rng = np.random.RandomState(0)
    p = rng.dirichlet(np.ones(v)).astype(np.float64)
    q = rng.dirichlet(np.ones(v)).astype(np.float64)
    n = 40000
    counts = np.zeros(v)
    for _ in range(n):
        d = int(rng.choice(v, p=q))
        a, resampled = speculative_accept([p], [q], [d], rng)
        counts[d if a == 1 else resampled] += 1
    tv = 0.5 * np.abs(counts / n - p).sum()
    assert tv < 0.02, f"total variation {tv:.4f} vs target"
    # degenerate residual (p == q at the proposal) falls back to p
    a, r = speculative_accept([p], [p], [3],
                              np.random.RandomState(1))
    assert a == 1 and r is None


def test_spec_bulk_scatter_writes_same_pool_rows(gpt):
    """The verify step's bulk KV scatter lands bitwise the same pool
    rows as the plain engine's one-token writes: read both pools back
    through `positions_to_rows` over the identical (ascending) block
    table and compare every committed position."""
    p = _prompt(77, 9)
    max_new = 8

    def pool_rows(eng):
        fut = eng.submit(np.asarray(p, np.int32), max_new_tokens=max_new,
                         timeout=None)
        eng._admit()
        table = None
        while eng.active:
            eng._step()
            for i, s in enumerate(eng._slots):
                if s is not None:
                    table = np.asarray(eng._bt[i]).copy()
        fut.result(10)
        # committed coverage: every prompt/emitted position except the
        # final sampled token (never fed back)
        positions = np.arange(p.size + max_new - 1)
        blk, off = positions_to_rows(table, positions, eng.block_size)
        return [np.asarray(a)[blk, off] for a in eng._arrays(eng._pools)]

    rows_plain = pool_rows(_engine(gpt))
    rows_spec = pool_rows(_engine(gpt, spec_len=3))
    for a, b in zip(rows_plain, rows_spec):
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6)


def test_spec_step_and_draft_update_their_pools_in_place(gpt, draft_gpt):
    """The verify step is handed the target's pools, each draft
    micro-step the draft's (2 heads over blocks of 8: `[nb, 8, 2, 8]`):
    what went in reads deleted afterwards, the engine holds live ones,
    and every step counted as in place."""
    eng = serving.SlotEngine(gpt, max_slots=2, block_size=8,
                             prefill_chunk=8, spec_len=3,
                             draft_model=draft_gpt)
    assert eng._pools[0][0].shape == (eng.num_blocks, 8, 4, 8)
    assert eng._spec.pools[0][0].shape == (eng.num_blocks, 8, 2, 8)
    built = eng._arrays(eng._pools) + eng._arrays(eng._spec.pools)
    eng.warmup()
    assert all(a.is_deleted() for a in built)
    fut = eng.submit(_prompt(41, 7), max_new_tokens=8, timeout=None)
    eng._admit()
    drafts = []
    real = eng._spec._draft

    def watched(*args):
        handed = eng._arrays(args[5])
        out = real(*args)
        drafts.append(all(a.is_deleted() for a in handed))
        return out

    eng._spec._draft = watched
    while eng.active:
        target, n = eng._arrays(eng._pools), eng.metrics.get("steps")
        eng._step()
        assert all(a.is_deleted() for a in target) \
            == (eng.metrics.get("steps") > n)
        live = eng._arrays(eng._pools) + eng._arrays(eng._spec.pools)
        assert not any(a.is_deleted() for a in live)
    want, _ = _drive(_engine(gpt), _prompt(41, 7), max_new=8)
    np.testing.assert_array_equal(fut.result(5), want)
    assert drafts and all(drafts)
    assert eng.metrics.get("pool_inplace_steps") == \
        eng.metrics.get("steps") > 0


def test_plain_engine_passes_no_speculative_site_and_spans_no_draft(gpt):
    """An engine that holds no speculation consumes no occurrence of
    `serving.draft` / `serving.verify` (a schedule that raises at every
    one of them never fires) and puts no `step.draft` / `serving.draft`
    span in the ring; its steps still span sample and commit."""
    plain = _engine(gpt)
    since = time.perf_counter() * 1e6
    with faults.ChaosSchedule("serving.draft@1-:raise",
                              "serving.verify@1-:raise") as ch:
        got, _ = _drive(plain, _prompt(47, 9), max_new=6)
        assert ch.fired() == {"serving.draft": 0, "serving.verify": 0}
    assert got.size == 15 and plain.metrics.get("step_errors") == 0
    names = [e["name"] for e in profiler.events()
             if e["ts"] >= since and e["tid"] == threading.get_ident()]
    assert "step.draft" not in names and "serving.draft" not in names
    # the last iteration samples, finishes its one slot and steps nothing
    assert names.count("step.sample") - 1 == names.count("step.commit") \
        == plain.metrics.get("steps") > 0


def test_draft_call_that_raises_after_dispatch_degrades_the_round(gpt):
    """A draft micro-step that raises once it was handed the draft
    pools took them with it: the round degrades to plain decode, the
    draft cache starts over on empty pools and catches up, and the
    answer is still bitwise plain greedy's."""
    plain = _engine(gpt)
    spec = _engine(gpt, spec_len=3)
    p = _prompt(43, 9)
    want, _ = _drive(plain, p, max_new=10)
    real, calls = spec._spec._draft, []

    def broken(*args):
        out = real(*args)
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("device fell over")
        return out

    spec._spec._draft = broken
    got, _ = _drive(spec, p, max_new=10)
    np.testing.assert_array_equal(got, want)
    assert spec.metrics.get("spec_draft_faults") == 1
    assert spec.metrics.get("step_errors") == 0
    assert not any(a.is_deleted() for a in spec._arrays(spec._spec.pools))
    # drafting went on after the fault, on the rebuilt pools
    assert len(calls) > 3
    assert spec.metrics.snapshot()["speculative"]["acceptance_rate"] == 1.0


# ---------------------------------------------------------------------------
# int8 weight path
# ---------------------------------------------------------------------------


def test_dequant_matmul_reference_and_pallas_interpret(monkeypatch):
    """`dequant_matmul` == x @ dequant(q).T against the canonical
    formula on both the lax fallback and the Pallas kernel
    (interpret-mode on CPU via PADDLE_TPU_QUANT_FORCE=pallas)."""
    rng = np.random.RandomState(7)
    x = rng.randn(5, 20).astype(np.float32)
    w = rng.randn(37, 20).astype(np.float32)
    scale = np.float32(np.abs(w).max())
    q = np.clip(np.round(w / scale * 127), -127, 127).astype(np.int8)
    ref = x @ (q.astype(np.float32) * (scale / 127.0)).T

    monkeypatch.setenv("PADDLE_TPU_QUANT_FORCE", "lax")
    lax_out = np.asarray(quant_ops.dequant_matmul(
        jnp.asarray(x), jnp.asarray(q), jnp.asarray(scale)))
    np.testing.assert_allclose(lax_out, ref, rtol=1e-5, atol=1e-5)

    monkeypatch.setenv("PADDLE_TPU_QUANT_FORCE", "pallas")
    t0 = quant_ops._TRACE_COUNT
    pl_out = np.asarray(quant_ops.dequant_matmul(
        jnp.asarray(x), jnp.asarray(q), jnp.asarray(scale)))
    assert quant_ops._TRACE_COUNT > t0        # the kernel really ran
    np.testing.assert_allclose(pl_out, ref, rtol=1e-5, atol=1e-5)
    # leading batch dims reshape through the same kernel
    x3 = rng.randn(2, 3, 20).astype(np.float32)
    out3 = np.asarray(quant_ops.dequant_matmul(
        jnp.asarray(x3), jnp.asarray(q), jnp.asarray(scale)))
    assert out3.shape == (2, 3, 37)


def test_quantize_state_roundtrip_and_manifest(gpt):
    from paddle_tpu.engine import state_values

    vals = state_values(gpt)
    qvals = quantize_state_int8(vals)
    assert is_quantized_state(qvals) and not is_quantized_state(vals)
    frozen = [k for k in qvals if k.endswith(SCALE_SUFFIX)]
    assert frozen                              # 2-D floats froze
    for sk in frozen:
        leaf = sk[: -len(SCALE_SUFFIX)]
        assert np.asarray(qvals[leaf]).dtype == np.int8
        w = np.asarray(vals[leaf], np.float32)
        back = np.asarray(dequantize_state(
            {leaf: qvals[leaf], sk: qvals[sk]})[leaf])
        assert np.abs(back - w).max() <= float(qvals[sk]) / 127.0 + 1e-6


def test_int8_engine_logits_close_to_float(gpt):
    """int8-frozen decode stays within per-tensor-quantization
    tolerance of the bf16/f32 engine's logits, and greedy+speculative
    still run the full request pipeline on the frozen weights."""
    plain = _engine(gpt)
    quant = _engine(gpt, quantize=True)
    assert quant.quantized and not plain.quantized
    assert quant.metrics.snapshot()["speculative"]["dequant_path"] == 1.0
    p = _prompt(12, 6)
    _, f_logits = _drive(plain, p, max_new=4, snoop_first_logits=True)
    _, q_logits = _drive(quant, p, max_new=4, snoop_first_logits=True)
    scale = np.abs(f_logits).max()
    err = np.abs(q_logits - f_logits).max() / max(scale, 1e-9)
    assert err < 0.25, f"int8 logits off by {err:.3f} of full scale"
    # int8 + speculative compose: the spec engine's parity is against
    # its OWN int8 plain twin, bitwise
    qspec = _engine(gpt, quantize=True, spec_len=3)
    for seed in (41, 42):
        pr = _prompt(seed, 7)
        want, _ = _drive(quant, pr, max_new=6)
        got, _ = _drive(qspec, pr, max_new=6)
        np.testing.assert_array_equal(got, want)
    assert qspec.metrics.snapshot()["speculative"]["acceptance_rate"] \
        == 1.0


# ---------------------------------------------------------------------------
# fault sites: serving.draft / serving.verify / serving.dequant
# ---------------------------------------------------------------------------


def test_draft_fault_degrades_to_plain_decode(gpt):
    """A fault in the draft phase (serving.draft) degrades that round
    to plain decode: the slot survives, the output is STILL bitwise
    greedy — no lost or duplicated tokens — and the engine keeps
    speculating on later rounds."""
    plain = _engine(gpt)
    spec = _engine(gpt, spec_len=2)
    p = _prompt(63, 7)
    want, _ = _drive(plain, p, max_new=9)
    with faults.ChaosSchedule("serving.draft@2:raise") as ch:
        got, _ = _drive(spec, p, max_new=9)
        ch.verify()
    np.testing.assert_array_equal(got, want)
    snap = spec.metrics.snapshot()
    assert snap["speculative"]["draft_faults"] == 1
    assert snap["counters"].get("failed", 0) == 0
    # later rounds kept drafting: some proposals were accepted
    assert snap["speculative"]["accepted_tokens"] > 0


def test_verify_fault_fails_step_engine_survives(gpt):
    """serving.verify fires before the verify dispatch; a raise there
    is a step error — in-flight requests fail deterministically, the
    engine stays up and the next request is bitwise clean."""
    spec = _engine(gpt, spec_len=2)
    with faults.ChaosSchedule("serving.verify@2:raise") as ch:
        with pytest.raises(faults.FaultError):
            _drive(spec, _prompt(70, 6), max_new=8)[0]
        ch.verify()
    assert spec.metrics.get("step_errors") == 1
    plain = _engine(gpt)
    p = _prompt(71, 6)
    want, _ = _drive(plain, p, max_new=5)
    got, _ = _drive(spec, p, max_new=5)
    np.testing.assert_array_equal(got, want)


def test_dequant_fault_fires_once_per_quantized_step(gpt):
    """serving.dequant fires each decode step of an int8-frozen engine
    (and never for a float engine); a raise is a plain step error."""
    quant = _engine(gpt, quantize=True)
    with faults.ChaosSchedule("serving.dequant@1:raise") as ch:
        with pytest.raises(faults.FaultError):
            _drive(quant, _prompt(80, 5), max_new=4)[0]
        ch.verify()
    out, _ = _drive(quant, _prompt(81, 5), max_new=4)   # still serves
    assert out.shape == (9,)
    # float engines never pass the site: an exhausted-after-1 schedule
    # on a float drive would fire 0 times
    plain = _engine(gpt)
    with faults.ChaosSchedule("serving.dequant@1-:raise") as ch:
        out, _ = _drive(plain, _prompt(82, 5), max_new=3)
        assert out.shape == (8,)
        assert ch.fired().get("serving.dequant", 0) == 0


# ---------------------------------------------------------------------------
# quantized rollout artifacts
# ---------------------------------------------------------------------------


def test_quantized_weight_version_rolls_out_and_back(gpt):
    """ISSUE 16 satellite: a `WeightVersion.quantized_from` artifact —
    int8 leaves + @scale companions, all in the per-leaf sha256
    manifest, plus the dtype/scale quant summary — rolls out through
    the RolloutController's bitwise golden gate, serves on the dequant
    path, and bitwise-rolls-back, all without breaking compile-once."""
    from paddle_tpu.serving import (
        RolloutController, Router, WeightRegistry, WeightVersion,
    )

    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8),
                    hedge=False, retry_budget=3, liveness_timeout_s=30.0,
                    backoff_base_s=0.02, name="spec_ro").start()
    try:
        reg = WeightRegistry(gpt)
        ro = RolloutController(router, reg, canary_secs=0.05,
                               wave_size=1, poll_s=0.005,
                               replica_timeout_s=120.0,
                               slo_p99_ms=60000.0)
        wv1 = reg.add(WeightVersion.quantized_from(reg.get(0), 1))
        assert is_quantized_state(wv1.values)
        assert wv1.quant and all(
            rec["dtype"] == "int8" and rec["scale"] > 0.0
            for rec in wv1.quant.values())
        # every int8 leaf AND its @scale companion is manifest-covered
        # (manifest keys use the checkpoint layer's path format)
        for leaf in wv1.quant:
            assert any(leaf in k for k in wv1.manifest)
            assert any(leaf + SCALE_SUFFIX in k for k in wv1.manifest)
        assert "int8" in repr(wv1)

        assert ro.roll_to(1) is True, ro.error
        assert reg.current == 1
        probe = _prompt(90, 6)
        on_v1 = np.asarray(router.generate(probe, max_new_tokens=6,
                                           timeout=60.0))
        for r in router.replica_set.replicas:
            assert r.engine.quantized
            assert r.engine.compile_counts == {"decode": 1, "cow": 1}

        # canary-gate failure on the next target auto-rolls-back to the
        # pinned quantized version, bitwise
        reg.add(WeightVersion.quantized_from(reg.get(1), 2))
        with faults.ChaosSchedule("serving.canary@1:raise") as ch:
            assert ro.roll_to(2) is False
            ch.verify()
        assert ro.state == "rolled_back" and reg.current == 1
        back = np.asarray(router.generate(probe, max_new_tokens=6,
                                          timeout=60.0))
        np.testing.assert_array_equal(back, on_v1)
    finally:
        router.shutdown(drain=True)


# ---------------------------------------------------------------------------
# observability + bench certification
# ---------------------------------------------------------------------------


def test_spec_prometheus_family_and_snapshot(gpt):
    spec = _engine(gpt, spec_len=2, quantize=True)
    _drive(spec, _prompt(55, 6), max_new=8)
    text = observe.prometheus_text(serving=spec.metrics)
    for needle in ("paddle_serving_spec_drafted_tokens_total",
                   "paddle_serving_spec_accepted_tokens_total",
                   "paddle_serving_spec_rejected_tokens_total",
                   "paddle_serving_spec_acceptance_rate",
                   'paddle_serving_spec_slot_acceptance_rate{slot="',
                   "paddle_serving_spec_dequant_path 1"):
        assert needle in text, needle
    # counters are emitted by the generic loop exactly once
    assert sum(
        ln.startswith("paddle_serving_spec_drafted_tokens_total ")
        for ln in text.splitlines()) == 1
    snap = observe.snapshot(serving=spec.metrics)["serving"]
    assert snap["speculative"]["acceptance_rate"] == 1.0
    assert snap["speculative"]["dequant_path"] == 1.0


@pytest.mark.slow
def test_bench_serving_smoke_subprocess():
    """`bench_serving.py --spec --smoke` certifies compile-once, zero
    errors, and the greedy-parity digest in one subprocess. The >=2x
    speedup is asserted by the bench itself on its exit code; under a
    loaded CI box we tolerate a timing miss but never a correctness
    one."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench_serving.py"), "--spec",
         "--smoke"],
        capture_output=True, text=True, timeout=580,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"}, cwd=str(REPO))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    result = next(json.loads(ln) for ln in lines
                  if json.loads(ln).get("bench") == "BENCH_SERVING_SMOKE")
    assert result["greedy_parity"] is True
    assert result["base"]["errors"] == 0
    assert result["spec"]["errors"] == 0
    assert result["spec"]["digest"] == result["base"]["digest"]
    assert result["base"]["compiles"] == {"decode": 1, "cow": 1}
    assert result["spec"]["compiles"] == {"decode": 1, "draft": 1,
                                          "cow": 1}
    assert result["spec"]["acceptance_rate"] == 1.0
    timing_only = result.get("failures", []) and all(
        "speedup" in f for f in result.get("failures", []))
    assert proc.returncode == 0 or timing_only, \
        (proc.returncode, result.get("failures"), proc.stderr[-800:])
