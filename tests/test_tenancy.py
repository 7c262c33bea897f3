"""Multi-tenant serving platform (ISSUE 20): batched LoRA adapter
banks inside the one compiled decode step, the (model, adapter,
version) artifact catalog, weighted-fair (deficit round robin)
per-tenant admission with token budgets, tier-based brownout, and
per-tenant metrics.

The invariants certified here:

- N adapters serve batched in ONE decode step: a mixed-adapter wave
  (with slot recycling) produces, per slot, tokens bitwise-equal to a
  single-adapter engine running that adapter alone; adapter row 0 is
  the base model and stays bitwise-identical to an adapter-less engine.
- Adapter banks hot-swap through the rollout-commit path with ZERO
  retraces: compile_counts stays {"decode": 1, "cow": 1} for engine
  life, and a mid-swap fault (site ``serving.adapter_swap``) aborts
  all-or-nothing — the OLD bank keeps serving bitwise.
- `TenantFairQueue` runs DRR weighted fair queueing: a flooding tenant
  only drains its own share; token budgets shed with a typed 429
  (`TenantBudgetError`) carrying the bucket's exact refill wait, and
  fault site ``serving.admit_tenant`` injects the same shed
  deterministically.
- The fleet Router sheds by tenant TIER during brownout when a
  `TenantDirectory` is attached, and `AdapterRollout` drives
  canary -> wave -> commit with all-or-nothing fleet-wide rollback.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.framework import faults
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (
    AdapterRollout, ArtifactCatalog, BrownoutShedError, Request, Router,
    TenantBudgetError, TenantDirectory, TenantFairQueue, TenantSpec,
)
from paddle_tpu.serving.engine import SlotEngine
from paddle_tpu.serving.tenancy import DEFAULT_TENANT, SLO_TIERS

VOCAB = 31
HIDDEN = 32
RANK = 4
N_ADAPTERS = 3


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def _bank(seed=7, scale=0.5):
    """A deterministic stacked adapter bank; row 0 all-zero (base)."""
    rng = np.random.RandomState(seed)
    la = np.zeros((N_ADAPTERS, RANK, HIDDEN), np.float32)
    lb = np.zeros((N_ADAPTERS, VOCAB, RANK), np.float32)
    la[1:] = rng.randn(N_ADAPTERS - 1, RANK, HIDDEN).astype(
        np.float32) * scale
    lb[1:] = rng.randn(N_ADAPTERS - 1, VOCAB, RANK).astype(
        np.float32) * scale
    return la, lb


def _prompt(seed, n=6):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(np.int32)


@pytest.fixture(scope="module")
def adapter_engine(gpt):
    """Shared adapter-bank engine: the parity/zero-retrace/fault tests
    reuse it so the compile-once invariant is checked ACROSS swaps and
    mixed waves."""
    eng = SlotEngine(gpt, max_slots=2, block_size=8,
                     max_adapters=N_ADAPTERS, lora_rank=RANK)
    eng.warmup()
    eng.start()
    la, lb = _bank()
    eng.swap_adapters(la, lb)
    yield eng
    eng.shutdown(drain=False)


@pytest.fixture(scope="module")
def ref_engine(gpt):
    """The single-adapter reference: same bank, but every wave it
    serves uses one adapter alone."""
    eng = SlotEngine(gpt, max_slots=2, block_size=8,
                     max_adapters=N_ADAPTERS, lora_rank=RANK)
    eng.warmup()
    eng.start()
    la, lb = _bank()
    eng.swap_adapters(la, lb)
    yield eng
    eng.shutdown(drain=False)


# ---------------------------------------------------------------------------
# tenant spec / directory
# ---------------------------------------------------------------------------


def test_tenant_spec_validation():
    with pytest.raises(ValueError):
        TenantSpec("x", slo_class="platinum")
    with pytest.raises(ValueError):
        TenantSpec("x", weight=0)
    s = TenantSpec("x", slo_class="gold")
    assert s.tier == SLO_TIERS["gold"] == 2
    assert s.unlimited and s.budget_remaining() is None


def test_token_bucket_debit_and_refill():
    s = TenantSpec("t", budget_tokens_per_s=100, burst_s=0.5)
    ok, wait = s.try_debit(40)
    assert ok and wait == 0.0
    ok, wait = s.try_debit(40)         # 10 left of the 50 burst
    assert not ok
    # refill must cover exactly the 30-token shortfall at 100 tok/s
    assert wait == pytest.approx(0.3, abs=0.05)
    assert s.budget_remaining() <= 50


def test_directory_resolve_and_brownout_floor():
    d = TenantDirectory([TenantSpec("gold-co", slo_class="gold")],
                        brownout_tier=1)
    assert d.resolve("gold-co").tier == 2
    assert d.resolve(None).name == DEFAULT_TENANT
    # unknown tenants auto-create a bronze default — admission never
    # fails on an unregistered name
    assert d.resolve("walk-in").tier == 0
    assert "walk-in" in d
    assert d.brownout_tier == 1
    snap = d.snapshot()
    assert snap["gold-co"]["slo_class"] == "gold"


def test_directory_mapping_form():
    d = TenantDirectory({"a": {"weight": 2.0},
                         "b": TenantSpec("b", priority=1)})
    assert d.resolve("a").weight == 2.0
    assert d.resolve("b").priority == 1


# ---------------------------------------------------------------------------
# weighted-fair admission
# ---------------------------------------------------------------------------


def _req(tenant, max_new=4, n=4):
    return Request(np.arange(1, n + 1, dtype=np.int32),
                   max_new_tokens=max_new, tenant=tenant)


def test_wfq_weighted_share_no_starvation():
    """A flooding weight-1 tenant cannot starve a weight-4 tenant: DRR
    serves the vip's whole backlog within the first rotation."""
    d = TenantDirectory([TenantSpec("flood", weight=1.0),
                         TenantSpec("vip", weight=4.0)])
    q = TenantFairQueue(64, tenancy=d, quantum=8)
    for _ in range(20):
        q.submit(_req("flood"))
    for _ in range(4):
        q.submit(_req("vip"))
    order = []
    while q.depth:
        r = q.pop(timeout=0.5)
        assert r is not None
        order.append(r.gen["tenant"])
    assert len(order) == 24
    # every vip head lands in the first 8 pops despite arriving last
    assert max(i for i, t in enumerate(order) if t == "vip") < 8
    depths = q.tenant_depths()
    assert depths == {}


def test_wfq_requeue_preserves_head_of_line(gpt):
    d = TenantDirectory()
    q = TenantFairQueue(8, tenancy=d, quantum=8)
    a, b = _req("t1"), _req("t1")
    q.submit(a)
    q.submit(b)
    got = q.pop(timeout=0.5)
    assert got is a
    q.requeue(got)
    assert q.pop(timeout=0.5) is a      # requeued head served first
    assert q.pop(timeout=0.5) is b


def test_budget_shed_carries_refill_wait():
    d = TenantDirectory([TenantSpec("tiny", budget_tokens_per_s=10,
                                    burst_s=1.0)])
    metrics = serving.ServingMetrics()
    q = TenantFairQueue(64, tenancy=d, metrics=metrics)
    q.submit(_req("tiny", max_new=2))     # cost 6 of the 10 burst
    with pytest.raises(TenantBudgetError) as ei:
        q.submit(_req("tiny", max_new=4))  # cost 8 > 4 left
    assert ei.value.status == 429
    assert ei.value.retriable
    assert 0 < ei.value.retry_after_s <= 1.0
    assert metrics.get("rejected_budget") == 1


def test_admit_tenant_fault_drop_sheds_one_tenant():
    """A ``drop`` at serving.admit_tenant is a deterministic per-tenant
    shed: the tagged tenant 429s, other tenants keep flowing."""
    d = TenantDirectory()
    metrics = serving.ServingMetrics()
    q = TenantFairQueue(64, tenancy=d, metrics=metrics)
    with faults.ChaosSchedule(
            "serving.admit_tenant[noisy]@1:drop") as ch:
        with pytest.raises(TenantBudgetError):
            q.submit(_req("noisy"))
        ok = q.submit(_req("quiet"))
        ch.verify()
    assert q.pop(timeout=0.5) is ok
    snap = metrics.snapshot()
    assert snap["tenants"]["noisy"]["counters"]["shed"] == 1


# ---------------------------------------------------------------------------
# batched adapters in the unified decode step
# ---------------------------------------------------------------------------


def test_adapter_zero_row_matches_base_engine(gpt, adapter_engine):
    """Adapter row 0 is the base model: with a live non-zero bank in
    rows 1.., adapter_id=0 must stay bitwise-identical to an engine
    built without adapters at all."""
    plain = SlotEngine(gpt, max_slots=2, block_size=8)
    plain.warmup()
    plain.start()
    try:
        p = _prompt(0)
        ref = plain.submit(p, max_new_tokens=8).result(60)
        out = adapter_engine.submit(p, max_new_tokens=8,
                                    adapter_id=0).result(60)
        np.testing.assert_array_equal(out, ref)
    finally:
        plain.shutdown(drain=False)


def test_mixed_adapter_wave_bitwise_vs_single_adapter(adapter_engine,
                                                      ref_engine):
    """The acceptance invariant: N adapters batched in one decode step,
    each slot's tokens bitwise-equal to a single-adapter engine running
    that adapter alone — across mixed waves AND slot recycling (3x more
    requests than slots)."""
    prompts = [_prompt(s) for s in range(6)]
    refs = {}
    for aid in range(N_ADAPTERS):
        # the reference serves each adapter ALONE (sequential waves)
        futs = [ref_engine.submit(p, max_new_tokens=8, adapter_id=aid)
                for p in prompts]
        refs[aid] = [f.result(60) for f in futs]
    # mixed wave: interleave all adapters at once over 2 slots
    futs = [(i, aid, adapter_engine.submit(
        prompts[i], max_new_tokens=8, adapter_id=aid,
        tenant=f"tenant-{aid}"))
        for i in range(6) for aid in range(N_ADAPTERS)]
    for i, aid, f in futs:
        np.testing.assert_array_equal(
            f.result(60), refs[aid][i],
            err_msg=f"prompt {i} adapter {aid} diverged in mixed wave")
    # different adapters on the same prompt actually decode differently
    assert not np.array_equal(refs[0][0], refs[1][0])
    assert not np.array_equal(refs[1][0], refs[2][0])


def test_the_steps_pick_follows_each_slots_adapter_delta(gpt):
    """The step picks AFTER the adapter delta is added: three slots
    hold the same prompt under the base model and two adapters whose
    deltas, at the prompt's last position, lift one token each far
    above the base logits. Each slot's first token is the one its own
    adapter says, and at every step the token committed is the first
    maximum of the row (delta included) behind the slot's handle."""
    prompt = _prompt(40, 9)
    hidden = np.asarray(gpt.gpt(paddle.to_tensor(prompt[None, :]))._value,
                        np.float32)[0, -1]
    base = int(np.asarray(
        gpt(paddle.to_tensor(prompt[None, :]))._value)[0, -1].argmax())
    lifted = {1: (base + 3) % VOCAB, 2: (base + 17) % VOCAB}
    la = np.zeros((N_ADAPTERS, RANK, HIDDEN), np.float32)
    lb = np.zeros((N_ADAPTERS, VOCAB, RANK), np.float32)
    for aid, token in lifted.items():
        la[aid, 0] = hidden / (hidden @ hidden)   # reads 1.0 at this h
        lb[aid, token, 0] = 50.0
    eng = SlotEngine(gpt, max_slots=3, block_size=8,
                     max_adapters=N_ADAPTERS, lora_rank=RANK)
    eng.warmup()
    eng.swap_adapters(la, lb)
    futs = [eng.submit(prompt, max_new_tokens=5, adapter_id=aid,
                       timeout=None) for aid in range(3)]
    eng._admit()
    checked = 0
    while eng.active:
        want = [(s, len(s.tokens), int(np.argmax(np.asarray(s.next_logits))))
                for s in eng._slots
                if s is not None and s.state == "decode"]
        eng._step()
        for s, at, token in want:
            assert s.tokens[at] == token
            checked += 1
    assert checked == 15
    firsts = [int(f.result(5)[prompt.size]) for f in futs]
    assert firsts == [base, lifted[1], lifted[2]]
    assert eng.metrics.get("device_picks") == 15
    assert eng.compile_counts == {"decode": 1, "cow": 1}


def test_adapter_swap_zero_retrace(adapter_engine):
    """Hot-swapping banks and serving every adapter must never retrace:
    compile_counts stays {decode: 1, cow: 1} for engine life."""
    la, lb = _bank(seed=23, scale=0.3)
    v0 = adapter_engine.adapter_version
    v1 = adapter_engine.swap_adapters(la, lb)
    assert v1 == v0 + 1
    futs = [adapter_engine.submit(_prompt(9), max_new_tokens=4,
                                  adapter_id=aid)
            for aid in range(N_ADAPTERS)]
    for f in futs:
        f.result(60)
    assert adapter_engine.compile_counts == {"decode": 1, "cow": 1}
    # restore the canonical bank for the other module tests
    adapter_engine.swap_adapters(*_bank())


def test_adapter_swap_validation(adapter_engine):
    la, lb = _bank()
    with pytest.raises(ValueError):       # wrong rank: rebuild, not swap
        adapter_engine.swap_adapters(la[:, :2], lb[:, :, :2])
    bad_a = la.copy()
    bad_a[0, 0, 0] = 1.0                  # row 0 must stay base
    with pytest.raises(ValueError):
        adapter_engine.swap_adapters(bad_a, lb)
    with pytest.raises(ValueError):       # id outside the bank
        adapter_engine.submit(_prompt(1), max_new_tokens=2,
                              adapter_id=N_ADAPTERS)


def test_mid_swap_fault_leaves_old_bank_serving_bitwise(adapter_engine):
    """serving.adapter_swap fires BEFORE any mutation: a faulted swap
    is all-or-nothing and the old bank keeps serving bitwise."""
    p = _prompt(3)
    before = [adapter_engine.submit(p, max_new_tokens=8,
                                    adapter_id=aid).result(60)
              for aid in range(N_ADAPTERS)]
    ver = adapter_engine.adapter_version
    la, lb = _bank(seed=99, scale=1.0)
    with faults.ChaosSchedule("serving.adapter_swap@1:raise") as ch:
        with pytest.raises(faults.FaultError):
            adapter_engine.swap_adapters(la, lb)
        ch.verify()
    assert adapter_engine.adapter_version == ver
    after = [adapter_engine.submit(p, max_new_tokens=8,
                                   adapter_id=aid).result(60)
             for aid in range(N_ADAPTERS)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert adapter_engine.compile_counts == {"decode": 1, "cow": 1}


def test_engine_without_adapters_rejects_swap_and_ids(gpt):
    eng = SlotEngine(gpt, max_slots=1, block_size=8)
    with pytest.raises(ValueError):
        eng.swap_adapters(*_bank())
    with pytest.raises(ValueError):
        eng.submit(_prompt(1), max_new_tokens=2, adapter_id=1)


# ---------------------------------------------------------------------------
# artifact catalog
# ---------------------------------------------------------------------------


def test_artifact_catalog_lines_and_digests():
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.serving.rollout import artifact_digest

    cat = ArtifactCatalog()
    w = {"w": np.arange(8, dtype=np.float32)}
    a1 = cat.add("model", "base", values=w)
    assert a1.version == 1 and a1.state == "registered"
    assert a1.digest == artifact_digest(ckpt.leaf_digests(
        {k: np.asarray(v) for k, v in w.items()}))
    la, lb = _bank()
    b1 = cat.add("adapter", "support-bot",
                 values={"lora_a": la, "lora_b": lb})
    b2 = cat.add("adapter", "support-bot",
                 values={"lora_a": la * 2, "lora_b": lb})
    assert (b1.version, b2.version) == (1, 2)
    assert b1.digest != b2.digest
    # lines roll independently: committing the adapter line never
    # touches the model line
    cat.commit("adapter", "support-bot", 2)
    assert cat.serving_version("adapter", "support-bot") == 2
    assert cat.serving_version("model", "base") is None
    assert cat.get("adapter", "support-bot").version == 2
    cat.commit("adapter", "support-bot", 1)    # roll back: 2 demoted
    assert b2.state == "registered" and b1.state == "serving"
    cat.retire("adapter", "support-bot", 1)
    with pytest.raises(KeyError):
        cat.get("adapter", "support-bot", 1)
    assert cat.get("adapter", "support-bot").version == 2  # latest live
    with pytest.raises(ValueError):
        cat.add("adapter", "support-bot", values={"x": la}, version=1)
    assert cat.lines() == [("adapter", "support-bot"),
                           ("model", "base")]


# ---------------------------------------------------------------------------
# fleet: tier brownout, adapter rollout, per-tenant export
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tenant_router(gpt):
    ten = TenantDirectory(
        [TenantSpec("gold-co", weight=4.0, slo_class="gold",
                    priority=2),
         TenantSpec("best-effort", weight=1.0, slo_class="bronze")],
        brownout_tier=1)
    router = Router(
        gpt, 2,
        engine_kw=dict(max_slots=2, block_size=8,
                       max_adapters=N_ADAPTERS, lora_rank=RANK),
        tenancy=ten, hedge=False, name="tenfleet")
    router.start()
    yield router
    router.shutdown(drain=False)


def test_router_sheds_by_tenant_tier_in_brownout(tenant_router):
    router = tenant_router
    router.set_brownout(True)
    try:
        with pytest.raises(BrownoutShedError):
            router.submit(_prompt(1), max_new_tokens=2,
                          tenant="best-effort")
        # gold rides through the same brownout
        out = router.submit(_prompt(1), max_new_tokens=2,
                            tenant="gold-co").result(60)
        assert out is not None
    finally:
        router.set_brownout(None)
    snap = router.metrics.snapshot()
    assert snap["tenants"]["best-effort"]["counters"]["shed"] >= 1
    assert snap["tenants"]["gold-co"]["counters"].get("shed", 0) == 0


def test_adapter_rollout_canary_wave_commit(tenant_router):
    ro = AdapterRollout(tenant_router, name="support-bot")
    la, lb = _bank(seed=31, scale=0.4)
    art = ro.roll_to(la, lb, probe=_prompt(2))
    assert ro.state == "committed" and ro.error is None
    assert ro.catalog.serving_version("adapter", "support-bot") \
        == art.version
    engines = [r.engine for r in tenant_router.replica_set.healthy()]
    assert all(e.adapter_version == art.version for e in engines)
    for e in engines:
        np.testing.assert_array_equal(np.asarray(e._lora_a), la)


def test_adapter_rollout_faulted_wave_rolls_back(tenant_router):
    """A fault on the SECOND replica's swap mid-wave restores the old
    bank on the already-swapped canary and retires the new version —
    all-or-nothing fleet-wide, bitwise."""
    engines = [r.engine for r in tenant_router.replica_set.healthy()]
    assert len(engines) == 2
    p = _prompt(4)
    before = [e.submit(p, max_new_tokens=8, adapter_id=1).result(60)
              for e in engines]
    vers = [e.adapter_version for e in engines]
    ro = AdapterRollout(tenant_router, name="support-bot")
    la, lb = _bank(seed=77, scale=0.9)
    with faults.ChaosSchedule("serving.adapter_swap@2:raise") as ch:
        with pytest.raises(faults.FaultError):
            ro.roll_to(la, lb)
        ch.verify()
    assert ro.state == "rolled_back"
    assert "FaultError" in ro.error
    new_ver = max(
        ro.catalog._lines[("adapter", "support-bot")])
    assert ro.catalog.serving_version("adapter",
                                      "support-bot") != new_ver
    after = [e.submit(p, max_new_tokens=8, adapter_id=1).result(60)
             for e in engines]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert [e.adapter_version for e in engines] == vers


def test_tenant_prometheus_families(tenant_router):
    from paddle_tpu import observe

    text = observe.prometheus_text(serving=tenant_router.metrics)
    assert 'paddle_tenant_completed_total{tenant="gold-co"}' in text
    assert 'paddle_tenant_qps{tenant="gold-co"}' in text
    assert 'paddle_tenant_shed_total{tenant="best-effort"}' in text
    assert 'paddle_tenant_latency_seconds{tenant="gold-co"' in text


# ---------------------------------------------------------------------------
# workload tenant mix + HTTP front
# ---------------------------------------------------------------------------


def test_workload_tenant_mix_deterministic_roundtrip():
    sc = serving.Scenario(
        name="mix", seed=5, vocab=VOCAB, n_users=8,
        phases=[{"duration_s": 3.0, "rate_rps": 10.0}],
        tenants={"gold-co": {"weight": 1.0, "priority": 2},
                 "best-effort": {"weight": 3.0}})
    t1 = sc.trace()
    assert t1, "empty trace"
    assert all(a.tenant in ("gold-co", "best-effort") for a in t1)
    # the tenant dict's priority overrides the drawn class
    assert all(a.priority == 2 for a in t1 if a.tenant == "gold-co")
    seen = {a.tenant for a in t1}
    assert seen == {"gold-co", "best-effort"}
    # JSON-roundtrip determinism: same spec, bitwise-same trace
    t2 = serving.Scenario.from_json(sc.to_json()).trace()
    assert len(t1) == len(t2)
    for a, b in zip(t1, t2):
        assert (a.t, a.tenant, a.priority, a.max_new) == \
            (b.t, b.tenant, b.priority, b.max_new)
        np.testing.assert_array_equal(a.prompt, b.prompt)


def test_workload_without_tenants_unchanged():
    """tenants=None consumes no extra RNG: the legacy trace shape is
    bit-identical and `to_dict` carries no tenants key."""
    sc = serving.Scenario(seed=3, n_users=4,
                          phases=[{"duration_s": 2.0, "rate_rps": 8.0}])
    assert "tenants" not in sc.to_dict()
    for a in sc.trace():
        assert a.tenant is None


def test_http_front_x_tenant_and_budget_429(gpt):
    ten = TenantDirectory(
        [TenantSpec("metered", budget_tokens_per_s=12, burst_s=1.0)])
    srv = serving.Server(gpt, max_slots=2, block_size=8,
                         max_adapters=2, lora_rank=RANK,
                         tenancy=ten).start()
    httpd = serving.http_front(srv)
    port = httpd.server_address[1]
    try:
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate", data=body,
            headers={"X-Tenant": "metered"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
            assert len(json.loads(r.read())["ids"]) == 7
        # the tenant's bucket (12 tokens) is now empty enough that the
        # next metered call sheds with ITS refill time as Retry-After
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps({"prompt": [1, 2, 3],
                                 "max_new_tokens": 8,
                                 "tenant": "metered"}).encode()))
        assert ei.value.code == 429
        assert float(ei.value.headers["Retry-After"]) > 0
        payload = json.loads(ei.value.read())
        assert payload["type"] == "TenantBudgetError"
        assert payload["retriable"]
        # anonymous traffic is untouched by the metered tenant's budget
        with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=body)) as r:
            assert r.status == 200
        snap = srv.snapshot()
        assert snap["tenants"]["metered"]["counters"]["completed"] == 1
        assert snap["tenants"]["metered"]["counters"]["shed"] == 1
    finally:
        httpd.shutdown()
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# bench subprocess smoke (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bench_fleet_tenants_smoke():
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_FAULTS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "bench_fleet.py"),
         "--tenants", "--smoke"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=570)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SMOKE OK" in r.stdout
    final = json.loads(r.stdout.strip().splitlines()[-2])
    assert final["bench"] == "BENCH_FLEET_TENANTS"
    assert final["chaos"]["tenants"]["crowd"]["shed"] == 3
    assert final["chaos"]["tenants"]["steady"]["shed"] == 0
