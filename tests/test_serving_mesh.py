"""Mesh-sharded serving (ISSUE 17): partition-rule weight sharding,
GSPMD-compiled unified steps with greedy token parity across mesh
shapes, ring-overlap routing of the sharded decode, disaggregated
prefill/decode KV migration behind the Router, chaos for the two new
fault sites, and rollout-under-sharding.

Runs on the 8-device virtual CPU mesh (conftest) — dist tier.
"""

import threading
import time

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import observe, serving
from paddle_tpu.distributed.topology import MP_AXIS
from paddle_tpu.engine import state_values
from paddle_tpu.framework import faults
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.serving.paging import CacheLayout
from paddle_tpu.serving.queueing import VersionRetiredError
from paddle_tpu.serving.rollout import (
    RolloutController, WeightRegistry, WeightVersion, _digest_ids,
)
from paddle_tpu.serving.sharding import (
    GPT_PARTITION_RULES, ShardingPlan, build_mesh, match_partition_rules,
    mesh_spec_of, parse_mesh_spec, resolve_mesh,
)

VOCAB = 97


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(23)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=True)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(
        1, VOCAB, (n,)).astype(np.int32)


def _engine(gpt, mesh=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    return serving.SlotEngine(gpt, mesh=mesh, **kw)


# ---------------------------------------------------------------------------
# partition rules + mesh spec plumbing
# ---------------------------------------------------------------------------


def test_mesh_spec_parse_and_build():
    assert parse_mesh_spec("dp1.mp2") == {"dp": 1, "mp": 2}
    assert parse_mesh_spec(" dp2.mp4 ") == {"dp": 2, "mp": 4}
    for bad in ("mp2.dp1", "dp1", "dp0.mp2", "dp1.mp0", "1x2", ""):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)
    mesh = build_mesh("dp2.mp4")
    assert mesh.size == 8
    assert mesh_spec_of(mesh) == "dp2.mp4"
    assert mesh_spec_of(None) == ""
    assert resolve_mesh(None) is None          # FLAGS_serving_mesh empty
    assert resolve_mesh(mesh) is mesh
    with pytest.raises(ValueError, match="devices"):
        build_mesh("dp4.mp4")                  # 16 > 8 virtual devices


def test_partition_rules_recover_training_layout(gpt):
    """The name-keyed rules reproduce the Column/Row/VocabParallel
    param_spec conventions over the real GPT state dict."""
    values = state_values(gpt)
    specs = match_partition_rules(GPT_PARTITION_RULES, values)
    got = {k: specs[k] for k in specs}
    qkv = [k for k in got if k.endswith("qkv_proj.weight")]
    assert qkv and all(got[k] == P(None, MP_AXIS) for k in qkv)
    assert all(got[k] == P(MP_AXIS)
               for k in got if k.endswith("qkv_proj.bias"))
    assert all(got[k] == P(MP_AXIS, None)
               for k in got if k.endswith("out_proj.weight")
               or k.endswith("fc2.weight"))
    assert all(got[k] == P(None, MP_AXIS)
               for k in got if k.endswith("fc1.weight"))
    # layernorms, position embeddings, row-parallel biases: replicated
    assert all(got[k] == P() for k in got
               if "norm" in k or "position_embeddings" in k
               or k.endswith("out_proj.bias") or k.endswith("fc2.bias"))
    # scalars always replicate, even when a rule would match
    specs = match_partition_rules(GPT_PARTITION_RULES,
                                  {"x.fc1.weight": np.float32(3.0)})
    assert specs["x.fc1.weight"] == P()
    # no catch-all -> an unmatched name is a hard error, never a
    # silently replicated layer
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules(GPT_PARTITION_RULES[:-1],
                              {"brand_new_layer.w": np.zeros((2, 2))})


def test_sharding_plan_fits_and_degrades(gpt):
    plan = ShardingPlan(build_mesh("dp1.mp2"))
    values = state_values(gpt)
    sh = plan.values_shardings(values)
    emb = next(k for k in values if k.endswith("word_embeddings.weight"))
    fc1 = next(k for k in values if k.endswith("fc1.weight"))
    # vocab 97 does not divide mp=2: the vocab-parallel rule degrades
    # that dim to replicated (device_put/jit require exact division;
    # GSPMD only pads internal values)
    assert sh[emb].spec == P(None, None)
    assert sh[fc1].spec == P(None, MP_AXIS)
    # a pool shards over the head axis its layout names iff it divides
    # mp; a pool with no head axis (latent rows) is replicated; block
    # tables stay host-side
    kv = CacheLayout("thd", (("k", (4, 8)), ("v", (4, 8))), 2, head_axis=2)
    assert plan.pool_sharding(kv, (32, 8, 4, 8)).spec \
        == P(None, None, MP_AXIS, None)
    assert plan.pool_sharding(kv, (32, 8, 3, 8)).spec == P()
    latent = CacheLayout("tc", (("latent", (24,)),), 2)
    assert plan.pool_sharding(latent, (32, 8, 24)).spec == P()


# ---------------------------------------------------------------------------
# tentpole a+b: sharded engine — parity, compile-once, overlap routing
# ---------------------------------------------------------------------------


def test_greedy_parity_across_mesh_shapes(gpt):
    """The acceptance gate: greedy decode is bitwise token-identical on
    a single device, dp1.mp2, and dp1.mp4, and every engine compiles
    exactly once per program for life."""
    prompts = [_prompt(11), _prompt(12, n=13)]
    outs = {}
    for spec in (None, "dp1.mp2", "dp1.mp4"):
        eng = _engine(gpt, mesh=spec)
        eng.warmup()
        eng.start()
        try:
            futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            outs[spec] = [np.asarray(f.result(60.0)) for f in futs]
        finally:
            eng.shutdown()
        assert eng.compile_counts == {"decode": 1, "cow": 1}, spec
        info = eng.mesh_info()
        if spec is None:
            assert info == {"spec": "", "devices": 1,
                            "kv_sharded": False}
        else:
            assert info["spec"] == spec
            assert info["kv_sharded"] is True     # 4 heads % mp == 0
    for spec in ("dp1.mp2", "dp1.mp4"):
        for a, b in zip(outs[None], outs[spec]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("spec", ["dp1.mp2", "dp1.mp4"])
def test_meshed_step_keeps_head_sharded_pools_in_place(gpt, spec):
    """The `in_shardings` / `out_shardings` branch donates like the
    plain one: the pool `[num_blocks, block_size, nh, hd]` stays
    sharded over its head axis (2) through warm-up and every step, the
    arrays handed in are gone after it, every step counted."""
    eng = _engine(gpt, mesh=spec, prefix_cache=True)
    want = P(None, None, MP_AXIS, None)
    assert eng._pools[0][0].shape == (32, 8, 4, 8)
    built = eng._arrays(eng._pools)
    eng.warmup()
    assert all(a.is_deleted() for a in built)
    assert all(a.sharding.spec == want for a in eng._arrays(eng._pools))
    a = np.arange(1, 18, dtype=np.int32)
    b = a.copy()
    b[11] = 77                                # CoW inside block 2
    for p in (a, b):
        fut = eng.submit(p, max_new_tokens=3)
        eng._admit()
        while eng.active:
            before, n = eng._arrays(eng._pools), eng.metrics.get("steps")
            eng._step()
            assert all(x.is_deleted() for x in before) \
                == (eng.metrics.get("steps") > n)
        fut.result(5)
    assert eng.metrics.get("cow_splits") == 1
    assert all(x.sharding.spec == want for x in eng._arrays(eng._pools))
    assert eng.metrics.get("pool_inplace_steps") == \
        eng.metrics.get("steps") > 0
    assert eng.compile_counts == {"decode": 1, "cow": 1}


def test_overlap_routes_sharded_decode(gpt):
    """FLAGS_mp_overlap routes the TP decode matmuls through the ring
    kernels inside the same compiled step (same silent-guard contract
    as training: unsupported shapes keep the GSPMD path)."""
    prompt = _prompt(21)
    eng = _engine(gpt, mesh="dp1.mp2")
    eng.warmup()
    eng.start()
    try:
        base = np.asarray(eng.submit(prompt, max_new_tokens=8)
                          .result(60.0))
    finally:
        eng.shutdown()
    paddle.set_flags({"FLAGS_mp_overlap": True})
    try:
        eng = _engine(gpt, mesh="dp1.mp2")
        eng.warmup()
        eng.start()
        try:
            out = np.asarray(eng.submit(prompt, max_new_tokens=8)
                             .result(60.0))
        finally:
            eng.shutdown()
    finally:
        paddle.set_flags({"FLAGS_mp_overlap": False})
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    # ring reduce may reassociate float adds; the generation must stay
    # a valid same-length decode and on this model it is bitwise
    assert out.shape == base.shape
    np.testing.assert_array_equal(out[:prompt.size], prompt)
    np.testing.assert_array_equal(out, base)


def test_repeat_warmup_does_not_recompile(gpt):
    """Satellite 6: re-entering warmup after a shard restart (same mesh
    shape) runs under observe.no_retrace() — zero new compiles; a
    different mesh shape is a hard error, not a silent retrace."""
    eng = _engine(gpt, mesh="dp1.mp2")
    eng.warmup()
    first = dict(eng.compile_counts)
    assert first == {"decode": 1, "cow": 1}
    eng.warmup(mesh="dp1.mp2")              # shard-restart re-entry
    eng.warmup(mesh=build_mesh("dp1.mp2"))  # prebuilt Mesh spelling
    assert eng.compile_counts == first
    with pytest.raises(ValueError, match="rebuild the engine"):
        eng.warmup(mesh="dp1.mp4")
    eng.shutdown()


def test_mesh_metrics_and_prometheus(gpt):
    """Satellite 2: snapshot()["mesh"] + the paddle_serving_mesh_*
    family carry the mesh shape label, per-shard occupancy, and the
    role gauge."""
    eng = _engine(gpt, mesh="dp1.mp2")
    eng.warmup()
    eng.start()
    try:
        eng.submit(_prompt(31), max_new_tokens=4).result(60.0)
    finally:
        eng.shutdown()
    snap = eng.metrics.snapshot()
    mesh = snap["mesh"]
    assert mesh["spec"] == "dp1.mp2" and mesh["devices"] == 2
    assert [s["shard"] for s in mesh["per_shard_occupancy"]] == [0, 1]
    text = observe.prometheus_text(serving=eng.metrics)
    assert 'paddle_serving_mesh_devices{mesh="dp1.mp2"} 2' in text
    assert 'paddle_serving_mesh_shard_occupancy{mesh="dp1.mp2",' \
           'shard="1"}' in text
    assert "paddle_serving_mesh_role" in text
    assert "paddle_serving_mesh_kv_migrations_total" in text
    assert "mesh" in observe.snapshot()     # monitor-level mirror


# ---------------------------------------------------------------------------
# tentpole c: prefill->decode KV migration
# ---------------------------------------------------------------------------


def _populate_cache(eng, prompt):
    """Run the prompt to completion so its fully-written blocks are
    donated to the engine's prefix cache at eviction."""
    return np.asarray(eng.submit(list(prompt), max_new_tokens=1)
                      .result(60.0))


def test_migrate_prefix_moves_blocks_and_stays_bitwise(gpt):
    prompt = np.arange(1, 18, dtype=np.int32)     # 2 full blocks of 8
    src = _engine(gpt, prefix_cache=True)
    dst = _engine(gpt, prefix_cache=True)
    src.warmup()
    dst.warmup()
    src.start()
    dst.start()
    try:
        baseline = np.asarray(
            src.submit(list(prompt), max_new_tokens=6).result(60.0))
        in_use0 = dst.blocks_in_use
        adopted = serving.migrate_prefix(src, dst, prompt)
        assert adopted == 16                       # 2 blocks * 8
        assert dst.blocks_in_use == in_use0 + 2
        assert dst.prefix_cache_size == 2
        assert dst.metrics.get("kv_migrations") == 1
        assert dst.metrics.get("kv_migrate_blocks") == 2
        assert dst.metrics.get("kv_migrate_bytes") > 0
        # adopted blocks are owned by the cache alone (refcount 1 per
        # block): the exporter dropped its pins, the adopter its refs
        hits0 = dst.metrics.get("prefix_hit_tokens")
        out = np.asarray(dst.submit(list(prompt), max_new_tokens=6)
                         .result(60.0))
        np.testing.assert_array_equal(out, baseline)
        assert dst.metrics.get("prefix_hit_tokens") >= hits0 + 16
        # nothing exportable -> clean 0, no payload
        assert src.export_prefix_blocks(np.asarray([1], np.int32)) is None
        assert serving.migrate_prefix(src, dst, [90, 91]) == 0
    finally:
        src.shutdown()
        dst.shutdown()


def test_old_order_migration_payload_is_refused(gpt):
    """A payload whose rows are head-major `[n, nh, block_size, hd]`
    (an engine from before the pool went token-major), or that does not
    say, is refused whole — also where heads == block size and the
    shapes agree, so that only the named order tells them apart."""
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                    num_heads=8, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    paddle.seed(29)
    square = GPTForPretraining(cfg)               # 8 heads, blocks of 8
    square.eval()
    prompt = np.arange(1, 18, dtype=np.int32)
    for model in (gpt, square):
        src = _engine(model, prefix_cache=True)
        dst = _engine(model, prefix_cache=True)
        _populate_cache(src.start(), prompt)
        src.shutdown()
        payload = src.export_prefix_blocks(prompt)
        assert payload["row_order"] == "thd"
        nh = model.config.num_heads
        assert payload["layers"][0][0].shape == (2, 8, nh, 64 // 8)
        old = dict(payload, row_order="htd", layers=[
            (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
            for k, v in payload["layers"]])
        unnamed = {k: v for k, v in old.items() if k != "row_order"}
        # the square model's transposed rows have the pool's own shape
        assert (old["layers"][0][0].shape
                == payload["layers"][0][0].shape) == (nh == 8)
        free0 = dst.free_blocks
        assert dst.adopt_prefix_blocks(old) == 0
        assert dst.adopt_prefix_blocks(unnamed) == 0
        assert dst.free_blocks == free0 and dst.prefix_cache_size == 0
        assert dst.adopt_prefix_blocks(payload) == 16
        assert dst.prefix_cache_size == 2


def test_kv_migrate_fault_is_leak_free(gpt):
    """Satellite 1: a fault mid-adoption frees every block taken so far
    — allocator refcounts return to the pre-migration state and the
    engine keeps serving."""
    prompt = np.arange(1, 18, dtype=np.int32)
    src = _engine(gpt, prefix_cache=True)
    dst = _engine(gpt, prefix_cache=True)
    src.warmup()
    dst.warmup()
    src.start()
    dst.start()
    try:
        _populate_cache(src, prompt)
        free0, cache0 = dst.free_blocks, dst.prefix_cache_size
        # second block's allocation faults -> all-or-nothing abort
        with faults.ChaosSchedule("serving.kv_migrate@2:raise") as ch:
            with pytest.raises(faults.FaultError):
                serving.migrate_prefix(src, dst, prompt)
            ch.verify()
        assert dst.free_blocks == free0                # leak-free
        assert dst.prefix_cache_size == cache0
        assert dst.metrics.get("kv_migrations") == 0
        # the pool still serves: a clean retry adopts both blocks
        assert serving.migrate_prefix(src, dst, prompt) == 16
        assert dst.free_blocks == free0 - 2
    finally:
        src.shutdown()
        dst.shutdown()


def test_mailbox_mirrors_p2p_deadline_contract():
    """KVMailbox wraps send/recv in the gang deadline guards, so the
    PR-14 chaos specs cover KV streaming: a recv with no payload raises
    the retriable PeerGoneError within its deadline."""
    from paddle_tpu.distributed.gang import PeerGoneError

    box = serving.KVMailbox()
    box.send({"layers": []}, "e1")
    assert box.recv("e1", timeout=0.5) == {"layers": []}
    t0 = time.monotonic()
    with pytest.raises(PeerGoneError):
        box.recv("e1", timeout=0.1)
    assert time.monotonic() - t0 < 5.0
    with faults.ChaosSchedule("dist.p2p_send@1:raise") as ch:
        with pytest.raises(faults.FaultError):
            box.send({"layers": []}, "e2")
        ch.verify()


# ---------------------------------------------------------------------------
# disaggregated fleet: router legs, chaos, failover
# ---------------------------------------------------------------------------


def _disagg_router(gpt, **kw):
    kw.setdefault("engine_kw", dict(max_slots=2, max_seq_len=64,
                                    block_size=8, num_blocks=32,
                                    prefix_cache=True))
    kw.setdefault("hedge", False)
    kw.setdefault("liveness_timeout_s", 30.0)
    return serving.Router(gpt, 2, roles=["prefill", "decode"],
                          role_kw={"decode": {"prefill_chunk": 8}},
                          disagg=True, name="dg", **kw)


def test_disagg_router_matches_colocated(gpt):
    """Tentpole c acceptance: the disaggregated two-leg path produces
    the exact colocated greedy tokens, with the KV blocks migrated
    between roles and both legs visible in the metrics."""
    prompt = np.arange(1, 18, dtype=np.int32)
    colo = serving.Router(gpt, 2, engine_kw=dict(
        max_slots=2, max_seq_len=64, block_size=8, num_blocks=32,
        prefix_cache=True), hedge=False, name="co").start()
    try:
        base = np.asarray(colo.generate(list(prompt), max_new_tokens=8,
                                        timeout=60.0))
    finally:
        colo.shutdown()
    r = _disagg_router(gpt).start()
    try:
        out = np.asarray(r.generate(list(prompt), max_new_tokens=8,
                                    timeout=60.0))
        np.testing.assert_array_equal(out, base)
        assert r.metrics.get("kv_migrations") == 1
        assert r.metrics.get("kv_migrate_blocks") == 2
        assert r.metrics.get("routed") == 2       # prefill + decode legs
        assert r.metrics.get("fleet_completed") == 1
        roles = {rep.name: rep.snapshot()["role"]
                 for rep in r.replica_set.replicas}
        assert sorted(roles.values()) == ["decode", "prefill"]
        # prefill replica got the wide default chunk, decode the narrow
        chunks = {rep.role: rep.engine.prefill_chunk
                  for rep in r.replica_set.replicas}
        assert chunks["decode"] == 8
    finally:
        r.shutdown()


def test_disagg_kv_migrate_fault_falls_back_colocated(gpt):
    """Satellite 1: a kv_migrate fault aborts the adoption leak-free
    and the Router degrades the request to colocated dispatch — same
    tokens, one counted fault, nothing lost."""
    prompt = np.arange(1, 18, dtype=np.int32)
    r = _disagg_router(gpt).start()
    try:
        base = np.asarray(r.generate(list(prompt), max_new_tokens=8,
                                     timeout=60.0))
        decode = next(rep.engine for rep in r.replica_set.replicas
                      if rep.role == "decode")
        free0 = decode.free_blocks
        faults0 = r.metrics.get("kv_migrate_faults")
        with faults.ChaosSchedule("serving.kv_migrate@1:raise") as ch:
            out = np.asarray(r.generate(list(prompt), max_new_tokens=8,
                                        timeout=60.0))
            ch.verify()
        np.testing.assert_array_equal(out, base)
        assert r.metrics.get("kv_migrate_faults") == faults0 + 1
        # the decode pool did not leak the aborted adoption (the
        # successful first request's 2 cached blocks stay resident)
        assert decode.free_blocks == free0
    finally:
        r.shutdown()


def test_shard_step_fault_survives_and_router_replays(gpt):
    """Satellite 1: serving.shard_step is a step error the sharded
    engine survives; behind the Router the failed attempt is retried on
    a sibling and the client still gets the full decode."""
    eng = _engine(gpt, mesh="dp1.mp2")
    eng.warmup()
    eng.start()
    try:
        with faults.ChaosSchedule("serving.shard_step@1:raise") as ch:
            fut = eng.submit(_prompt(41), max_new_tokens=4)
            with pytest.raises(faults.FaultError):
                fut.result(60.0)
            ch.verify()
        # the engine survived the step error and serves the next one
        out = np.asarray(eng.submit(_prompt(41), max_new_tokens=4)
                         .result(60.0))
        assert out.size == 8 + 4
    finally:
        eng.shutdown()
    r = serving.Router(gpt, 2, engine_kw=dict(
        max_slots=2, max_seq_len=64, block_size=8, num_blocks=32,
        mesh="dp1.mp2"), hedge=False, retry_budget=3, name="ms").start()
    try:
        base = np.asarray(r.generate(_prompt(42), max_new_tokens=4,
                                     timeout=60.0))
        retries0 = r.metrics.get("retries")
        with faults.ChaosSchedule("serving.shard_step@1:raise") as ch:
            out = np.asarray(r.generate(_prompt(42), max_new_tokens=4,
                                        timeout=60.0))
            ch.verify()
        np.testing.assert_array_equal(out, base)
        assert r.metrics.get("retries") >= retries0 + 1
    finally:
        r.shutdown()


def test_disagg_prefill_replica_death_stays_replayable(gpt):
    """Kill the prefill replica with requests in flight: every request
    still completes (replayed / degraded to the surviving replica) —
    first-wins dedup holds across legs."""
    r = _disagg_router(gpt, backoff_base_s=0.02).start()
    try:
        prompts = [np.arange(1, 18, dtype=np.int32) + i
                   for i in range(4)]
        base = [np.asarray(r.generate(list(p), max_new_tokens=6,
                                      timeout=60.0)) for p in prompts]
        futs = [r.submit(list(p), max_new_tokens=6, timeout=60.0)
                for p in prompts]
        victim = next(rep for rep in r.replica_set.replicas
                      if rep.role == "prefill")
        r.kill(victim.name)
        outs = [np.asarray(f.result(60.0)) for f in futs]
        for a, b in zip(outs, base):
            np.testing.assert_array_equal(a, b)
        assert r.metrics.get("fleet_failed") == 0
    finally:
        r.shutdown()


# ---------------------------------------------------------------------------
# satellite 3: rollout under sharding
# ---------------------------------------------------------------------------


def _perturbed(model, seed, scale=0.05):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(np.asarray(v)
                           + rng.normal(0.0, scale, np.shape(v))
                           .astype(np.asarray(v).dtype))
            for k, v in state_values(model).items()}


def test_rollout_swaps_sharded_replicas_atomically(gpt):
    """A canary rollout over 2-shard (dp1.mp2) replicas swaps each
    replica's weights as one unit — both shards move at the rebuild,
    certified by the bitwise golden gate decoded through the sharded
    engines — and a pin to the retired version fails typed (503)
    rather than silently mixing weight versions within a mesh."""
    router = serving.Router(
        gpt, 2, engine_kw=dict(max_slots=2, max_seq_len=64,
                               block_size=8, num_blocks=32,
                               mesh="dp1.mp2"),
        hedge=False, retry_budget=3, backoff_base_s=0.02,
        liveness_timeout_s=30.0, name="rs").start()
    try:
        reg = WeightRegistry(gpt)
        ro = RolloutController(router, reg, canary_secs=0.05,
                               wave_size=1, poll_s=0.005,
                               replica_timeout_s=120.0,
                               slo_p99_ms=60000.0)
        wv1 = reg.add(WeightVersion(1, _perturbed(gpt, 7)))
        assert ro.roll_to(1) is True, ro.error
        assert ro.state == "committed"
        healthy = [rep for rep in router.replica_set.replicas
                   if rep.state == "healthy"]
        assert {rep.engine.weight_version for rep in healthy} == {1}
        for rep in healthy:
            # the rebuilt engines kept the mesh shape and compile-once
            assert rep.engine.mesh_spec == "dp1.mp2"
            assert rep.engine.compile_counts == {"decode": 1,
                                                 "cow": 1}
        # bitwise golden gate against the sharded engines
        p0 = ro._prompts()[0]
        out = router.generate(list(p0), max_new_tokens=ro.golden_max_new,
                              timeout=60.0)
        assert _digest_ids(out) == wv1.golden["p0"]

        # half-upgraded pin: a flight pinned to the retired v0 finds no
        # replica (nor rebuild target) serving it -> typed 503, never a
        # silent decode on mixed versions
        retired0 = router.metrics.get("version_retired_failures")
        fut = router.submit(_prompt(51), max_new_tokens=40,
                            timeout=60.0)
        with router._lock:
            flight = router._flights[fut.id]
            flight.pin = 0
            victim = next(rep for rep, _ in flight.attempts.values())
        assert 0 not in router.replica_set.versions_live()
        router.kill(victim.name)
        with pytest.raises(VersionRetiredError) as ei:
            fut.result(60.0)
        assert ei.value.status == 503 and ei.value.retriable
        assert router.metrics.get("version_retired_failures") \
            == retired0 + 1
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if len(router.replica_set.healthy()) == 2:
                break
            time.sleep(0.01)
        assert len(router.replica_set.healthy()) == 2
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# server plumbing
# ---------------------------------------------------------------------------


def test_server_threads_mesh_through(gpt):
    with serving.Server(gpt, max_slots=2, max_seq_len=64, block_size=8,
                        num_blocks=32, mesh="dp1.mp2") as srv:
        out = np.asarray(srv.generate(_prompt(61), max_new_tokens=4,
                                      timeout=60.0))
        assert out.size == 8 + 4
        assert srv.engine.mesh_info()["spec"] == "dp1.mp2"
        assert "paddle_serving_mesh_devices" in srv.metrics_prometheus()
