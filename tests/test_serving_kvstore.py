"""Global KV fabric (ISSUE 18): crash-safe SSD-tiered KV spill/restore
for durable multi-turn sessions, prefix-affinity routing, and the
PrefixCache refcount edge under interleaved insert/reclaim/CoW.

The durability contract under test: a session whose radix-cached KV was
evicted (pool pressure, drain, replica death) resumes from spilled
records with BITWISE-identical tokens — and every failure mode (torn
tail, bit rot, injected fault, fenced generation, pool pressure)
degrades to re-prefill, never to wrong tokens or leaked blocks.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observe, serving
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import faults, monitor
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (
    BlockAllocator, KVSpillStore, PrefixCache, Router, ServingError,
    ServingMetrics, SpillFencedError, open_spill_store,
    reset_spill_stores,
)
from paddle_tpu.serving.workload import Scenario

VOCAB = 97


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(13)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _fresh_stores():
    reset_spill_stores()
    yield
    reset_spill_stores()


_REF_PAD = 64


def _ref_greedy(m, ids, n):
    """No-cache argmax reference: full re-forward per emitted token."""
    ref = np.asarray(ids, np.int32).reshape(1, -1)
    for _ in range(n):
        padded = np.zeros((1, _REF_PAD), np.int32)
        padded[:, :ref.shape[1]] = ref
        out = m(Tensor(jnp.asarray(padded, jnp.int32)))
        logits = np.asarray(out._value, np.float32)[:, :ref.shape[1]]
        nxt = int(logits[:, -1].argmax(-1)[0])
        ref = np.concatenate([ref, [[nxt]]], axis=1).astype(np.int32)
    return ref[0]


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(np.int32)


def _record(seed, n_tokens=8, bs=8, n_layers=2, nh=4, hd=16):
    """(digest, tokens, layers) for store unit tests — the digest is
    arbitrary 20 bytes; the store never interprets it."""
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, VOCAB, (n_tokens,)).astype(np.int32)
    layers = [(rng.randn(bs, nh, hd).astype(np.float32),
               rng.randn(bs, nh, hd).astype(np.float32))
              for _ in range(n_layers)]
    return bytes(rng.randint(0, 256, (20,), np.uint8)), tokens, layers


# ---------------------------------------------------------------------------
# KVSpillStore: framing, recovery, fencing, compaction
# ---------------------------------------------------------------------------


def test_store_roundtrip_across_reopen(tmp_path):
    d, tokens, layers = _record(0)
    store = KVSpillStore(str(tmp_path), metrics=ServingMetrics())
    store.append(d, 0, tokens, layers)
    assert d in store and len(store) == 1
    assert store.metrics.get("kv_spilled_blocks") == 1
    assert store.metrics.get("kv_spill_bytes") == store.nbytes
    store.close()

    again = KVSpillStore(str(tmp_path))     # rebuild index by scan
    rec = again.get(d)
    assert rec["generation"] == 0 and rec["block_size"] == 8
    np.testing.assert_array_equal(rec["tokens"], tokens)
    for (k, v), (k0, v0) in zip(rec["layers"], layers):
        np.testing.assert_array_equal(k, k0)
        np.testing.assert_array_equal(v, v0)
    again.close()


def test_store_torn_tail_truncated_on_reopen(tmp_path):
    d1, t1, l1 = _record(1)
    d2, t2, l2 = _record(2)
    store = KVSpillStore(str(tmp_path))
    store.append(d1, 0, t1, l1)
    end1 = store.nbytes
    store.append(d2, 0, t2, l2)
    store.close()
    # a crash mid-append leaves a torn tail: recovery keeps the durable
    # prefix and truncates the rest for good
    os.truncate(store.path, end1 + 7)
    again = KVSpillStore(str(tmp_path))
    assert d1 in again and d2 not in again
    assert again.nbytes == end1
    np.testing.assert_array_equal(again.get(d1)["tokens"], t1)
    again.append(d2, 0, t2, l2)             # the tier keeps working
    np.testing.assert_array_equal(again.get(d2)["tokens"], t2)
    again.close()


def test_store_bit_rot_degrades_to_absent(tmp_path):
    d, tokens, layers = _record(3)
    m = ServingMetrics()
    store = KVSpillStore(str(tmp_path), metrics=m)
    store.append(d, 0, tokens, layers)
    with open(store.path, "r+b") as f:      # flip one payload byte
        f.seek(30)
        b = f.read(1)
        f.seek(30)
        f.write(bytes([b[0] ^ 0xFF]))
    # read-time crc re-verification: the record stops existing instead
    # of ever producing wrong tokens
    assert store.get(d) is None
    assert d not in store
    assert m.get("kv_restore_corrupt") == 1
    store.close()


def test_store_fence_raises_typed_retriable(tmp_path):
    d0, t0, l0 = _record(4)
    d1, t1, l1 = _record(5)
    m = ServingMetrics()
    store = KVSpillStore(str(tmp_path), metrics=m)
    store.append(d0, 0, t0, l0)
    store.append(d1, 1, t1, l1)
    assert store.fence(0) == 1
    assert m.get("kv_invalidated_blocks") == 1
    with pytest.raises(SpillFencedError) as ei:
        store.get(d0)
    assert isinstance(ei.value, ServingError)
    assert ei.value.status == 503 and ei.value.retriable
    np.testing.assert_array_equal(store.get(d1)["tokens"], t1)
    store.close()


def test_store_compaction_drops_fenced_keeps_live(tmp_path):
    store = KVSpillStore(str(tmp_path))
    recs = [_record(10 + i) for i in range(3)]
    store.append(recs[0][0], 0, recs[0][1], recs[0][2])
    store.append(recs[1][0], 1, recs[1][1], recs[1][2])
    store.append(recs[2][0], 1, recs[2][1], recs[2][2])
    before = store.nbytes
    store.fence(0)
    assert store.compact() == 2
    assert store.nbytes < before
    assert store.get(recs[0][0]) is None     # gone, not fenced-error
    for d, t, _l in recs[1:]:
        np.testing.assert_array_equal(store.get(d)["tokens"], t)
    store.close()


def test_store_cap_triggers_compaction(tmp_path):
    c0 = monitor.stat_get("serving.kv_spill_compactions")
    store = KVSpillStore(str(tmp_path), cap_mb=0.01)   # ~10 KiB cap
    d, tokens, layers = _record(6)
    for _ in range(8):              # same digest: superseded records
        store.append(d, 0, tokens, layers)
    assert monitor.stat_get("serving.kv_spill_compactions") > c0
    assert len(store) == 1
    assert store.nbytes <= 0.01 * (1 << 20)
    np.testing.assert_array_equal(store.get(d)["tokens"], tokens)
    store.close()


def _old_order_frame(digest, generation, tokens, layers):
    """One record as the store framed it while pools were head-major:
    no row order in the header, rows `[nh, block_size, hd]`."""
    import struct
    import zlib

    bs, nh, hd = layers[0][0].shape
    parts = [struct.pack("<20sq5i8s", digest, generation, tokens.size, bs,
                         len(layers), nh, hd,
                         str(layers[0][0].dtype).encode()[:8]),
             np.ascontiguousarray(tokens, np.int32).tobytes()]
    for k, v in layers:
        parts.append(np.ascontiguousarray(k.transpose(1, 0, 2)).tobytes())
        parts.append(np.ascontiguousarray(v.transpose(1, 0, 2)).tobytes())
    body = b"".join(parts)
    return struct.pack("<II", zlib.crc32(body), len(body)) + body


def test_store_skips_old_order_records(tmp_path):
    """A record written head-major is a miss after a reopen, beside a
    current one that is found; compaction drops it for good. Heads ==
    block size here, so nothing but the header's row order could tell."""
    d_old, t_old, l_old = _record(7, nh=8)
    d_new, t_new, l_new = _record(8, nh=8)
    store = KVSpillStore(str(tmp_path))
    store.append(d_new, 0, t_new, l_new)
    end = store.nbytes
    store.close()
    with open(store.path, "ab") as f:
        f.write(_old_order_frame(d_old, 0, t_old, l_old))
    again = KVSpillStore(str(tmp_path))
    assert d_new in again and d_old not in again
    assert again.get(d_old) is None
    assert again.stats()["stale_records"] == 1 and len(again) == 1
    np.testing.assert_array_equal(again.get(d_new)["layers"][1][0],
                                  l_new[1][0])
    again.append(d_old, 0, t_old, l_old)      # re-spilled in this order
    assert again.get(d_old)["layers"][0][0].shape == (8, 8, 16)
    assert again.compact() == 2
    assert again.stats()["stale_records"] == 0
    assert again.nbytes == 2 * end
    again.close()


def test_open_spill_store_shared_per_dir_and_disabled(tmp_path):
    a = open_spill_store(str(tmp_path))
    assert open_spill_store(str(tmp_path)) is a
    assert open_spill_store("") is None     # "" = tier disabled
    reset_spill_stores()
    b = open_spill_store(str(tmp_path))     # reopen after reset
    assert b is not a and not b._f.closed


# ---------------------------------------------------------------------------
# PrefixCache donation/refcount edge (ISSUE 18 satellite 4)
# ---------------------------------------------------------------------------


def test_prefix_cache_interleaved_insert_reclaim_cow_balances():
    """Interleave insert, reclaim-under-pressure, and CoW incref on the
    same hash chain: after every session closes and the cache clears,
    the allocator must balance to zero outstanding references."""
    alloc = BlockAllocator(10)              # 9 usable
    cache = PrefixCache(alloc, block_size=4)
    toks = np.arange(16, dtype=np.int32)

    blocks_a = [alloc.alloc() for _ in range(4)]    # session A, 4 blocks
    cache.insert(toks, blocks_a, 16)
    for b in blocks_a:                      # session A closes
        alloc.decref(b)
    assert all(alloc.refcount(b) == 1 for b in blocks_a)

    # session B: shares the chain, pins a CoW source mid-block
    div = np.concatenate([toks[:10], [90, 91]]).astype(np.int32)
    shared, n, cow = cache.match(div, div.size)
    assert n == 8 and cow is not None
    src, rows = cow
    assert src == blocks_a[2] and rows == 2
    for b in shared:                        # B's slot refs
        alloc.incref(b)
    alloc.incref(src)                       # CoW source pin

    # pressure: only the unpinned tail leaf may actually free
    freed = cache.reclaim(4)
    assert freed == 1 and alloc.refcount(blocks_a[3]) == 0

    # session C re-extends the surviving prefix with fresh blocks
    toks_c = np.concatenate([toks[:12], [70, 71, 72, 73]]) \
        .astype(np.int32)
    tail = alloc.alloc()
    cache.insert(toks_c, list(shared) + [src, tail], 16)
    alloc.decref(tail)

    for b in shared:                        # B's slot closes
        alloc.decref(b)
    alloc.decref(src)                       # CoW pin released
    cache.clear()
    assert len(cache) == 0
    assert alloc.free_blocks == alloc.usable
    assert all(alloc.refcount(b) == 0 for b in range(1, 10))


def test_prefix_cache_clear_spills_leaves_before_parents():
    """clear() must evict children first so the spill hook can resolve
    every entry's full token prefix through live parents."""
    alloc = BlockAllocator(6)
    cache = PrefixCache(alloc, block_size=4)
    toks = np.arange(12, dtype=np.int32)
    blocks = [alloc.alloc() for _ in range(3)]
    cache.insert(toks, blocks, 12)
    for b in blocks:
        alloc.decref(b)
    spilled = []
    cache.spill_hook = lambda key, prefix, bid, rows: \
        spilled.append((np.asarray(prefix), bid, rows))
    cache.clear()
    assert len(spilled) == 3
    for prefix, bid, rows in spilled:
        assert rows == 4
        np.testing.assert_array_equal(prefix, toks[:prefix.size])
    assert {b for _p, b, _r in spilled} == set(blocks)
    assert alloc.free_blocks == alloc.usable


# ---------------------------------------------------------------------------
# multi-turn workload (ISSUE 18 satellite 2)
# ---------------------------------------------------------------------------


def _sessions_scenario():
    return Scenario(name="mt", seed=5, vocab=VOCAB, n_users=8,
                    user_prefix_len=4, prompt_len=(4, 8), max_new=(2, 4),
                    multi_turn=True, session_turns=(2, 4),
                    think_time=(0.01, 0.05),
                    phases=[{"duration_s": 1.0, "rate_rps": 6.0}])


def test_multi_turn_scenario_json_roundtrip_and_determinism():
    sc = _sessions_scenario()
    assert Scenario.from_json(sc.to_json()).to_json() == sc.to_json()
    a, b = sc.trace(), sc.trace()
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.t, x.user, x.session, x.turn) == \
            (y.t, y.user, y.session, y.turn)
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_multi_turn_trace_extends_prompts_with_think_gaps():
    sc = _sessions_scenario()
    trace = sc.trace()
    assert [a.t for a in trace] == sorted(a.t for a in trace)
    by_session: dict = {}
    for a in trace:
        assert a.session is not None
        by_session.setdefault(a.session, []).append(a)
    assert len(by_session) >= 2
    for turns in by_session.values():
        assert 2 <= len(turns) <= 4
        assert [a.turn for a in turns] == list(range(len(turns)))
        for prev, nxt in zip(turns, turns[1:]):
            assert nxt.t > prev.t           # think-time gap
            assert nxt.prompt.size > prev.prompt.size
            np.testing.assert_array_equal(    # pure prefix extension
                nxt.prompt[:prev.prompt.size], prev.prompt)
            assert nxt.user == prev.user


def test_single_turn_scenario_has_no_sessions():
    sc = Scenario(name="st", seed=5, vocab=VOCAB,
                  phases=[{"duration_s": 0.5, "rate_rps": 6.0}])
    for a in sc.trace():
        assert a.session is None and a.turn == 0
    assert "multi_turn" in sc.to_dict()


# ---------------------------------------------------------------------------
# SlotEngine spill/restore: bitwise resume, leak-free faults
# ---------------------------------------------------------------------------


def _server(gpt, tmp, **kw):
    return serving.Server(gpt, max_slots=2, block_size=8,
                          prefill_chunk=8,
                          spill_dir=None if tmp is None else str(tmp),
                          **kw).start()


def test_spill_restore_resume_bitwise_same_engine(tmp_path, gpt):
    srv = _server(gpt, tmp_path)
    eng = srv.engine
    p1 = _prompt(3, 24)
    out1 = np.asarray(srv.generate(p1, max_new_tokens=4, timeout=120.0),
                      np.int32)
    np.testing.assert_array_equal(out1, _ref_greedy(gpt, p1, 4))
    # between-turn pressure: the whole radix cache drains through the
    # spill tier; every block ref must come back
    assert eng.spill_cache() > 0
    assert eng.free_blocks == eng._alloc.usable
    assert srv.metrics.get("kv_spilled_blocks") == 3    # 24 full rows
    p2 = np.concatenate([out1, _prompt(4, 9)])
    out2 = np.asarray(srv.generate(p2, max_new_tokens=4, timeout=120.0),
                      np.int32)
    np.testing.assert_array_equal(out2, _ref_greedy(gpt, p2, 4))
    assert srv.metrics.get("kv_restored_blocks") == 3
    snap = srv.metrics.snapshot()
    assert snap["kvstore"]["restored_blocks"] == 3
    srv.shutdown(drain=True)


def test_spill_restore_cross_engine_shared_tier(tmp_path, gpt):
    """The replica-death resume shape: engine 1 spills, dies; engine 2
    (same spill dir = same shared store) restores the session."""
    srv1 = _server(gpt, tmp_path)
    p1 = _prompt(6, 24)
    out1 = np.asarray(srv1.generate(p1, max_new_tokens=3, timeout=120.0),
                      np.int32)
    srv1.engine.spill_cache()
    srv1.shutdown(drain=True)

    srv2 = _server(gpt, tmp_path)
    p2 = np.concatenate([out1, _prompt(7, 6)])
    out2 = np.asarray(srv2.generate(p2, max_new_tokens=3, timeout=120.0),
                      np.int32)
    np.testing.assert_array_equal(out2, _ref_greedy(gpt, p2, 3))
    assert srv2.metrics.get("kv_restored_blocks") == 3
    srv2.shutdown(drain=True)


def test_spill_fault_keeps_eviction_leak_free(tmp_path, gpt):
    srv = _server(gpt, tmp_path)
    eng = srv.engine
    srv.generate(_prompt(8, 24), max_new_tokens=2, timeout=120.0)
    with faults.ChaosSchedule("serving.spill@1:raise") as ch:
        eng.spill_cache()
        ch.verify()
    # the faulted append lost ONE record's durability, nothing else:
    # eviction completed, allocator balanced, later records landed
    assert eng.free_blocks == eng._alloc.usable
    assert len(eng._cache) == 0
    assert srv.metrics.get("kv_spill_errors") == 1
    assert srv.metrics.get("kv_spilled_blocks") == 2
    srv.shutdown(drain=True)


def test_restore_fault_falls_back_to_reprefill_bitwise(tmp_path, gpt):
    srv = _server(gpt, tmp_path)
    eng = srv.engine
    p1 = _prompt(9, 24)
    out1 = np.asarray(srv.generate(p1, max_new_tokens=3, timeout=120.0),
                      np.int32)
    eng.spill_cache()
    p2 = np.concatenate([out1, _prompt(10, 6)])
    with faults.ChaosSchedule("serving.kv_restore@1:raise") as ch:
        out2 = np.asarray(srv.generate(p2, max_new_tokens=3,
                                       timeout=120.0), np.int32)
        ch.verify()
    np.testing.assert_array_equal(out2, _ref_greedy(gpt, p2, 3))
    assert srv.metrics.get("kv_restored_blocks") == 0
    eng.spill_cache()
    assert eng.free_blocks == eng._alloc.usable     # no leaked blocks
    srv.shutdown(drain=True)


def test_tampered_spill_reprefills_bitwise(tmp_path, gpt):
    srv = _server(gpt, tmp_path)
    eng = srv.engine
    p1 = _prompt(11, 24)
    out1 = np.asarray(srv.generate(p1, max_new_tokens=3, timeout=120.0),
                      np.int32)
    eng.spill_cache()
    # clear() spills leaves first, so the file's FIRST record is the
    # deepest (24-token) block — the last one the restore walk reaches
    with open(eng.spill_store.path, "r+b") as f:
        f.seek(40)
        f.write(b"\xff\xff\xff\xff")
    p2 = np.concatenate([out1, _prompt(12, 6)])
    out2 = np.asarray(srv.generate(p2, max_new_tokens=3, timeout=120.0),
                      np.int32)
    # the intact prefix restores; the rotted block degrades to
    # re-prefill of the remainder — never wrong tokens
    np.testing.assert_array_equal(out2, _ref_greedy(gpt, p2, 3))
    assert srv.metrics.get("kv_restored_blocks") == 2
    assert srv.metrics.get("kv_restore_corrupt") == 1
    srv.shutdown(drain=True)


def test_old_order_spill_records_reprefill_bitwise(tmp_path):
    """A spill directory left by an engine whose pools were head-major:
    the session's records are there under the right digests, same
    weights, same shapes (8 heads over blocks of 8) — and none is
    restored. The request re-prefills and answers the same tokens."""
    paddle.seed(17)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                    num_heads=8, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    square = GPTForPretraining(cfg)
    square.eval()
    srv = _server(square, tmp_path)
    p1 = _prompt(21, 24)
    out1 = np.asarray(srv.generate(p1, max_new_tokens=3, timeout=120.0),
                      np.int32)
    srv.engine.spill_cache()
    store = srv.engine.spill_store
    recs = [store.get(d) for d in list(store._index)]
    assert len(recs) == 3 and recs[0]["layers"][0][0].shape == (8, 8, 8)
    srv.shutdown(drain=True)
    reset_spill_stores()
    with open(store.path, "wb") as f:
        for r in recs:
            f.write(_old_order_frame(r["digest"], r["generation"],
                                     r["tokens"], r["layers"]))
    srv = _server(square, tmp_path)
    assert srv.engine.spill_store.stats()["stale_records"] == 3
    p2 = np.concatenate([out1, _prompt(22, 6)])
    out2 = np.asarray(srv.generate(p2, max_new_tokens=3, timeout=120.0),
                      np.int32)
    np.testing.assert_array_equal(out2, _ref_greedy(square, p2, 3))
    assert srv.metrics.get("kv_restored_blocks") == 0
    assert srv.metrics.get("prefix_hit_tokens") == 0
    srv.shutdown(drain=True)


# ---------------------------------------------------------------------------
# prefix-affinity routing (the tentpole's fleet half)
# ---------------------------------------------------------------------------


def test_affinity_sticks_faults_over_and_survives_kill(gpt):
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8,
                                   prefill_chunk=8),
                    hedge=False, retry_budget=3, liveness_timeout_s=30.0,
                    backoff_base_s=0.05, name="aff",
                    prefix_affinity=True).start()
    try:
        p = _prompt(20, 16)
        ref2 = _ref_greedy(gpt, p, 2)
        out = router.submit(p, max_new_tokens=2, timeout=120.0) \
            .result(120.0)
        np.testing.assert_array_equal(out, ref2)

        # the repeat lands on the SAME replica (sticky prefix hash)
        out = router.submit(p, max_new_tokens=2, timeout=120.0) \
            .result(120.0)
        np.testing.assert_array_equal(out, ref2)
        snap = router.snapshot()["affinity"]
        assert snap["lookups"] >= 2 and snap["hits"] >= 1
        assert snap["table_size"] >= 2
        served = [r for r in router.replica_set.replicas
                  if r.engine.prefix_lookups > 0]
        assert len(served) == 1             # both turns on one engine
        home = served[0]
        assert snap["per_replica"][home.name]["prefix_hit_rate"] > 0

        # a fault at the routing decision falls back to least-loaded —
        # the request itself never notices
        with faults.ChaosSchedule("serving.affinity@1:raise") as ch:
            out = router.submit(p, max_new_tokens=2, timeout=120.0) \
                .result(120.0)
            ch.verify()
        np.testing.assert_array_equal(out, ref2)
        assert router.metrics.get("affinity_faults") == 1

        # kill the affine replica: the mapping is stale, failover picks
        # the survivor cleanly and the session re-sticks there
        router.kill(home.name, "affinity failover test")
        out = router.submit(p, max_new_tokens=2, timeout=120.0) \
            .result(120.0)
        np.testing.assert_array_equal(out, ref2)
        other = next(r for r in router.replica_set.replicas
                     if r.name != home.name)
        assert other.engine.prefix_lookups > 0
    finally:
        router.shutdown(drain=True)


# ---------------------------------------------------------------------------
# observability: prometheus family + export snapshot mirror
# ---------------------------------------------------------------------------


def test_kvstore_prometheus_family_and_snapshot(tmp_path):
    d, tokens, layers = _record(30)
    store = KVSpillStore(str(tmp_path))     # no registry: monitor stats
    store.append(d, 0, tokens, layers)
    store.fence(0)
    store.close()
    text = observe.prometheus_text()
    for name in ("paddle_serving_kvstore_spilled_blocks_total",
                 "paddle_serving_kvstore_invalidated_blocks_total",
                 "paddle_serving_kvstore_spill_bytes_total"):
        assert f"# TYPE {name} counter" in text
    snap = observe.snapshot()
    assert snap["kvstore"]["kv_spilled_blocks"] >= 1
    assert snap["kvstore"]["kv_invalidated_blocks"] >= 1
