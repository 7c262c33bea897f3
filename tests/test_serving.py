"""Serving subsystem: continuous-batching slot engine over a block-paged
KV cache (prefix sharing, copy-on-write, chunked prefill — ONE compiled
step), dynamic batcher bucket ladder (one compile per bucket), admission
control (queue-full shed, block-capacity 429, deadlines, graceful
drain), deterministic fault injection, and the metrics/percentile
registry.

Ref parity: paddle/fluid/inference/api (AnalysisPredictor/PredictorPool)
+ the Orca-style continuous batching the reference's serving stack
approximates with request-level batching, paged along the
vLLM/SGLang lineage. Everything here runs on CPU with thread-based
clients — no network.
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
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import faults
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (
    AdmissionQueue, BlockAllocator, BrownoutShedError,
    CapacityExhaustedError, CircuitBreaker, DeadlineExceededError,
    DynamicBatcher, NULL_BLOCK, PoolExhausted, PrefixCache,
    QueueFullError, ReplicaDiedError, Request, RequestCancelled,
    RetriesExhaustedError, Router, ServerClosedError, ServingError,
    ServingMetrics, bucket_for, bucket_ladder, pad_batch, retriable,
)

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
def server(gpt):
    """Shared started server: parity/metrics tests reuse it so the
    compile-once invariant is checked ACROSS many requests (and the
    prefix cache sees real repeat traffic)."""
    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    yield srv
    srv.shutdown(drain=True)


_REF_PAD = 64   # fixture max_seq_len: references always forward this
                # one shape so the per-op dispatch caches hit (causal
                # attention makes the padded tail invisible to real rows)


def _full_logits(m, ids):
    ids = np.asarray(ids, np.int32).reshape(1, -1)
    n = ids.shape[1]
    padded = np.zeros((1, _REF_PAD), np.int32)
    padded[:, :n] = ids
    out = m(Tensor(jnp.asarray(padded, jnp.int32)))
    return np.asarray(out._value, np.float32)[:, :n]


def _ref_greedy(m, ids, n, eos=None):
    """The no-cache reference decoder: argmax chain over full
    re-forwarding, stopping early at eos."""
    ref = np.asarray(ids, np.int32).reshape(1, -1)
    for _ in range(n):
        nxt = int(_full_logits(m, ref)[:, -1].argmax(-1)[0])
        ref = np.concatenate([ref, [[nxt]]], axis=1).astype(np.int32)
        if eos is not None and nxt == eos:
            break
    return ref[0]


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# bucket ladders + padding
# ---------------------------------------------------------------------------


def test_bucket_ladder_shapes():
    assert bucket_ladder(8) == [1, 2, 4, 8]
    assert bucket_ladder(6) == [1, 2, 4, 6]   # top rung always included
    assert bucket_ladder(1) == [1]
    with pytest.raises(ValueError):
        bucket_ladder(0)


def test_bucket_for_selection():
    ladder = [1, 2, 4, 8]
    assert bucket_for(1, ladder) == 1
    assert bucket_for(3, ladder) == 4
    assert bucket_for(8, ladder) == 8
    with pytest.raises(ValueError):
        bucket_for(9, ladder)


def test_pad_batch_repeats_last_sample():
    a = [np.full((3,), i, np.float32) for i in range(3)]
    x = pad_batch(a, 4)
    assert x.shape == (4, 3)
    np.testing.assert_array_equal(x[3], a[2])  # repeat, not zeros


# ---------------------------------------------------------------------------
# paged-KV host bookkeeping: block allocator + radix prefix cache
# ---------------------------------------------------------------------------


def test_block_allocator_refcounts_and_exhaustion():
    a = BlockAllocator(4)                 # 1 reserved null + 3 usable
    assert a.usable == 3 and a.free_blocks == 3
    b1, b2 = a.alloc(), a.alloc()
    assert b1 != NULL_BLOCK and b2 != NULL_BLOCK
    assert a.blocks_in_use == 2
    a.incref(b1)                          # shared by a second holder
    assert not a.decref(b1)               # still referenced
    assert a.decref(b1)                   # now actually freed
    assert a.free_blocks == 2
    with pytest.raises(ValueError):
        a.incref(b1)                      # freed: not refcountable
    a.alloc(), a.alloc()
    with pytest.raises(PoolExhausted):
        a.alloc()
    with pytest.raises(ValueError):       # the null block is untouchable
        a.decref(NULL_BLOCK)


def test_prefix_cache_match_insert_cow_reclaim():
    a = BlockAllocator(8)
    c = PrefixCache(a, block_size=4)
    toks = np.arange(1, 13, dtype=np.int32)        # 12 tokens, 3 blocks
    blocks = [a.alloc() for _ in range(3)]
    # only 8 positions really written -> only 2 full blocks indexed
    assert c.insert(toks, blocks, written=8) == 2
    assert a.refcount(blocks[0]) == 2              # cache holds a ref
    # exact-prefix hit walks the cumulative hashes
    hit, n, cow = c.match(toks, limit=11)
    assert hit == blocks[:2] and n == 8 and cow is None
    # divergence INSIDE block 2 -> CoW candidate (src block, rows kept)
    div = toks.copy()
    div[6] = 88
    hit, n, cow = c.match(div, limit=11)
    assert hit == blocks[:1] and n == 4
    assert cow == (blocks[1], 2)                   # 2 matching rows kept
    # reclaim frees cache-only blocks; slot-held ones are not stealable
    for b in blocks:
        a.decref(b)                                # slots release theirs
    assert c.reclaim(2) == 2 and len(c) == 0
    assert a.free_blocks == a.usable


# ---------------------------------------------------------------------------
# dynamic batcher: one compile per bucket, parity, threading
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def batch_fn():
    w = jnp.asarray(np.random.RandomState(3).randn(6, 4), jnp.float32)
    return lambda x: jnp.tanh(x @ w)


def test_batcher_one_compile_per_bucket(batch_fn):
    b = DynamicBatcher(batch_fn, max_batch=4)
    samples = [np.random.RandomState(i).randn(6).astype(np.float32)
               for i in range(8)]
    b.run_batch(samples[:3])          # -> bucket 4: compile
    b.run_batch(samples[:4])          # same bucket: cached
    b.run_batch(samples[3:6])         # same bucket: cached
    b.run_batch(samples[:1])          # -> bucket 1: compile
    b.run_batch(samples[1:2])         # cached
    assert b.compile_counts == {4: 1, 1: 1}


def test_batcher_results_match_direct(batch_fn):
    b = DynamicBatcher(batch_fn, max_batch=4)
    samples = [np.random.RandomState(10 + i).randn(6).astype(np.float32)
               for i in range(3)]
    outs = b.run_batch(samples)
    want = np.asarray(batch_fn(jnp.asarray(np.stack(samples))))
    for got, exp in zip(outs, want):
        np.testing.assert_allclose(got, exp, rtol=1e-6)


def test_batcher_threaded_hot_path_never_recompiles(batch_fn):
    metrics = ServingMetrics()
    b = DynamicBatcher(batch_fn, max_batch=4, max_wait_s=0.01,
                       metrics=metrics)
    sample = np.zeros((6,), np.float32)
    b.warmup(sample)                      # compile every rung up front
    warm = b.compile_counts
    assert warm == {1: 1, 2: 1, 4: 1}
    b.start()
    samples = [np.random.RandomState(20 + i).randn(6).astype(np.float32)
               for i in range(16)]
    futures = []
    threads = [threading.Thread(
        target=lambda s=s: futures.append((s, b.submit(s))))
        for s in samples]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for s, fut in futures:
        got = fut.result(30)
        want = np.asarray(batch_fn(jnp.asarray(s[None])))[0]
        np.testing.assert_allclose(got, want, rtol=1e-6)
    b.close()
    # whatever flush sizes the race produced, every padded shape was a
    # pre-compiled rung: the hot path never traced again
    assert b.compile_counts == warm
    assert metrics.get("completed") == 16
    assert metrics.snapshot()["batch_occupancy"]["samples"] > 0


def test_batcher_single_request_flushes_on_max_wait(batch_fn):
    b = DynamicBatcher(batch_fn, max_batch=4, max_wait_s=0.005).start()
    s = np.random.RandomState(30).randn(6).astype(np.float32)
    got = b(s, timeout=30)
    np.testing.assert_allclose(
        got, np.asarray(batch_fn(jnp.asarray(s[None])))[0], rtol=1e-6)
    b.close()


def test_batcher_fault_fails_members_but_survives(batch_fn):
    b = DynamicBatcher(batch_fn, max_batch=2, max_wait_s=0.005).start()
    s = np.zeros((6,), np.float32)
    with faults.inject("serving.batch@1:raise"):
        with pytest.raises(faults.FaultError):
            b(s, timeout=30)
        got = b(s, timeout=30)   # batcher thread survived the fault
        np.testing.assert_allclose(
            got, np.asarray(batch_fn(jnp.asarray(s[None])))[0], rtol=1e-6)
    b.close()


# ---------------------------------------------------------------------------
# admission queue: shed, deadline, drain
# ---------------------------------------------------------------------------


def test_queue_full_sheds_fast():
    m = ServingMetrics()
    q = AdmissionQueue(2, metrics=m)
    q.submit(Request("a"))
    q.submit(Request("b"))
    t0 = time.monotonic()
    with pytest.raises(QueueFullError):
        q.submit(Request("c"))
    assert time.monotonic() - t0 < 0.1   # 429-style: no blocking
    assert m.get("rejected_queue_full") == 1
    assert m.get("accepted") == 2
    assert q.depth == 2


def test_queue_deadline_expires_while_queued():
    q = AdmissionQueue(4)
    req = q.submit(Request("x", timeout=0.01))
    time.sleep(0.03)
    assert q.pop(timeout=0.0) is None    # expired request skipped
    with pytest.raises(DeadlineExceededError):
        req.result(1.0)


def test_queue_fifo_and_cancelled_skip():
    q = AdmissionQueue(4)
    a, b, c = Request(1), Request(2), Request(3)
    for r in (a, b, c):
        q.submit(r)
    b.cancel()
    assert q.pop(timeout=0.0) is a
    assert q.pop(timeout=0.0) is c       # b failed + skipped
    with pytest.raises(RequestCancelled):
        b.result(1.0)


def test_queue_close_drain_semantics():
    q = AdmissionQueue(4)
    kept = q.submit(Request("kept"))
    q.close(drain=True)
    with pytest.raises(ServerClosedError):
        q.submit(Request("late"))
    assert q.pop(timeout=0.0) is kept    # drain leaves queued work
    assert q.drained()

    q2 = AdmissionQueue(4)
    dropped = q2.submit(Request("dropped"))
    q2.close(drain=False)
    with pytest.raises(ServerClosedError):
        dropped.result(1.0)


def test_submit_drop_fault_is_deterministic_overload():
    q = AdmissionQueue(8)
    with faults.inject("serving.submit@2:drop"):
        q.submit(Request(1))
        with pytest.raises(QueueFullError):   # exactly the 2nd submit
            q.submit(Request(2))
        q.submit(Request(3))
    assert q.depth == 2


# ---------------------------------------------------------------------------
# continuous-batching slot engine: token parity vs uncached decode
# ---------------------------------------------------------------------------


def test_slot_engine_greedy_parity_single(gpt, server):
    p = _prompt(0, 5)
    out = server.generate(p, max_new_tokens=6, timeout=120)
    np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 6))


def test_slot_engine_concurrent_parity_and_midflight_join(gpt, server):
    """3 requests of different prompt lengths on 2 slots: the third
    joins at a step boundary in whichever slot frees first (a recycled
    slot), while the survivor keeps decoding. Every output must be
    token-identical to the uncached reference chain."""
    prompts = [_prompt(1, 5), _prompt(2, 9), _prompt(3, 3)]
    new = [7, 3, 6]
    futs = [server.submit(p, max_new_tokens=n, timeout=120)
            for p, n in zip(prompts, new)]
    outs = [f.result(120) for f in futs]   # engine idle before refs
    for p, n, out in zip(prompts, new, outs):
        np.testing.assert_array_equal(out, _ref_greedy(gpt, p, n))


def test_recycled_slot_stale_kv_masked(gpt):
    """max_slots=1 forces B into the slot A just used — and with the
    prefix cache off, into the very physical blocks A's eviction freed
    (the allocator reissues them), with A's longer KV still in the
    rows; B's parity proves stale keys are masked/overwritten, never
    attended."""
    srv = serving.Server(gpt, max_slots=1, block_size=8,
                         prefix_cache=False).start()
    try:
        a, b = _prompt(4, 12), _prompt(5, 4)
        out_a = srv.generate(a, max_new_tokens=4, timeout=120)
        assert srv.engine.blocks_in_use == 0     # A's blocks recycled
        out_b = srv.generate(b, max_new_tokens=6, timeout=120)
        np.testing.assert_array_equal(out_a, _ref_greedy(gpt, a, 4))
        np.testing.assert_array_equal(out_b, _ref_greedy(gpt, b, 6))
        assert srv.engine.compile_counts["decode"] == 1
    finally:
        srv.shutdown(drain=True)


def test_eos_eviction_frees_slot_early(gpt, server):
    p = _prompt(6, 4)
    eos = int(_full_logits(gpt, p.reshape(1, -1))[:, -1].argmax(-1)[0])
    out = server.generate(p, max_new_tokens=5, eos_token_id=eos,
                          timeout=120)
    # stops AT the eos token — no padding, slot freed for the next join
    np.testing.assert_array_equal(
        out, np.concatenate([p, [eos]]).astype(np.int32))
    assert server.engine.active == 0


def test_sampling_topk1_degenerates_to_greedy(gpt, server):
    p = _prompt(7, 5)
    greedy = server.generate(p, max_new_tokens=4, timeout=120)
    for seed in (0, 9):
        sampled = server.generate(p, max_new_tokens=4, do_sample=True,
                                  top_k=1, seed=seed, timeout=120)
        np.testing.assert_array_equal(sampled, greedy)


def test_slot_engine_compiles_exactly_once_total(server):
    """After everything the shared server has decoded — many requests,
    short and long prompts, joins, evictions — there is exactly ONE
    compiled step (prefill folded in; the per-rung ladder is gone) and
    one CoW helper, both traced at warmup."""
    counts = server.engine.compile_counts
    assert counts == {"decode": 1, "cow": 1}
    assert not any(isinstance(k, tuple) for k in counts)


# what each engine configuration's step takes and returns: the columns
# of a slot's row of the one staged `batch` array (beside the chunk's 8
# token columns and the table's 8), the keys of `extras`, and the keys
# of the returned `out`
_BATCH = {"tok", "pos", "nvalid", "tables"}
_EXTRAS = {"prev_pick"}
_OUT = {"pick", "logits", "aux"}
_SEAM = {
    "plain": ({}, _BATCH, _EXTRAS, _OUT),
    "spec_k2_self_draft": ({"spec_len": 2}, _BATCH, _EXTRAS,
                           _OUT | {"verify"}),
    "adapters": ({"max_adapters": 3, "lora_rank": 2}, _BATCH | {"aid"},
                 _EXTRAS | {"lora_a", "lora_b"}, _OUT),
    "int8_w8a8": ({"quantize": True, "w8a8": True}, _BATCH,
                  _EXTRAS | {"act_scale"}, _OUT | {"amax"}),
    "mesh_dp1_mp2": ({"mesh": "dp1.mp2"}, _BATCH, _EXTRAS, _OUT),
}


@pytest.mark.parametrize("config", sorted(_SEAM))
def test_step_contract_and_one_trace_a_program(gpt, config):
    """Every engine configuration has ONE signature of the compiled
    step: `_stage` puts everything the host says into one int32 array,
    a row a slot, the step names what comes out, an option adds a
    column or a key and moves nothing. `warmup()` and twenty mixed
    steps (chunked prefill beside decode, greedy beside sampling) then
    leave each program traced exactly once."""
    import jax

    kw, batch_keys, extras_keys, out_keys = _SEAM[config]
    if "mesh" in kw and len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    eng = serving.SlotEngine(gpt, max_slots=2, block_size=8,
                             prefill_chunk=8, **kw)
    programs = {"decode": 1, "cow": 1}
    if "spec_len" in kw:
        programs["draft"] = 1
    assert eng.warmup() == programs
    with observe.no_retrace():
        vec = np.zeros((2,), np.int32)
        batch, extras = eng._stage(np.full((2, 8), 7, np.int32), vec + 5,
                                   vec + 3)
        assert set(eng._batch_cols) == batch_keys
        assert set(extras) == extras_keys
        # tok | pos | nvalid | tables (| aid), the tables null so far
        assert batch.dtype == np.int32
        assert batch.shape == (2, 8 + 2 + 8 + ("aid" in batch_keys))
        np.testing.assert_array_equal(
            batch[0, :18], [7] * 8 + [5, 3] + [NULL_BLOCK] * 8)
        out, eng._pools = eng._decode(eng._values, batch, eng._pools,
                                      extras)
        assert set(out) == out_keys
        assert out["logits"].shape == (2, VOCAB)
        assert out["pick"].shape == (2,) and out["pick"].dtype == np.int32
        np.testing.assert_array_equal(
            out["pick"], np.asarray(out["logits"]).argmax(-1))
        futs = [eng.submit(_prompt(300 + n, n), max_new_tokens=m,
                           timeout=None, do_sample=bool(n % 2), seed=n,
                           adapter_id=n % 3 if "max_adapters" in kw else 0)
                for n, m in ((5, 16), (19, 20), (11, 14), (26, 18))]
        eng._admit()
        while eng.active or eng.queue.depth:
            eng._step()
            eng._admit()
        for f in futs:
            assert f.result(5).size == f.payload.size \
                + f.gen["max_new_tokens"]
    assert eng.metrics.get("steps") >= 20
    assert eng.metrics.get("step_errors") == 0
    assert eng.compile_counts == programs


def test_submit_validates_lengths(server):
    with pytest.raises(ValueError):
        server.submit(np.arange(60), max_new_tokens=10)  # > max_seq_len
    with pytest.raises(ValueError):
        server.submit(np.zeros((0,), np.int32))


def test_submit_block_capacity_sheds_with_429(gpt):
    """A request whose block demand exceeds the whole pool sheds with
    the retriable CapacityExhaustedError (429), distinct from the hard
    ValueError for out-of-range lengths."""
    srv = serving.Server(gpt, max_slots=2, block_size=8,
                         num_blocks=3, warmup=False)   # 2 usable blocks
    try:
        with pytest.raises(CapacityExhaustedError) as ei:
            srv.submit(np.arange(1, 11), max_new_tokens=10)  # 3 blocks
        assert ei.value.status == 429 and ei.value.retriable
        assert srv.metrics.get("rejected_capacity") == 1
        # a pool-sized request is still admissible
        assert srv.engine._blocks_needed(16) <= srv.engine._alloc.usable
    finally:
        srv.shutdown(drain=True)


# ---------------------------------------------------------------------------
# paged decode paths: chunked prefill, prefix sharing, copy-on-write
# ---------------------------------------------------------------------------


def _drive(eng, prompt, max_new=6, snoop_first_logits=False):
    """Synchronously admit + step one request on an idle engine (no
    thread — deterministic scheduling). Optionally snoops the logits
    that seeded decode (the prefill output)."""
    fut = eng.submit(np.asarray(prompt, np.int32), max_new_tokens=max_new,
                     timeout=None)
    eng._admit()
    first = None
    while eng.active:
        eng._step()
        if snoop_first_logits and first is None:
            for s in eng._slots:
                if s is not None and s.state == "decode":
                    first = np.asarray(s.next_logits).copy()
    return fut.result(timeout=5), first


@pytest.fixture()
def eng(gpt):
    e = serving.SlotEngine(gpt, max_slots=2, block_size=8,
                           prefill_chunk=8)
    e.warmup()
    return e


def test_chunked_prefill_long_prompt_parity(gpt, eng):
    """A prompt much longer than the chunk prefills across several
    steps of the SAME compiled program — token parity and no extra
    traces."""
    p = _prompt(50, 29)                       # 29 tokens, chunk 8
    out, _ = _drive(eng, p, max_new=5)
    np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 5))
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    assert eng.metrics.get("prefill_tokens") >= 28


def test_prefix_cache_hit_bitwise_identical_logits(gpt, eng):
    """Warm run re-serves a finished prompt's blocks from the prefix
    cache: fewer prompt tokens computed, same tokens, and the logits
    that seed decode are BITWISE identical to the cold run's."""
    p = list(range(1, 21))
    cold_out, cold_logits = _drive(eng, p, snoop_first_logits=True)
    assert eng.metrics.get("prefix_hit_blocks") == 0
    assert eng.prefix_cache_size > 0          # eviction donated blocks
    warm_out, warm_logits = _drive(eng, p, snoop_first_logits=True)
    assert eng.metrics.get("prefix_hit_blocks") > 0
    np.testing.assert_array_equal(cold_out, warm_out)
    assert np.array_equal(cold_logits, warm_logits)   # bitwise
    assert eng.metrics.get("prefix_hit_tokens") >= 16


def test_cow_divergence_parity(gpt, eng):
    """A second prompt diverging INSIDE a cached block triggers
    copy-on-write (block copied, tail overwritten); its tokens must
    match the uncached reference exactly, and the original cached
    sequence must be unaffected."""
    a = list(range(1, 18))
    out_a, _ = _drive(eng, a)
    b = list(a)
    b[11] = 77                                # diverge inside block 2
    out_b, _ = _drive(eng, b)
    assert eng.metrics.get("cow_splits") >= 1
    np.testing.assert_array_equal(out_b, _ref_greedy(gpt, b, 6))
    # the shared source block was copied, not mutated: a re-run of the
    # original prompt still matches
    out_a2, _ = _drive(eng, a)
    np.testing.assert_array_equal(out_a, out_a2)


def test_alloc_block_fault_fails_request_no_leak(gpt, eng):
    """Deterministic pool exhaustion mid-admission: the request fails,
    partially reserved blocks roll back, the engine keeps serving."""
    free0 = eng.free_blocks
    with faults.inject("serving.alloc_block@2:raise"):
        fut = eng.submit(_prompt(60, 10), max_new_tokens=6, timeout=None)
        eng._admit()
        with pytest.raises(faults.FaultError):
            fut.result(5)
    assert eng.free_blocks == free0           # rollback: no leak
    p = _prompt(61, 6)
    out, _ = _drive(eng, p, max_new=3)        # engine still serves
    np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 3))


def test_cow_split_fault_fails_request_no_leak(gpt, eng):
    a = list(range(1, 18))
    _drive(eng, a)                            # populate the cache
    b = list(a)
    b[11] = 77
    free0 = eng.free_blocks
    with faults.inject("serving.cow_split@1:raise"):
        fut = eng.submit(np.asarray(b, np.int32), max_new_tokens=6,
                         timeout=None)
        eng._admit()
        with pytest.raises(faults.FaultError):
            fut.result(5)
    assert eng.free_blocks == free0
    out, _ = _drive(eng, b)                   # retry succeeds, parity
    np.testing.assert_array_equal(out, _ref_greedy(gpt, b, 6))


def test_admission_waits_for_freed_blocks(gpt):
    """A pool too small for two concurrent requests serialises them via
    requeue-at-head instead of shedding: all complete, with parity,
    and the prefix cache yields its blocks back under pressure."""
    srv = serving.Server(gpt, max_slots=2, block_size=8,
                         num_blocks=4).start()   # 3 usable blocks
    try:
        prompts = [_prompt(70 + i, 10) for i in range(3)]   # 2 blocks ea
        futs = [srv.submit(p, max_new_tokens=4, timeout=120)
                for p in prompts]
        for p, f in zip(prompts, futs):
            np.testing.assert_array_equal(
                f.result(120), _ref_greedy(gpt, p, 4))
        assert srv.metrics.get("completed") == 3
        assert srv.metrics.get("rejected_capacity") == 0
    finally:
        srv.shutdown(drain=True)


def test_steady_state_runs_under_no_retrace(gpt):
    """strict_shapes: after warmup the engine loop runs inside
    observe.no_retrace() — the whole run proves the unified paged step
    never traces again (shape drift would raise RetraceError)."""
    srv = serving.Server(gpt, max_slots=2, block_size=8,
                         strict_shapes=True).start()
    try:
        for i in range(3):
            p = _prompt(80 + i, 5 + 7 * i)    # mixed lengths on purpose
            out = srv.generate(p, max_new_tokens=4, timeout=120)
            np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 4))
        assert srv.engine.compile_counts == {"decode": 1, "cow": 1}
        # the global compile audit agrees: one unified step, traced at
        # warmup, never again under traffic
        assert len(observe.compile_events("serving.step")) >= 1
    finally:
        srv.shutdown(drain=True)


# ---------------------------------------------------------------------------
# robustness: mid-decode faults, deadlines, cancel, drain
# ---------------------------------------------------------------------------
# the pools: token-major, donated to every program, updated in place
# (the fixture has 4 heads of 8 over blocks of 8 tokens... and a head
# dim of 8, so `[num_blocks, 8, 4, 8]`: a swapped head and token axis
# cannot pass by shape)
# ---------------------------------------------------------------------------


def _dead(pools):
    return [a.is_deleted() for a in pools]


def test_paged_engine_matches_dense_generate_over_mixed_steps(gpt, eng):
    """Token for token against `generate()`'s dense `[b, nh, s, hd]`
    cache, over a run whose steps mix a prefilling slot (a 19-token
    prompt in chunks of 8) with a decoding one, and whose third request
    joins a recycled slot."""
    prompts = [_prompt(70, 19), _prompt(71, 5), _prompt(72, 11)]
    new = [6, 9, 4]
    futs = [eng.submit(p, max_new_tokens=n, timeout=None)
            for p, n in zip(prompts, new)]
    mixed = 0
    while eng.active or eng.queue.depth:
        eng._admit()
        states = {s.state for s in eng._slots if s is not None}
        mixed += states == {"prefill", "decode"}
        eng._step()
    assert mixed >= 1
    for p, n, fut in zip(prompts, new, futs):
        want = gpt.generate(p[None, :], max_new_tokens=n)
        np.testing.assert_array_equal(fut.result(5),
                                      np.asarray(want._value)[0])
    assert eng.metrics.get("pool_inplace_steps") == \
        eng.metrics.get("steps") > 0


# -- what crosses between the host and the device a step ----------------------


def _parent_pick(row, gen, rng):
    """`_pick` as it read before the step picked: the whole row on the
    host, argmax for a greedy request, the warped draw from the
    request's own stream for a sampling one."""
    if not gen.get("do_sample"):
        return int(row.argmax())
    scaled = row / max(gen.get("temperature", 1.0), 1e-6)
    top_k = gen.get("top_k", 0)
    if top_k:
        kth = np.sort(scaled)[-min(top_k, scaled.size)]
        scaled = np.where(scaled < kth, -np.inf, scaled)
    p = np.exp(scaled - scaled.max())
    p /= p.sum()
    return int(rng.choice(p.size, p=p))


def _run_against_the_host_path(eng, futs):
    """Step an idle engine through `futs`. Before every step, take each
    decoding slot's pending row through its handle and pick from it as
    the parent's host path did (a stream of our own for a sampling
    request, seeded like the slot's); after the step, the token the
    engine committed has to be that one. Returns how many tokens were
    checked, by kind of request, and how many steps mixed a prefilling
    slot with a decoding one."""
    rngs, checked, mixed = {}, {"greedy": 0, "sampled": 0}, 0
    while eng.active or eng.queue.depth:
        eng._admit()
        live = [s for s in eng._slots if s is not None]
        mixed += {s.state for s in live} == {"prefill", "decode"}
        want = []
        for s in live:
            if s.state != "decode" or s.next_logits is None:
                continue
            gen = s.req.gen
            rng = rngs.setdefault(
                s.req.id, np.random.RandomState(gen.get("seed", 0)))
            row = np.asarray(s.next_logits)
            assert row.shape == (VOCAB,) and row.dtype == np.float32
            want.append((s, len(s.tokens), _parent_pick(row, gen, rng)))
        eng._step()
        for s, at, token in want:
            assert s.tokens[at] == token
            checked["sampled" if s.req.gen.get("do_sample")
                    else "greedy"] += 1
    for f in futs:
        assert f.result(5).size == f.payload.size \
            + f.gen["max_new_tokens"]
    return checked, mixed


def test_greedy_token_is_the_argmax_of_the_row_at_every_step(gpt, eng):
    """The step's own pick, read back as 4 bytes a slot, is the first
    maximum of the row the host used to read whole: at every step of a
    run that mixes chunked prefill with decode and recycles a slot,
    and so the whole answers are the no-cache reference's."""
    prompts = [_prompt(170, 19), _prompt(171, 5), _prompt(172, 11)]
    new = [6, 9, 4]
    futs = [eng.submit(p, max_new_tokens=n, timeout=None)
            for p, n in zip(prompts, new)]
    checked, mixed = _run_against_the_host_path(eng, futs)
    assert mixed >= 1 and checked == {"greedy": sum(new), "sampled": 0}
    for p, n, fut in zip(prompts, new, futs):
        np.testing.assert_array_equal(fut.result(5), _ref_greedy(gpt, p, n))
    assert eng.metrics.get("device_picks") == sum(new) \
        == eng.metrics.get("tokens_out")


@pytest.mark.parametrize("gen", [
    {"seed": 5}, {"seed": 6, "temperature": 0.7, "top_k": 12}],
    ids=["plain", "warped"])
def test_seeded_sampling_beside_greedy_slots_is_the_host_paths(gpt, gen):
    """A sampling request fetches its row through the handle, warps it
    and draws from its own stream exactly as when the whole batch's
    logits came to the host: the same tokens for the same seed, with a
    greedy request decoding in the slot beside it, whose tokens stay
    the reference's."""
    eng = serving.SlotEngine(gpt, max_slots=3, block_size=8,
                             prefill_chunk=8)
    eng.warmup()
    greedy = [_prompt(180, 13), _prompt(181, 4)]
    futs = [eng.submit(p, max_new_tokens=10, timeout=None) for p in greedy]
    futs.append(eng.submit(_prompt(182, 9), max_new_tokens=12,
                           timeout=None, do_sample=True, **gen))
    checked, _ = _run_against_the_host_path(eng, futs)
    assert checked == {"greedy": 20, "sampled": 12}
    for p, fut in zip(greedy, futs):
        np.testing.assert_array_equal(fut.result(5), _ref_greedy(gpt, p, 10))
    # a second engine, nobody looking at its rows: the same answer
    other = serving.SlotEngine(gpt, max_slots=3, block_size=8,
                               prefill_chunk=8)
    again = other.submit(_prompt(182, 9), max_new_tokens=12, timeout=None,
                         do_sample=True, **gen)
    other._admit()
    while other.active:
        other._step()
    np.testing.assert_array_equal(again.result(5), futs[-1].result(5))
    assert other.metrics.get("logit_rows_fetched") == 12
    assert other.metrics.get("device_picks") == 0


def test_next_logits_is_a_handle_on_a_row_of_the_devices_logits(gpt, eng):
    """What a slot carries between steps is no host row but a handle:
    a new object every commit, converted with and without a dtype, the
    same numbers each time, each conversion one row fetched; and the
    token beside it is that row's argmax."""
    fut = eng.submit(_prompt(190, 11), max_new_tokens=5, timeout=None)
    eng._admit()
    handles, rows = [], []
    while eng.active:
        eng._step()
        for s in eng._slots:
            if s is not None and s.state == "decode":
                h = s.next_logits
                assert h is not None and not isinstance(h, np.ndarray)
                assert all(h is not seen for seen in handles)
                before = eng.metrics.get("logit_rows_fetched")
                row = np.asarray(h)
                assert row.shape == (VOCAB,) and row.dtype == np.float32
                np.testing.assert_array_equal(
                    np.asarray(h, np.float32), row)
                assert np.asarray(h, np.float64).dtype == np.float64
                kept = np.asarray(h).copy()
                kept[0] += 1.0                # a copy is the caller's
                np.testing.assert_array_equal(np.asarray(h), row)
                assert eng.metrics.get("logit_rows_fetched") == before + 5
                assert s.next_token == int(row.argmax())
                handles.append(h)
                rows.append(row)
    assert len(handles) == 5
    answer = fut.result(5)
    np.testing.assert_array_equal(
        answer[11:], [int(r.argmax()) for r in rows])


def test_counters_say_what_crossed(gpt):
    """`device_picks`, `logit_rows_fetched` and `readback_bytes` on a
    greedy run (every token the step's own pick, no row fetched, a
    step's read-back the picks and the model's one count) and on a run
    with a sampling request (a row a sampled token, nothing more)."""
    eng = serving.SlotEngine(gpt, max_slots=4, block_size=8,
                             prefill_chunk=8)
    eng.warmup()
    assert eng.metrics.get("readback_bytes") == 0     # warm-up reads none
    m = eng.metrics

    def run(**gen):
        futs = [eng.submit(_prompt(200 + n, n), max_new_tokens=7,
                           timeout=None) for n in (3, 12)]
        futs.append(eng.submit(_prompt(210, 6), max_new_tokens=9,
                               timeout=None, **gen))
        was = {k: m.get(k) for k in (
            "device_picks", "logit_rows_fetched", "readback_bytes",
            "steps", "tokens_out")}
        eng._admit()
        while eng.active:
            eng._step()
        for f in futs:
            f.result(5)
        return {k: m.get(k) - v for k, v in was.items()}

    # 4 slots' picks and GPT's one int32 count (`attn_key_tiles`)
    a_step = 4 * 4 + 4
    greedy = run()
    assert greedy["device_picks"] == greedy["tokens_out"] == 23
    assert greedy["logit_rows_fetched"] == 0
    assert greedy["readback_bytes"] == a_step * greedy["steps"] > 0
    mixed = run(do_sample=True, seed=3)
    assert mixed["tokens_out"] == 23
    assert mixed["device_picks"] == 14 and mixed["logit_rows_fetched"] == 9
    assert mixed["readback_bytes"] == a_step * mixed["steps"]
    counters = m.snapshot()["counters"]
    assert counters["device_picks"] == 37
    text = observe.prometheus_text(serving=m)
    for name in ("device_picks", "logit_rows_fetched", "readback_bytes"):
        assert f"paddle_serving_{name}_total {counters[name]}" in text


def test_warmup_leaves_live_pools(gpt):
    """`warmup()` hands the pools to the step and to the CoW copy like
    any caller: what it was built with is gone, what it holds after is
    live, twice over."""
    e = serving.SlotEngine(gpt, max_slots=2, block_size=8,
                           prefill_chunk=8)
    assert e._pools[0][0].shape == (e.num_blocks, 8, 4, 8)
    built = e._arrays(e._pools)
    e.warmup()
    assert all(_dead(built))
    assert not any(_dead(e._arrays(e._pools)))
    e.warmup()                                # under no_retrace
    assert not any(_dead(e._arrays(e._pools)))
    assert e.metrics.get("pool_inplace_steps") == 0   # no step yet
    p = _prompt(73, 6)
    out, _ = _drive(e, p, max_new=3)
    np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 3))


def test_step_and_cow_update_the_pools_in_place(gpt, eng):
    """The arrays handed to a step, and to a copy-on-write copy, read
    deleted after it; the engine holds live ones; every step counted."""
    a = list(range(1, 18))
    eng.submit(np.asarray(a, np.int32), max_new_tokens=3, timeout=None)
    eng._admit()
    while eng.active:
        before, n = eng._arrays(eng._pools), eng.metrics.get("steps")
        eng._step()
        # (the last call only samples the last token: no dispatch)
        assert all(_dead(before)) == (eng.metrics.get("steps") > n)
        assert not any(_dead(eng._arrays(eng._pools)))
    b = list(a)
    b[11] = 77                                # diverge inside block 2
    fut = eng.submit(np.asarray(b, np.int32), max_new_tokens=3,
                     timeout=None)
    before = eng._arrays(eng._pools)
    eng._admit()
    assert eng.metrics.get("cow_splits") == 1
    assert all(_dead(before))
    assert not any(_dead(eng._arrays(eng._pools)))
    while eng.active:
        eng._step()
    np.testing.assert_array_equal(fut.result(5), _ref_greedy(gpt, b, 3))
    assert eng.metrics.get("pool_inplace_steps") == \
        eng.metrics.get("steps") > 0
    assert eng.metrics.get("pool_rebuilds") == 0


def test_export_racing_a_running_loop_returns_whole_blocks(gpt):
    """`export_prefix_blocks` from another thread while the loop steps:
    every payload is None or the same whole blocks, never "Array has
    been deleted". The step is held between its dispatch and the rebind
    so that a reader without the lock would meet the donated arrays."""
    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    eng = srv.engine
    stop = threading.Event()

    def traffic():
        i = 0
        while not stop.is_set():
            srv.generate(_prompt(200 + i, 6), max_new_tokens=6,
                         timeout=120)
            i += 1

    t = threading.Thread(target=traffic, daemon=True)
    try:
        p = _prompt(80, 20)
        srv.generate(p, max_new_tokens=2, timeout=120)
        ref = eng.export_prefix_blocks(p)     # the loop is idle
        assert ref["n_tokens"] == 16 and ref["row_order"] == "thd"
        assert ref["layers"][0][0].shape == (2, 8, 4, 8)
        real = eng._decode

        def held(*args):
            out = real(*args)
            time.sleep(0.002)
            return out

        eng._decode = held
        t.start()
        whole, until = 0, time.monotonic() + 60
        target = eng.metrics.get("steps") + 40
        while eng.metrics.get("steps") < target \
                and time.monotonic() < until:
            got = eng.export_prefix_blocks(p)
            if got is None:                   # evicted under pressure
                continue
            for (k, v), (k0, v0) in zip(got["layers"], ref["layers"]):
                np.testing.assert_array_equal(k, k0)
                np.testing.assert_array_equal(v, v0)
            whole += 1
        assert whole > 0
        assert eng.metrics.get("step_errors") == 0
    finally:
        stop.set()
        if t.is_alive():
            t.join(120)
        srv.shutdown(drain=True)


@pytest.mark.parametrize("when", ["before", "after", "readback"])
def test_step_that_raises_leaves_a_serving_engine(gpt, when, monkeypatch):
    """A step that raises once its inputs were donated (or whose picks
    cannot be read) leaves no pool: the engine rebuilds empty ones,
    drops the prefix index, fails the live slots and serves the next
    request. One that raises before its dispatch donated nothing: the
    pools and the index stay."""
    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    eng = srv.engine
    try:
        warm = _prompt(90, 20)
        srv.generate(warm, max_new_tokens=2, timeout=120)
        assert eng.prefix_cache_size > 0
        real, calls = eng._decode, []

        def broken(*args):
            if calls:
                return real(*args)
            calls.append(when)
            if when == "before":
                raise RuntimeError("device fell over")
            out = real(*args)                 # dispatched: inputs gone
            if when == "after":
                raise RuntimeError("device fell over")
            # learnt of only when its picks are read, by which time the
            # loop has launched the next step on its outputs
            import jax

            def unreadable(tree, get=jax.device_get):
                monkeypatch.setattr(jax, "device_get", get)
                raise RuntimeError("device fell over")

            monkeypatch.setattr(jax, "device_get", unreadable)
            return out

        eng._decode = broken
        fut = srv.submit(_prompt(91, 4), max_new_tokens=8, timeout=120)
        with pytest.raises(RuntimeError, match="fell over"):
            fut.result(120)
        lost = when != "before"
        for p in (_prompt(92, 5), warm):      # the cached prompt too
            out = srv.generate(p, max_new_tokens=3, timeout=120)
            np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 3))
        assert srv.metrics.get("step_errors") == 1
        assert srv.metrics.get("pool_rebuilds") == int(lost)
        # the warm prompt's blocks were served from the index only
        # where the pools behind it survived
        assert (srv.metrics.get("prefix_hit_blocks") > 0) == (not lost)
        assert not any(_dead(eng._arrays(eng._pools)))
    finally:
        srv.shutdown(drain=True)


# ---------------------------------------------------------------------------


def test_mid_decode_fault_fails_inflight_engine_survives(gpt):
    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    try:
        with faults.inject("serving.step@2:raise"):
            fut = srv.submit(_prompt(8, 4), max_new_tokens=8, timeout=120)
            with pytest.raises(faults.FaultError):
                fut.result(120)
        # engine thread survived: the next request completes with parity
        p = _prompt(9, 4)
        out = srv.generate(p, max_new_tokens=3, timeout=120)
        np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 3))
        assert srv.metrics.get("failed") == 1
    finally:
        srv.shutdown(drain=True)


def test_deadline_exceeded_mid_decode(gpt):
    """A slow model (delay fault on every step) pushes a long request
    past its deadline while decoding; it must fail with
    DeadlineExceededError at a step boundary, not hang."""
    srv = serving.Server(gpt, max_slots=1, block_size=8).start()
    try:
        with faults.inject("serving.step@*:delay:0.05"):
            fut = srv.submit(_prompt(10, 4), max_new_tokens=40,
                             timeout=0.15)
            with pytest.raises(DeadlineExceededError):
                fut.result(120)
        assert srv.metrics.get("timeouts") >= 1
    finally:
        srv.shutdown(drain=True)


def test_cancel_mid_decode_frees_slot(gpt):
    srv = serving.Server(gpt, max_slots=1, block_size=8).start()
    try:
        with faults.inject("serving.step@*:delay:0.02"):
            fut = srv.submit(_prompt(11, 4), max_new_tokens=50,
                             timeout=120)
            deadline = time.monotonic() + 30
            while srv.engine.active == 0:   # wait until it holds a slot
                assert time.monotonic() < deadline
                time.sleep(0.005)
            fut.cancel()
            with pytest.raises(RequestCancelled):
                fut.result(120)
        # the slot is free again and serves the next request
        p = _prompt(12, 4)
        out = srv.generate(p, max_new_tokens=2, timeout=120)
        np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 2))
    finally:
        srv.shutdown(drain=True)


def test_graceful_drain_completes_all_pending(gpt):
    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    prompts = [_prompt(20 + i, 4) for i in range(5)]
    futs = [srv.submit(p, max_new_tokens=2, timeout=120) for p in prompts]
    srv.shutdown(drain=True)        # blocks until queue + slots drain
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(f.result(1), _ref_greedy(gpt, p, 2))
    with pytest.raises(ServerClosedError):
        srv.submit(prompts[0], max_new_tokens=2)


def test_non_drain_shutdown_sheds_and_evicts(gpt):
    srv = serving.Server(gpt, max_slots=1, block_size=8).start()
    with faults.inject("serving.step@*:delay:0.05"):
        futs = [srv.submit(_prompt(30 + i, 4), max_new_tokens=50,
                           timeout=120) for i in range(3)]
        deadline = time.monotonic() + 30
        while srv.engine.active == 0:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        srv.shutdown(drain=False)
    for f in futs:
        with pytest.raises(ServingError):   # evicted or shed, never hung
            f.result(5)


# ---------------------------------------------------------------------------
# the loop keeps one step in flight
# ---------------------------------------------------------------------------


def _by_hand(eng, waves):
    """`waves` of ``(prompt, gen)`` through an engine with no thread,
    each wave submitted when the one before it has been answered, one
    whole `_step()` at a time."""
    out = []
    for wave in waves:
        futs = [eng.submit(p, timeout=None, **gen) for p, gen in wave]
        eng._admit()
        while eng.active or eng.queue.depth:
            eng._step()
            eng._admit()
        out += [f.result(5) for f in futs]
    return out


def _by_loop(eng, waves, gap_s=0.003):
    """The same through the engine's own loop, which launches step n+1
    before it reads step n; the requests of a wave arrive `gap_s`
    apart, so they join a batch that is already decoding."""
    out = []
    eng.start()
    try:
        for wave in waves:
            futs = []
            for p, gen in wave:
                futs.append(eng.submit(p, timeout=None, **gen))
                time.sleep(gap_s)
            out += [f.result(60) for f in futs]
    finally:
        eng.shutdown(drain=True, timeout=60)
    assert eng._flight is None
    return out


@pytest.mark.parametrize("chunk", [4, 8])
def test_the_loop_and_steps_by_hand_give_the_same_tokens(gpt, chunk):
    """Greedy requests with staggered arrivals, a prefix hit and a
    copy-on-write: the loop, which keeps a step in flight and feeds a
    row's token back on the device, answers with the very ids of an
    engine stepped by hand, which are the no-cache reference's. It
    compiled nothing new for it (one trace, one executable, whether
    `prev_pick` is a step's result or the first step's zeros), wasted
    no column (every request ends by `max_new_tokens`, known at
    launch) and left nearly every step unwaited-for."""
    base = _prompt(400, 20)
    first = [(base, {"max_new_tokens": 9})]
    then = [(np.concatenate([base[:18], _prompt(401, 7)]),     # CoW at 16+2
             {"max_new_tokens": 12}),
            (np.concatenate([base, _prompt(402, 3)]),          # two blocks hit
             {"max_new_tokens": 5}),
            (_prompt(403, 31), {"max_new_tokens": 14}),
            (_prompt(404, 2), {"max_new_tokens": 1}),
            (_prompt(405, 6), {"max_new_tokens": 2})]
    kw = dict(max_slots=3, block_size=8, prefill_chunk=chunk)
    hand = serving.SlotEngine(gpt, **kw)
    loop = serving.SlotEngine(gpt, strict_shapes=True, **kw)
    assert loop.warmup() == {"decode": 1, "cow": 1}
    want = _by_hand(hand, [first, then])
    got = _by_loop(loop, [first, then])
    for (p, gen), a, b in zip(first + then, want, got):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            a, _ref_greedy(gpt, p, gen["max_new_tokens"]))
    assert loop.compile_counts == {"decode": 1, "cow": 1}
    assert loop._decode._cache_size() == 1
    for eng in (hand, loop):
        m = eng.metrics
        assert m.get("cow_splits") >= 1 and m.get("prefix_hit_blocks") >= 2
        assert m.get("pool_inplace_steps") == m.get("steps")
        assert m.get("tokens_out") == m.get("device_picks") == 43
        assert m.get("columns_wasted") == m.get("step_errors") == 0
    assert hand.metrics.get("steps_launched_ahead") == 0
    steps = loop.metrics.get("steps")
    assert 0.8 * steps <= loop.metrics.get("steps_launched_ahead") < steps


def test_eos_is_learnt_a_step_late_and_wastes_one_column(gpt):
    """An `eos_token_id` that fires mid-answer, under the loop: the row
    has one more column in flight by the time the host sees the token.
    The answer ends at EOS all the same, `columns_wasted` counts that
    column, and the request that takes the slot while the stray column
    is still in flight is untouched by it: the stray pick lands on no
    one, the stray row is written where no one reads."""
    p, q = _prompt(411, 11), _prompt(410, 13)
    plain = _ref_greedy(gpt, p, 12)
    at = next(k for k in range(3, 12)
              if plain[11 + k] not in plain[11:11 + k])
    eos = int(plain[11 + at])
    eng = serving.SlotEngine(gpt, max_slots=1, block_size=8,
                             prefill_chunk=8)
    eng.warmup()
    first = eng.submit(p, max_new_tokens=12, eos_token_id=eos, timeout=None)
    second = eng.submit(q, max_new_tokens=10, timeout=None)
    eng.start()
    try:
        got, after = first.result(60), second.result(60)
    finally:
        eng.shutdown(drain=True, timeout=60)
    np.testing.assert_array_equal(got, plain[:11 + at + 1])
    assert got[-1] == eos and len(first.token_times) == at + 1
    np.testing.assert_array_equal(after, _ref_greedy(gpt, q, 10))
    m = eng.metrics
    assert m.get("columns_wasted") == 1
    assert m.get("tokens_out") == at + 1 + 10 == m.get("device_picks")
    # the wasted column was computed, and said so; what the index was
    # given ends before it
    assert m.get("computed_tokens") == 11 + at + 1 + 13 + 9
    assert eng.free_blocks + eng.prefix_cache_size == eng._alloc.usable


def test_a_sampling_request_keeps_the_loop_in_order_while_it_lives(gpt):
    """A `do_sample` request among greedy ones: its token is drawn on
    the host from the row the step left, so while it decodes the loop
    lands each step before it launches the next (`steps_launched_ahead`
    stays where it was), and its draws are those of the engine stepped
    by hand for the same seed (which the tests above tie to the
    parent's host path). When it has gone the loop runs ahead again."""
    gen = {"max_new_tokens": 14, "do_sample": True, "seed": 5,
           "temperature": 0.8, "top_k": 20}
    wave = [(_prompt(420, 9), gen),
            (_prompt(421, 13), {"max_new_tokens": 8}),
            (_prompt(422, 4), {"max_new_tokens": 10})]
    kw = dict(max_slots=3, block_size=8, prefill_chunk=8)
    want = _by_hand(serving.SlotEngine(gpt, **kw), [wave])
    eng = serving.SlotEngine(gpt, **kw)
    eng.warmup()
    futs = [eng.submit(p, timeout=None, **g) for p, g in wave]
    eng.start()
    try:
        got = [f.result(60) for f in futs]
        m = eng.metrics
        steps, ahead = m.get("steps"), m.get("steps_launched_ahead")
        # all three joined the first step; the sampling request's two
        # prefill steps are the only ones no draw stood behind
        assert steps >= 2 + 13 and ahead == 1
        assert m.get("logit_rows_fetched") == 14
        tail = eng.submit(_prompt(423, 5), max_new_tokens=12, timeout=None)
        np.testing.assert_array_equal(
            tail.result(60), _ref_greedy(gpt, _prompt(423, 5), 12))
        assert m.get("steps_launched_ahead") - ahead >= 10
    finally:
        eng.shutdown(drain=True, timeout=60)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert eng.metrics.get("columns_wasted") == 0


@pytest.mark.parametrize("what", ["cancel", "deadline", "fault"])
def test_a_request_that_goes_with_a_step_in_flight(gpt, what):
    """A cancel and a deadline are seen by the sweep that opens a
    launch, a fault at ``serving.step`` fires there: each time the
    row's last column is still in flight. The request fails with its
    own error, the column is counted wasted, its pick lands on no one,
    and the engine serves the next request from the same slot with the
    reference's tokens."""
    eng = serving.SlotEngine(gpt, max_slots=1, block_size=8,
                             prefill_chunk=8)
    eng.warmup()
    eng.start()
    error = {"cancel": RequestCancelled, "deadline": DeadlineExceededError,
             "fault": faults.FaultError}[what]
    spec = "serving.step@6:raise" if what == "fault" \
        else "serving.step@*:delay:0.02"
    try:
        with faults.inject(spec):
            fut = eng.submit(_prompt(430, 5), max_new_tokens=50,
                             timeout=0.4 if what == "deadline" else None)
            if what == "cancel":
                until = time.monotonic() + 30
                while len(fut.token_times) < 3:
                    assert time.monotonic() < until
                    time.sleep(0.002)
                fut.cancel()
            with pytest.raises(error):
                fut.result(60)
        nxt = _prompt(431, 7)
        np.testing.assert_array_equal(
            eng.submit(nxt, max_new_tokens=6, timeout=None).result(60),
            _ref_greedy(gpt, nxt, 6))
    finally:
        eng.shutdown(drain=True, timeout=60)
    m = eng.metrics
    assert m.get("columns_wasted") == 1 and m.get("failed") == 1
    assert m.get("step_errors") == 0 and m.get("pool_rebuilds") == 0
    assert m.get("pool_inplace_steps") == m.get("steps")
    assert eng.free_blocks + eng.prefix_cache_size == eng._alloc.usable


# ---------------------------------------------------------------------------
# metrics + percentiles + trace integration
# ---------------------------------------------------------------------------


def test_metrics_snapshot_after_traffic(server):
    snap = server.snapshot()
    c = snap["counters"]
    assert c["completed"] >= 6
    assert c["accepted"] >= c["completed"]
    assert c["tokens_out"] >= 6
    assert 0 < snap["batch_occupancy"]["avg"] <= 1.0
    assert snap["qps"] > 0
    lat = snap["latency_s"]["e2e"]
    assert lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    # paged-KV sections: block occupancy, prefix traffic, chunked prefill
    blk = snap["kv_blocks"]
    assert blk["total"] == server.engine._alloc.usable
    assert 0 <= blk["occupancy"] <= 1.0 and blk["samples"] > 0
    pfx = snap["prefix_cache"]
    assert pfx["lookups"] >= c["completed"]
    assert 0 <= pfx["hit_rate"] <= 1.0
    cp = snap["chunked_prefill"]
    assert cp["tokens"] >= c["completed"] and cp["tokens_per_step"] > 0
    # JSON-exportable end to end
    assert json.loads(server.metrics_json())["counters"] == c


def test_prometheus_text_exports_paged_kv_gauges(server):
    text = server.metrics_prometheus()
    for needle in ("paddle_serving_kv_blocks_in_use",
                   "paddle_serving_kv_blocks_total",
                   "paddle_serving_kv_block_occupancy",
                   "paddle_serving_prefix_cache_hit_rate",
                   "paddle_serving_prefill_tokens_per_step",
                   "paddle_serving_queue_depth"):
        assert needle in text, needle


def test_percentile_linear_interpolation_exact():
    samples = [10.0, 20.0, 30.0, 40.0]
    assert serving.percentile(samples, 0) == 10.0
    assert serving.percentile(samples, 50) == 25.0
    assert serving.percentile(samples, 95) == pytest.approx(38.5)
    assert serving.percentile(samples, 100) == 40.0
    with pytest.raises(ValueError):
        serving.percentile(samples, 101)
    with pytest.raises(ValueError):
        serving.percentile([], 50)


def test_serving_spans_land_in_chrome_trace(server, tmp_path):
    names = {e["name"] for e in profiler.events()}
    assert "serving.step" in names
    assert "serving.prefill" not in names   # the ladder is gone
    path = profiler.export_chrome_tracing(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    assert any(ev["name"] == "serving.step" and ev["cat"] == "serving"
               for ev in trace["traceEvents"])
    # the percentile helper reads the same spans
    p = profiler.percentiles("serving.step", (50, 99))
    assert 0 < p[50] <= p[99]


# ---------------------------------------------------------------------------
# predictor satellites: unfilled handles, pool bounds
# ---------------------------------------------------------------------------


def _export_linear(tmp_path):
    from paddle_tpu.jit import InputSpec
    import paddle_tpu.nn as nn

    paddle.seed(5)
    model = nn.Sequential(nn.Linear(8, 4))
    model.eval()
    prefix = str(tmp_path / "served")
    paddle.jit.save(model, prefix,
                    input_spec=[InputSpec([4, 8], "float32")])
    return prefix


def test_predictor_unfilled_handle_raises(tmp_path):
    prefix = _export_linear(tmp_path)
    pred = paddle.inference.create_predictor(
        paddle.inference.Config(prefix))
    with pytest.raises(ValueError, match="input_0"):
        pred.run()    # nothing filled: must name the handle, not misalign
    h = pred.get_input_handle("input_0")
    h.copy_from_cpu(np.zeros((4, 8), np.float32))
    assert pred.run()


def test_predictor_pool_retrieve_bounds(tmp_path):
    prefix = _export_linear(tmp_path)
    pool = paddle.inference.PredictorPool(
        paddle.inference.Config(prefix), 2)
    assert pool.retrieve(1) is not None
    with pytest.raises(IndexError, match="valid indices"):
        pool.retrieve(2)
    with pytest.raises(IndexError):
        pool.retrieve(-1)


# ---------------------------------------------------------------------------
# bench smoke + optional http front
# ---------------------------------------------------------------------------


def test_bench_serving_smoke():
    """--steps 2 dry run of the closed-loop benchmark emits the
    BENCH_SERVING record."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench_serving.py"), "--steps", "2",
         "--clients", "1,2", "--max-new", "2", "--prompt-len", "4",
         "--hidden", "16", "--layers", "1", "--heads", "2",
         "--vocab", "31", "--max-seq-len", "32"],
        capture_output=True, text=True, timeout=420,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["bench"] == "BENCH_SERVING"
    assert len(final["levels"]) == 2
    for row in final["levels"]:
        assert row["errors"] == 0
        assert row["qps"] > 0 and row["p99_ms"] > 0


def test_http_front_door(gpt):
    """Bonus stdlib front door: generate + metrics + status mapping."""
    import urllib.error
    import urllib.request

    srv = serving.Server(gpt, max_slots=2, block_size=8).start()
    try:
        try:
            httpd = serving.http_front(srv, port=0)
        except OSError as e:
            pytest.skip(f"cannot bind loopback: {e}")
        port = httpd.server_address[1]
        p = _prompt(40, 4)
        body = json.dumps({"prompt": p.tolist(),
                           "max_new_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())["ids"]
        np.testing.assert_array_equal(out, _ref_greedy(gpt, p, 3))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            snap = json.loads(resp.read())
        assert snap["counters"]["completed"] >= 1
        # length validation maps to a 4xx, not a hang
        bad = json.dumps({"prompt": list(range(60)),
                          "max_new_tokens": 30}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate", data=bad,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert ei.value.code == 400
        httpd.shutdown()
    finally:
        srv.shutdown(drain=True)

# ---------------------------------------------------------------------------
# resilient fleet: supervision, failover, retry, hedge, breaker, brownout
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet(gpt):
    """Shared 2-replica Router: parity/sweep/brownout tests reuse it so
    the per-replica compile-once invariant is certified across many
    requests and injected fault rounds. Liveness is generous (no
    watchdog false-positives under CPU load); death only via kill() in
    dedicated fleets."""
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8),
                    hedge=False, retry_budget=3, breaker_threshold=10,
                    liveness_timeout_s=30.0, name="tf").start()
    yield router
    router.shutdown(drain=True)


def test_fleet_greedy_parity_and_compile_once(gpt, fleet):
    """Fleet-served greedy decode is bitwise the reference chain, and
    each replica holds exactly one decode + one cow trace."""
    prompts = [_prompt(60 + i, 4 + i) for i in range(4)]
    futs = [fleet.submit(p, max_new_tokens=5) for p in prompts]
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(f.result(120),
                                      _ref_greedy(gpt, p, 5))
    for name, counts in fleet.compile_counts().items():
        assert counts == {"decode": 1, "cow": 1}, (name, counts)


def test_fleet_failover_replay_bitwise(gpt):
    """Kill the replica holding an in-flight request: the Router
    replays it from the original prompt on the surviving replica and
    the client sees bitwise-identical greedy tokens, exactly once. The
    dead replica restarts with one fresh trace; a replay-path fault on
    a second kill surfaces as a typed error, never a hang."""
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8),
                    hedge=False, liveness_timeout_s=30.0,
                    backoff_base_s=0.02, name="kf").start()
    try:
        p = _prompt(70, 6)
        ref = router.submit(p, max_new_tokens=8).result(120)
        np.testing.assert_array_equal(ref, _ref_greedy(gpt, p, 8))

        resolved = []
        with faults.inject("serving.replica_step[kf.r0]@*:delay:0.05"):
            fut = router.submit(p, max_new_tokens=8)
            fut.add_done_callback(lambda r: resolved.append(r.id))
            time.sleep(0.12)            # in-flight on slowed r0
            router.kill("kf.r0")
            out = fut.result(120)
        np.testing.assert_array_equal(out, ref)
        assert len(resolved) == 1       # exactly-once delivery
        m = router.metrics
        assert m.get("replica_deaths") >= 1
        assert m.get("replays") >= 1

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(r["state"] == "healthy"
                   for r in router.snapshot()["replicas"]):
                break
            time.sleep(0.05)
        assert m.get("replica_restarts") >= 1
        # restart = ONE fresh trace per rebuilt engine, no extras
        for name, counts in router.compile_counts().items():
            assert counts == {"decode": 1, "cow": 1}, (name, counts)
        np.testing.assert_array_equal(
            router.submit(p, max_new_tokens=8).result(120), ref)

        # failover whose replay path itself faults -> typed error
        with faults.inject("serving.replica_step[kf.r0]@*:delay:0.05",
                           "serving.replay@1:raise"):
            fut = router.submit(p, max_new_tokens=8)
            time.sleep(0.12)            # on r0 again (least loaded tie)
            router.kill("kf.r0")
            with pytest.raises(ServingError):
                fut.result(120)
    finally:
        router.shutdown(drain=True)


def test_fleet_retry_budget_exhaustion_typed_error(gpt, fleet):
    """Persistent retriable faults burn the retry budget and surface as
    RetriesExhaustedError carrying the last underlying error; the fleet
    serves clean traffic immediately after."""
    p = _prompt(71, 5)
    ref = fleet.submit(p, max_new_tokens=4).result(120)
    with faults.inject("serving.replica_step@*:raise"):
        fut = fleet.submit(p, max_new_tokens=4)
        with pytest.raises(RetriesExhaustedError) as ei:
            fut.result(120)
        assert isinstance(ei.value.last_error, faults.FaultError)
        assert ei.value.retriable    # a later resubmission could work
    assert fleet.metrics.get("retry_budget_exhausted") >= 1
    np.testing.assert_array_equal(
        fleet.submit(p, max_new_tokens=4).result(120), ref)


def test_fleet_hedge_first_wins_loser_cancelled(gpt):
    """A straggling attempt is hedged onto the other replica after the
    configured delay; the fast attempt wins, the loser is cancelled and
    its late outcome suppressed — the client sees one result."""
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8),
                    hedge=True, hedge_after_s=0.05,
                    liveness_timeout_s=30.0, name="hf").start()
    try:
        p = _prompt(72, 5)
        ref = router.submit(p, max_new_tokens=6).result(120)
        with faults.inject("serving.replica_step[hf.r0]@*:delay:0.08"):
            out = router.submit(p, max_new_tokens=6).result(120)
        np.testing.assert_array_equal(out, ref)
        m = router.metrics
        assert m.get("hedges") == 1
        assert m.get("hedge_wins") == 1
        assert m.get("stale_attempts") >= 1   # the cancelled loser
        assert m.get("fleet_completed") == m.get("fleet_submitted")
    finally:
        router.shutdown(drain=True)


def test_circuit_breaker_state_machine():
    """Unit cycle under an injected clock: closed -> open on threshold
    consecutive failures -> half-open single probe after cooloff ->
    closed on success / re-open on probe failure."""
    now = [0.0]
    br = CircuitBreaker(threshold=2, cooloff_s=1.0, clock=lambda: now[0])
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"      # below threshold
    br.record_failure()
    assert br.state == "open"
    assert not br.allow()            # cooloff not elapsed
    now[0] = 1.5
    assert br.allow()                # the half-open probe
    assert br.state == "half-open"
    assert not br.allow()            # single probe only
    br.record_failure()              # probe failed -> re-open
    assert br.state == "open"
    now[0] = 3.0
    assert br.allow()
    br.record_success()              # probe succeeded -> closed
    assert br.state == "closed" and br.failures == 0
    # success resets the consecutive-failure count
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"


def test_fleet_breaker_opens_and_recovers(gpt):
    """Integration: consecutive failures on one replica open its
    breaker (traffic routes around it); after cooloff the half-open
    probe closes it again."""
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8),
                    hedge=False, breaker_threshold=2,
                    breaker_cooloff_s=0.4, retry_budget=3,
                    liveness_timeout_s=30.0, name="bf").start()
    try:
        p = _prompt(73, 5)
        r0 = router.replica_set.replicas[0]
        with faults.inject("serving.replica_step[bf.r0]@1-2:raise"):
            # two sequential requests: each lands on r0 first (least
            # loaded, lowest index), fails there, retries onto r1
            for _ in range(2):
                router.submit(p, max_new_tokens=3).result(120)
        assert r0.breaker.state == "open"
        # while open, traffic keeps flowing (routed around r0, or
        # through its half-open probe once the cooloff elapses)
        router.submit(p, max_new_tokens=3).result(120)
        time.sleep(0.5)              # cooloff elapses
        for _ in range(3):           # probe lands on r0 and closes it
            router.submit(p, max_new_tokens=3).result(120)
        assert r0.breaker.state == "closed"
    finally:
        router.shutdown(drain=True)


def test_fleet_brownout_sheds_by_priority_and_clamps(gpt, fleet):
    """Forced brownout: below-floor priorities shed with the retriable
    429 BrownoutShedError, admitted requests get max_new_tokens
    clamped; clearing the override restores full service."""
    p = _prompt(74, 5)
    fleet.set_brownout(True)
    try:
        with pytest.raises(BrownoutShedError) as ei:
            fleet.submit(p, max_new_tokens=12, priority=0)
        assert ei.value.status == 429 and ei.value.retriable
        assert fleet.metrics.get("brownout_sheds") >= 1
        out = fleet.submit(p, max_new_tokens=12, priority=2).result(120)
        assert out.size == p.size + fleet._brownout_max_new  # clamped
    finally:
        fleet.set_brownout(None)
    out = fleet.submit(p, max_new_tokens=12, priority=0).result(120)
    assert out.size == p.size + 12   # full service restored


def test_fleet_brownout_auto_enters_and_exits(gpt):
    """Hysteresis: load above brownout_high trips brownout
    automatically; drained load below brownout_low clears it."""
    # the four requests land within a millisecond, before either
    # engine's loop has moved one from its queue into its slot: each
    # replica's queue has to hold both of its two, or admission (not
    # brownout, which sheds nothing of priority 5) rejects the overflow
    # with its fast 429. Capacity 2 x (1 slot + 2 queued) = 6, and 4 in
    # flight is 0.67 of it
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=1, block_size=8),
                    hedge=False, queue_cap=2, tick_s=0.002,
                    brownout_high=0.4, brownout_low=0.1,
                    liveness_timeout_s=30.0, name="bo").start()
    try:
        with faults.inject("serving.replica_step@*:delay:0.03"):
            futs = [router.submit(_prompt(75 + i, 4), max_new_tokens=6,
                                  priority=5)
                    for i in range(4)]
            deadline = time.monotonic() + 10
            while not router.brownout_active \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            assert router.brownout_active
            assert router.metrics.get("brownout_entries") >= 1
            for f in futs:
                f.result(120)
            assert router.metrics.get("brownout_sheds") == 0
        deadline = time.monotonic() + 10
        while router.brownout_active and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not router.brownout_active
    finally:
        router.shutdown(drain=True)


def test_fleet_route_fault_retried_transparently(gpt, fleet):
    """A transient routing failure is retried under the budget and the
    client still gets correct tokens."""
    p = _prompt(76, 5)
    ref = _ref_greedy(gpt, p, 4)
    before = fleet.metrics.get("retries")
    with faults.inject("serving.route@1:raise"):
        out = fleet.submit(p, max_new_tokens=4).result(120)
    np.testing.assert_array_equal(out, ref)
    assert fleet.metrics.get("retries") > before


def test_fleet_zero_lost_zero_duplicate_sweep(gpt, fleet):
    """The chaos certification: under a scripted error sweep across
    both replicas and the routing path, every submitted request
    resolves exactly once — bitwise-correct greedy tokens or a typed
    ServingError — the schedule verifiably fired in full, and the
    per-replica compile counts never move."""
    prompts = [_prompt(80 + i, 4 + (i % 3)) for i in range(6)]
    refs = [_ref_greedy(gpt, p, 5) for p in prompts]

    resolutions = []
    lock = threading.Lock()

    def on_done(req):
        with lock:
            resolutions.append(req.id)

    with faults.ChaosSchedule(
            "serving.replica_step[tf.r0]@2:raise",
            "serving.replica_step[tf.r1]@3:raise",
            "serving.route@4:raise") as sched:
        futs = []
        for p in prompts:
            f = fleet.submit(p, max_new_tokens=5)
            f.add_done_callback(on_done)
            futs.append(f)
        outcomes = {"ok": 0, "typed": 0}
        for p, ref, f in zip(prompts, refs, futs):
            try:
                out = f.result(120)
                np.testing.assert_array_equal(out, ref)
                outcomes["ok"] += 1
            except ServingError:
                outcomes["typed"] += 1
        fired = sched.verify()       # every planned fault fired

    assert outcomes["ok"] + outcomes["typed"] == len(prompts)
    assert fired["serving.replica_step"] == 2
    assert fired["serving.route"] == 1
    # exactly-once: one done-callback per request, no duplicates
    assert sorted(resolutions) == sorted({f.id for f in futs})
    m = fleet.metrics
    assert m.get("fleet_submitted") == \
        m.get("fleet_completed") + m.get("fleet_failed")
    for name, counts in fleet.compile_counts().items():
        assert counts == {"decode": 1, "cow": 1}, (name, counts)


def test_fleet_watchdog_restarts_hung_replica(gpt):
    """Liveness: a replica whose heartbeat stalls (injected delay) is
    declared dead by the watchdog, its requests fail over bitwise, and
    it restarts with exactly one fresh trace."""
    router = Router(gpt, replicas=2,
                    engine_kw=dict(max_slots=2, block_size=8),
                    hedge=False, liveness_timeout_s=0.15,
                    backoff_base_s=0.02, name="wd").start()
    try:
        p = _prompt(77, 5)
        ref = router.submit(p, max_new_tokens=5).result(120)
        with faults.inject(
                "serving.replica_heartbeat[wd.r0]@5:delay:1.0"):
            futs = [router.submit(p, max_new_tokens=5)
                    for _ in range(3)]
            for f in futs:
                np.testing.assert_array_equal(f.result(120), ref)
        m = router.metrics
        assert m.get("replica_deaths") >= 1
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(r["state"] == "healthy"
                   for r in router.snapshot()["replicas"]):
                break
            time.sleep(0.05)
        assert m.get("replica_restarts") >= 1
        for name, counts in router.compile_counts().items():
            assert counts == {"decode": 1, "cow": 1}, (name, counts)
        np.testing.assert_array_equal(
            router.submit(p, max_new_tokens=5).result(120), ref)
    finally:
        router.shutdown(drain=True)


def test_retriable_classifier():
    assert retriable(CapacityExhaustedError("x"))
    assert retriable(QueueFullError("x"))
    assert retriable(ServerClosedError("x"))
    assert retriable(ReplicaDiedError("x"))
    assert retriable(faults.FaultError("x"))
    assert not retriable(RequestCancelled("x"))
    assert not retriable(DeadlineExceededError("x"))
    assert not retriable(ValueError("x"))


# ---------------------------------------------------------------------------
# request cancellation satellites
# ---------------------------------------------------------------------------


def test_cancel_wakes_blocked_result_promptly():
    """cancel() fails the future immediately: a client blocked in
    result() wakes with RequestCancelled without waiting for the engine
    to reach a step boundary (or forever, if nothing ever ran it)."""
    req = Request(np.array([1, 2, 3], np.int32))
    woke = []

    def waiter():
        t0 = time.monotonic()
        with pytest.raises(RequestCancelled):
            req.result(timeout=30)
        woke.append(time.monotonic() - t0)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    req.cancel()
    t.join(10)
    assert woke and woke[0] < 5     # promptly, not at the 30s timeout


def test_result_cancel_on_timeout_reclaims_queue_slot():
    """A client that gives up with cancel_on_timeout=True also cancels
    the request, so its queue entry is swept instead of leaking."""
    q = AdmissionQueue(2)
    req = q.submit(Request(np.array([1], np.int32)))
    with pytest.raises(TimeoutError):
        req.result(timeout=0.05, cancel_on_timeout=True)
    assert req.cancelled
    # the queue sweeps it on the next pop instead of handing it out
    assert q.pop(timeout=0.05) is None
    assert isinstance(req.exception(1), RequestCancelled)
    # without the opt-in, timeout leaves the request live
    q2 = AdmissionQueue(2)
    req2 = q2.submit(Request(np.array([1], np.int32)))
    with pytest.raises(TimeoutError):
        req2.result(timeout=0.05)
    assert not req2.cancelled
    assert q2.pop(timeout=0.05) is req2


def test_request_first_wins_and_done_callbacks():
    """The future is exactly-once: the first resolution wins, later
    ones report False; done-callbacks fire exactly once each, and one
    registered after resolution fires immediately."""
    req = Request(np.array([1], np.int32))
    calls = []
    req.add_done_callback(lambda r: calls.append("a"))
    assert req._complete(np.array([7], np.int32))
    assert not req._fail(RuntimeError("late"))     # suppressed
    assert not req._complete(np.array([9], np.int32))
    np.testing.assert_array_equal(req.result(1), [7])
    req.add_done_callback(lambda r: calls.append("b"))
    assert calls == ["a", "b"]


# ---------------------------------------------------------------------------
# server satellites: idempotent shutdown, fleet mode, Retry-After
# ---------------------------------------------------------------------------


def test_server_shutdown_idempotent(gpt):
    """shutdown() on a never-started server is a no-op, and double
    shutdown never re-runs drain against stopped backends."""
    srv = serving.Server(gpt, max_slots=2, block_size=8, warmup=False)
    srv.shutdown()                   # never started: no-op, no error
    srv.shutdown(drain=False)
    srv.start()
    out = srv.generate(_prompt(78, 4), max_new_tokens=2, timeout=120)
    assert out.size == 6
    srv.shutdown(drain=True)
    srv.shutdown(drain=True)         # second call: no-op
    srv.shutdown(drain=False)


def test_server_fleet_mode(gpt):
    """Server(replicas=2) serves through the Router: same API, fleet
    snapshot + per-replica prometheus gauges."""
    with serving.Server(gpt, replicas=2, max_slots=2, block_size=8,
                        fleet=dict(hedge=False, liveness_timeout_s=30.0,
                                   name="sv")) as srv:
        p = _prompt(79, 5)
        np.testing.assert_array_equal(
            srv.generate(p, max_new_tokens=4, timeout=120),
            _ref_greedy(gpt, p, 4))
        fut = srv.submit(p, max_new_tokens=4, priority=3)
        fut.result(120)
        snap = srv.snapshot()
        assert len(snap["fleet"]["replicas"]) == 2
        assert snap["counters"]["fleet_completed"] >= 2
        text = srv.metrics_prometheus()
        assert "paddle_serving_replica_state" in text
        assert "paddle_serving_replica_breaker_state" in text
        assert "paddle_serving_brownout_active" in text
        assert "paddle_serving_fleet_in_flight" in text


def test_http_front_retry_after_and_retriable_body(gpt):
    """429 responses carry Retry-After and every error body says
    whether the client may retry — the external mirror of the
    in-process Router's backoff contract."""
    import urllib.error
    import urllib.request

    srv = serving.Server(gpt, max_slots=1, block_size=8, queue_cap=1,
                         num_blocks=2).start()
    try:
        try:
            httpd = serving.http_front(srv, port=0)
        except OSError as e:
            pytest.skip(f"cannot bind loopback: {e}")
        port = httpd.server_address[1]
        # block demand beyond the whole pool -> CapacityExhausted 429
        body = json.dumps({"prompt": list(range(1, 6)),
                           "max_new_tokens": 40}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert ei.value.code == 429
        assert float(ei.value.headers["Retry-After"]) > 0
        err = json.loads(ei.value.read())
        assert err["retriable"] is True
        assert err["type"] == "CapacityExhaustedError"
        # client errors are non-retriable
        bad = json.dumps({"prompt": []}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate", data=bad,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert ei.value.code == 400
        assert json.loads(ei.value.read())["retriable"] is False
        httpd.shutdown()
    finally:
        srv.shutdown(drain=True)


def test_bench_serving_chaos_smoke():
    """--chaos dry run emits the BENCH_SERVING_CHAOS record with full
    goodput under the scripted schedule."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench_serving.py"), "--chaos",
         "--steps", "4", "--clients", "3", "--max-new", "3",
         "--prompt-len", "5", "--hidden", "16", "--layers", "1",
         "--heads", "2", "--vocab", "31", "--max-seq-len", "48",
         "--max-slots", "4", "--block-size", "8"],
        capture_output=True, text=True, timeout=420,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["bench"] == "BENCH_SERVING_CHAOS"
    assert final["goodput"] == 1.0       # retries/replays absorb it all
    assert final["counters"]["fleet_submitted"] == \
        final["counters"]["fleet_completed"]
    assert "p99_delta_ms" in final and "restarts" in final
