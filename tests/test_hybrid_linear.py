"""The hybrid linear-attention family (`nlp/transformers/hybrid_linear.py`)
against its float32 reference (`nlp/reference/hybrid_linear.py`), and
the cache manager's second kind of array: per-slot state beside the
paged K/V pool, reset at admission, snapshotted at block boundaries,
recorded in the prefix cache and restored on a hit.

Every comparison is on logits or states, float32 on the CPU: the two
forms of the delta rule and the engine's path differ from the
reference by the order of float32 additions alone, so the tolerance is
1e-5 of values of order 0.1-1 (readings are 1e-7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observe, serving
from paddle_tpu.engine import state_values
from paddle_tpu.nlp.reference import hybrid_linear as reference
from paddle_tpu.nlp.transformers import (
    GPTConfig, GPTForPretraining, HybridLinearConfig,
    HybridLinearForCausalLM, LatentMoEConfig, LatentMoEForCausalLM,
)
from paddle_tpu.nlp.transformers.hybrid_linear import (
    filter_chunk, gated_delta_chunk, gated_delta_step,
)
from paddle_tpu.serving.paging import (
    BlockAllocator, CacheLayout, PrefixCache, SnapshotEntries,
)

TOL = 1e-5
VOCAB = 97


def _config(**over):
    sizes = dict(vocab_size=VOCAB, hidden_size=32, num_layers=4,
                 num_heads=4, intermediate_size=64, linear_num_heads=4,
                 linear_key_head_dim=8, linear_value_head_dim=16,
                 max_seq_len=512)
    sizes.update(over)
    return HybridLinearConfig(**sizes)


@pytest.fixture(scope="module")
def hybrid():
    paddle.seed(0)
    cfg = _config()
    model = HybridLinearForCausalLM(cfg)
    model.eval()
    return cfg, model


def _engine(model, **kw):
    kw = {"max_slots": 2, "max_seq_len": 256, "block_size": 8,
          "prefill_chunk": 16, "snapshot_entries": 4, **kw}
    return serving.SlotEngine(model, **kw)


def _tokens(seed, n, vocab=VOCAB):
    return np.random.RandomState(seed).randint(1, vocab, (n,)) \
        .astype(np.int32)


def _stepped(eng, prompt, new):
    """One request through an idle engine, step by step: the logits the
    step handed to sampling from the last prompt position on, and the
    answer."""
    fut = eng.submit(np.asarray(prompt, np.int32), max_new_tokens=new,
                     timeout=None)
    eng._admit()
    rows, seen = [], None
    while eng.active:
        eng._step()
        for s in eng._slots:
            if s is not None and s.state == "decode" \
                    and s.next_logits is not None \
                    and s.next_logits is not seen:
                seen = s.next_logits
                rows.append(np.asarray(seen, np.float32).copy())
    return np.stack(rows), np.asarray(fut.result(timeout=30))


def _reference(model, cfg, tokens, **kw):
    return np.asarray(reference.forward(state_values(model), vars(cfg),
                                        tokens, **kw))


def _delta_inputs(seed, s, h=3, dk=8, dv=16):
    r = np.random.RandomState(seed)
    q = r.randn(s, h, dk).astype(np.float32)
    k = r.randn(s, h, dk).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(s, h, dv).astype(np.float32)
    alpha = r.uniform(0.7, 1.0, (s, h)).astype(np.float32)
    beta = r.uniform(0.0, 2.0, (s, h)).astype(np.float32)
    return q, k, v, alpha, beta


# -- the two forms of the recurrence -----------------------------------------


@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_chunked_form_is_the_token_by_token_reference(length):
    """Chunks of 64 columns with the state carried between them, the
    last one padded (beta = 0, g = 0), against the reference's scan."""
    q, k, v, alpha, beta = _delta_inputs(length, length)
    want_o, want_S = reference.delta_rule(q, k, v, alpha, beta)
    chunk = 64
    S = jnp.zeros((3, 8, 16), jnp.float32)
    outs = []
    for at in range(0, length, chunk):
        n = min(chunk, length - at)
        pad = chunk - n

        def cols(a, fill=0.0):
            a = np.moveaxis(a[at:at + n], 0, 1)      # [h, n, ...]
            width = [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
            return jnp.asarray(np.pad(a, width, constant_values=fill))

        o, S = gated_delta_chunk(cols(q), cols(k), cols(v),
                                 jnp.log(cols(alpha, 1.0)), cols(beta), S)
        outs.append(np.moveaxis(np.asarray(o), 1, 0)[:n])
    np.testing.assert_allclose(np.concatenate(outs), want_o, atol=TOL)
    np.testing.assert_allclose(S, want_S, atol=TOL)


def test_one_token_form_continues_a_chunked_prefix():
    q, k, v, alpha, beta = _delta_inputs(7, 40)
    want_o, want_S = reference.delta_rule(q, k, v, alpha, beta)

    def heads_first(a):
        return jnp.asarray(np.moveaxis(a, 0, 1))

    o, S = gated_delta_chunk(
        *(heads_first(a[:24]) for a in (q, k, v)),
        jnp.log(heads_first(alpha[:24])), heads_first(beta[:24]),
        jnp.zeros((3, 8, 16), jnp.float32))
    np.testing.assert_allclose(np.moveaxis(np.asarray(o), 1, 0),
                               want_o[:24], atol=TOL)
    for t in range(24, 40):
        o_t, S = gated_delta_step(q[t], k[t], v[t], np.log(alpha[t]),
                                  beta[t], S)
        np.testing.assert_allclose(o_t, want_o[t], atol=TOL)
    np.testing.assert_allclose(S, want_S, atol=TOL)


def test_rows_of_mixed_nvalid_in_one_step(hybrid):
    """One call of a linear layer's paged form over rows with 0, 1, 5
    and all 8 columns real, each from its own earlier state: every row
    is the reference over its own tokens, and the idle row's state and
    filter tail come back bit for bit."""
    cfg, model = hybrid
    mixer = model.model.layers[0].mixer
    r = np.random.RandomState(3)
    before, chunk = [5, 9, 0, 12], 8
    nvalid = np.array([0, 1, 5, 8], np.int32)
    xs = [r.randn(before[b] + chunk, cfg.hidden_size).astype(np.float32)
          for b in range(4)]
    # each row's state after its `before` tokens, through the same form
    S = np.zeros((4, 4, 8, 16), np.float32)
    tail = np.zeros((4, 3, cfg.filter_columns), np.float32)
    for b in range(4):
        if before[b]:
            n = before[b]
            x = np.zeros((1, 16, cfg.hidden_size), np.float32)
            x[0, :n] = xs[b][:n]
            _, (S_b, tail_b) = mixer.forward_paged(
                jnp.asarray(x), jnp.arange(16)[None] < n,
                jnp.array([n], jnp.int32),
                (jnp.asarray(S[b:b + 1]), jnp.asarray(tail[b:b + 1])))
            S[b], tail[b] = S_b[0], tail_b[0]
    x = np.stack([x_b[before[b]:] for b, x_b in enumerate(xs)])
    valid = np.arange(chunk)[None] < nvalid[:, None]
    out, (S_new, tail_new) = mixer.forward_paged(
        jnp.asarray(x), jnp.asarray(valid), jnp.asarray(nvalid),
        (jnp.asarray(S), jnp.asarray(tail)))
    values = state_values(model)

    def p(name):
        return values[f"model.layers.0.mixer.{name}"]

    for b in range(4):
        n = before[b] + int(nvalid[b])
        if not n:
            continue
        want, want_S = reference.linear_attention(
            jnp.asarray(xs[b][:n]), p("in_proj.weight"), p("conv_weight"),
            p("ab_proj.weight"), p("A_log"), p("dt_bias"),
            p("gate_proj.weight"), p("out_norm.weight"),
            p("o_proj.weight"), cfg=vars(cfg))
        np.testing.assert_allclose(out[b, :nvalid[b]], want[before[b]:],
                                   atol=TOL)
        np.testing.assert_allclose(S_new[b], want_S, atol=TOL)
    np.testing.assert_array_equal(S_new[0], S[0])
    np.testing.assert_array_equal(tail_new[0], tail[0])
    assert np.abs(np.asarray(S_new[1]) - S[1]).max() > 1e-3


def test_filter_padding_feeds_nothing():
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 6, 5).astype(np.float32))
    tail = jnp.asarray(r.randn(2, 3, 5).astype(np.float32))
    taps = jnp.asarray(r.randn(4, 5).astype(np.float32))
    y, new = filter_chunk(x, tail, taps, jnp.array([0, 2], jnp.int32))
    np.testing.assert_array_equal(new[0], tail[0])
    np.testing.assert_array_equal(new[1], jnp.concatenate(
        [tail[1, 2:], x[1, :2]]))
    seen = np.concatenate([tail[1], x[1]])
    np.testing.assert_allclose(
        y[1, 1], sum(taps[j] * seen[1 + j] for j in range(4)), atol=1e-6)


# -- the model and the engine against the reference ---------------------------


def test_model_forward_is_the_reference(hybrid):
    cfg, model = hybrid
    tokens = _tokens(0, 70)
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value)[0]
    want = _reference(model, cfg, tokens)
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=TOL)
    # a position off is another answer entirely
    assert np.abs(got[1:] - want[:-1]).max() > 1000 * TOL


def test_layer_types_choose_the_mixers():
    cfg = _config(num_layers=6)
    assert cfg.layer_types == ("linear_attention",) * 3 \
        + ("full_attention",) + ("linear_attention",) * 2
    cut = _config(num_layers=2, layer_types=["full_attention"] * 8)
    assert cut.layer_types == ("full_attention",) * 2
    with pytest.raises(ValueError, match="layer types"):
        _config(layer_types=["sliding_attention"] * 4)
    paddle.seed(0)
    model = HybridLinearForCausalLM(cfg)
    kinds = [type(la.mixer).__name__ for la in model.model.layers]
    assert kinds == ["GatedDeltaNet"] * 3 + ["HybridFullAttention"] \
        + ["GatedDeltaNet"] * 2
    layout = model.cache_layout()
    assert (layout.layers, layout.state_layers) == (1, 5)
    assert layout.arrays == (("k", (16, 8)), ("v", (16, 8)))   # 4 -> 16
    assert layout.state == (("S", (4, 8, 16), "float32"),
                            ("tail", (3, 4 * 32), "float32"))
    assert layout.state_bytes_per_slot() == 5 * 4 * (4 * 8 * 16 + 3 * 128)


def test_decay_parameters_follow_the_published_initialiser():
    paddle.seed(5)
    model = HybridLinearForCausalLM(_config(linear_num_heads=64,
                                            hidden_size=64, num_heads=4))
    mixer = model.model.layers[0].mixer
    A = np.exp(np.asarray(mixer.A_log._value))
    dt = np.log1p(np.exp(np.asarray(mixer.dt_bias._value)))
    assert mixer.A_log._value.dtype == mixer.dt_bias._value.dtype \
        == jnp.float32
    assert 0 < A.min() and A.max() <= 16 and A.max() > 8
    assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001


def test_prefill_through_the_engine_then_decode_is_the_reference(hybrid):
    cfg, model = hybrid
    eng = _engine(model)
    assert eng.warmup() == {"decode": 1, "cow": 1, "snapshot": 1}
    prompt = _tokens(1, 37)          # 16 + 16 + 5: three prefill steps
    got, answer = _stepped(eng, prompt, 6)
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[36:], atol=TOL)
    m = eng.metrics
    assert m.get("computed_tokens") == 37 + 5
    assert m.get("state_resets") == 1 and m.get("state_snapshot_hits") == 0
    assert m.get("pool_inplace_steps") == m.get("steps")
    assert eng.compile_counts == {"decode": 1, "cow": 1, "snapshot": 1}
    # the turns of the shared key-tile loop, counted as GPT's are
    assert int(eng.aux_totals["attn_key_tiles"]) == m.get("steps")


@pytest.mark.parametrize("snapshot", ["resumed", "reclaimed"])
def test_a_second_turn_is_the_full_forward_over_the_transcript(
        hybrid, snapshot):
    """The next turn of a session (its transcript plus new tokens)
    resumes from the snapshot the last one left at its deepest block
    boundary; with that snapshot gone the blocks still match but are no
    prefix to resume from, and the turn is computed again from zero.
    The logits are the reference's either way."""
    cfg, model = hybrid
    eng = _engine(model)
    m = eng.metrics
    _, first = _stepped(eng, _tokens(1, 37), 6)        # written: 42
    assert m.get("state_snapshots_taken") >= 1
    assert eng._snapshots.free_entries == 3             # one recorded
    if snapshot == "reclaimed":
        assert eng._cache.evict_lru_snapshot()
        assert m.get("state_snapshot_evictions") == 1
        assert eng._snapshots.free_entries == 4
    turn = np.concatenate([first, _tokens(2, 21)])
    computed = m.get("computed_tokens")
    got, answer = _stepped(eng, turn, 5)
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[turn.size - 1:], atol=TOL)
    if snapshot == "resumed":
        assert m.get("state_snapshot_hits") == 1
        assert m.get("prefix_hit_tokens") == 40
        assert m.get("prefix_tokens_lost_to_state") == 0
        assert m.get("computed_tokens") - computed == turn.size - 40 + 4
    else:
        assert m.get("state_snapshot_hits") == 0
        assert m.get("state_resets") == 2
        assert m.get("prefix_hit_tokens") == 0
        assert m.get("prefix_tokens_lost_to_state") == 40
        assert m.get("computed_tokens") - computed == turn.size + 4
    # the older snapshot on the chain went when the deeper was recorded
    assert eng._snapshots.free_entries == 3
    assert eng.free_blocks + eng.prefix_cache_size == eng._alloc.usable


def test_a_reused_slot_starts_from_zero(hybrid):
    """Two different requests, one after the other through an engine of
    ONE slot: the second's logits are the reference's over its own
    tokens alone."""
    cfg, model = hybrid
    eng = _engine(model, max_slots=1, snapshot_entries=2)
    _stepped(eng, _tokens(4, 30), 4)
    S = np.asarray(eng._state[0][0][0])
    assert np.abs(S).max() > 1e-6       # the slot's state was left as is
    got, answer = _stepped(eng, _tokens(5, 19), 3)
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[18:], atol=TOL)
    assert eng.metrics.get("state_resets") == 2
    # the row that stays zero was never written
    assert all(not np.asarray(a[-1]).any()
               for layer in eng._state for a in layer)


def test_entries_run_out_and_the_least_recent_snapshot_goes(hybrid):
    cfg, model = hybrid
    eng = _engine(model, max_slots=1, snapshot_entries=2)
    m = eng.metrics
    _stepped(eng, _tokens(10, 20), 4)
    _stepped(eng, _tokens(11, 20), 4)
    assert eng._snapshots.free_entries == 0
    assert m.get("state_snapshot_evictions") == 0
    # a third request needs a working entry: the oldest recorded goes
    _stepped(eng, _tokens(12, 20), 4)
    assert m.get("state_snapshot_evictions") == 1
    # the first session's blocks are still indexed, but no prefix now
    blocks, n, entry, matched = eng._cache.match_snapshot(
        np.concatenate([_tokens(10, 20), [1] * 20]), 39)
    assert (blocks, n, entry, matched) == ([], 0, None, 16)


def test_a_failed_step_takes_pools_and_state_and_both_come_back(hybrid):
    cfg, model = hybrid
    eng = _engine(model)
    _stepped(eng, _tokens(1, 20), 3)
    fut = eng.submit(_tokens(2, 20), max_new_tokens=3, timeout=None)
    eng._admit()
    for a in eng._arrays(eng._pools + eng._state):
        a.delete()                       # what a step that raised leaves
    eng._recover_pools(RuntimeError("lost"))
    with pytest.raises(RuntimeError, match="lost"):
        fut.result(timeout=5)
    assert eng.metrics.get("pool_rebuilds") == 1
    assert eng._snapshots.free_entries == 4 and eng.prefix_cache_size == 0
    got, answer = _stepped(eng, _tokens(3, 25), 3)
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[24:], atol=TOL)


def _by_hand(eng, prompt, **gen):
    fut = eng.submit(np.asarray(prompt, np.int32), timeout=None, **gen)
    eng._admit()
    while eng.active:
        eng._step()
    return np.asarray(fut.result(5))


def _served(eng, prompt, **gen):
    """One request through the engine's own loop (started by the
    caller), which keeps a step in flight."""
    return np.asarray(eng.submit(np.asarray(prompt, np.int32),
                                 timeout=None, **gen).result(60))


@pytest.mark.parametrize("ending", ["max_new_tokens", "eos_on_a_boundary"])
def test_snapshots_under_the_loop_are_those_of_steps_by_hand(hybrid, ending):
    """The row copies that take and restore a snapshot are enqueued
    between the same two steps whether or not the host waited for the
    first, since positions alone decide them. A turn that ends by
    `max_new_tokens` leaves under the loop the snapshot it leaves by
    hand: the next turn resumes from it, as deep, and answers and slot
    state are equal to the bit. A turn that ends by EOS has one more
    column in flight; where that column crosses a block boundary it
    snapshots a state one token deeper than the request wrote: that
    snapshot is freed, not recorded, and the next turn starts from
    zero and still answers what the by-hand engine answers."""
    cfg, model = hybrid
    prompt, more = _tokens(1, 33), _tokens(2, 21)   # 33 + 7 tokens = 40
    plain = _stepped(_engine(model, max_slots=1), prompt, 12)[1]
    gen = {"max_new_tokens": 6}
    if ending == "eos_on_a_boundary":
        assert plain[39] not in plain[33:39]
        gen = {"max_new_tokens": 12, "eos_token_id": int(plain[39])}
    hand, loop = _engine(model, max_slots=1), _engine(model, max_slots=1)
    hand.warmup(), loop.warmup()
    want = _by_hand(hand, prompt, **gen)
    wasted = int(ending == "eos_on_a_boundary")
    loop.start()
    try:
        got = _served(loop, prompt, **gen)
        np.testing.assert_array_equal(got, want)
        assert want.size == (40 if wasted else 39)
        assert hand._snapshots.free_entries == 3        # recorded at 32
        assert loop.metrics.get("columns_wasted") == wasted
        assert loop._snapshots.free_entries == 3 + wasted
        assert loop.prefix_cache_size == hand.prefix_cache_size == 4
        turn = np.concatenate([want, more])
        want_b = _by_hand(hand, turn, max_new_tokens=5)
        got_b = _served(loop, turn, max_new_tokens=5)
    finally:
        loop.shutdown(drain=True, timeout=60)
    np.testing.assert_array_equal(got_b, want_b)
    assert hand.metrics.get("state_snapshot_hits") == 1
    assert loop.metrics.get("state_snapshot_hits") == 1 - wasted
    assert loop.metrics.get("prefix_hit_tokens") == 32 * (1 - wasted)
    if not wasted:
        for mine, theirs in zip(loop._arrays(loop._state),
                                hand._arrays(hand._state)):
            np.testing.assert_array_equal(np.asarray(mine[0]),
                                          np.asarray(theirs[0]))
    assert loop.metrics.get("steps_launched_ahead") > 0
    assert loop.compile_counts == {"decode": 1, "cow": 1, "snapshot": 1}
    assert loop._decode._cache_size() == 1


# -- the prefix cache's snapshots ---------------------------------------------


def _cache(entries=4, blocks=32):
    alloc = BlockAllocator(blocks)
    snaps = SnapshotEntries(entries)
    return alloc, snaps, PrefixCache(alloc, 4, snapshots=snaps)


def test_a_match_is_never_deeper_than_a_snapshot():
    alloc, snaps, cache = _cache()
    toks = np.arange(1, 30, dtype=np.int32)
    blocks = [alloc.alloc() for _ in range(6)]
    entry = snaps.alloc()
    cache.insert(toks, blocks, 22, snapshot=(entry, 12))
    # five blocks are indexed; the state is known after three of them
    got, n, e, matched = cache.match_snapshot(toks, 28)
    assert (got, n, e, matched) == (blocks[:3], 12, entry, 20)
    # a limit shallower than the snapshot: nothing to resume from
    assert cache.match_snapshot(toks, 11) == ([], 0, None, 8)
    # another sequence with the same first block: matched, not usable
    other = np.concatenate([toks[:4], toks[:8]])
    assert cache.match_snapshot(other, 11) == ([], 0, None, 4)
    # the plain match is what a layout without state gets, untouched
    assert cache.match(toks, 28)[:2] == (blocks[:5], 20)


def test_a_deeper_snapshot_frees_the_shallower_and_eviction_frees_both():
    alloc, snaps, cache = _cache()
    toks = np.arange(1, 40, dtype=np.int32)
    blocks = [alloc.alloc() for _ in range(8)]
    first = snaps.alloc()
    cache.insert(toks[:14], blocks[:3], 13, snapshot=(first, 12))
    second = snaps.alloc()
    cache.insert(toks, blocks, 33, snapshot=(second, 32))
    assert snaps.free_entries == 3                      # `first` freed
    assert cache.match_snapshot(toks, 38)[1:3] == (32, second)
    # a snapshot that lands on no block is handed back at once
    third = snaps.alloc()
    cache.insert(toks[:3], blocks[:1], 2, snapshot=(third, 0))
    assert snaps.free_entries == 3
    dropped = []
    cache.snapshot_evicted_hook = lambda: dropped.append(1)
    for bid in blocks:
        alloc.decref(bid)               # the sequence's own references
    cache.reclaim(8)
    assert snaps.free_entries == 4 and len(dropped) == 1
    assert alloc.free_blocks == alloc.usable
    with pytest.raises(ValueError, match="not held"):
        snaps.free(second)


def test_clear_frees_every_entry():
    alloc, snaps, cache = _cache()
    for seed in range(3):
        toks = _tokens(seed, 9)
        blocks = [alloc.alloc(), alloc.alloc()]
        cache.insert(toks, blocks, 8, snapshot=(snaps.alloc(), 8))
    assert snaps.free_entries == 1
    cache.clear()
    assert snaps.free_entries == 4 and len(cache) == 0


# -- the seam: what other layouts take, and what refuses this one -------------


def test_layouts_without_state_take_the_code_they_took():
    paddle.seed(0)
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=64, dropout=0.0, attn_dropout=0.0, use_parallel=False))
    latent = LatentMoEForCausalLM(LatentMoEConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
        router_experts=4, num_experts_per_tok=2, max_seq_len=64))
    for model in (gpt, latent):
        layout = model.cache_layout()
        assert layout.state == () and layout.state_layers == 0
        assert layout.state_bytes_per_slot() == 0
        eng = serving.SlotEngine(model, max_slots=2, block_size=8,
                                 prefill_chunk=8)
        assert eng.warmup() == {"decode": 1, "cow": 1}
        assert eng._state == [] and eng._snapshots is None
        assert eng._held() is eng._pools
        _stepped(eng, _tokens(0, 19), 3)
        assert eng.compile_counts == {"decode": 1, "cow": 1}
        counters = eng.metrics.snapshot()["counters"]
        assert not any(k.startswith("state_") for k in counters)
        assert "state_bytes_per_slot" not in eng.metrics.snapshot()["model"]
    # a state that no layer holds is no state
    assert CacheLayout("thd", (("k", (2, 4)),), 1,
                       state=(("S", (2,), "float32"),)).state == ()


@pytest.mark.parametrize("path", ["speculation", "export", "adopt",
                                  "migrate", "spill"])
def test_side_paths_refuse_a_layout_with_state_arrays_by_name(
        hybrid, path, tmp_path):
    _, model = hybrid
    names = r"per-slot state arrays \['S', 'tail'\]"
    if path == "speculation":
        with pytest.raises(ValueError, match="speculation.*" + names):
            _engine(model, spec_len=2)
        return
    if path == "spill":
        with pytest.raises(ValueError, match="KV spill tier.*" + names):
            _engine(model, spill_dir=str(tmp_path))
        return
    eng = _engine(model)
    _stepped(eng, _tokens(1, 20), 3)
    if path == "export":
        with pytest.raises(ValueError,
                           match="export_prefix_blocks.*" + names):
            eng.export_prefix_blocks(_tokens(1, 20))
    elif path == "adopt":
        with pytest.raises(ValueError,
                           match="adopt_prefix_blocks.*" + names):
            eng.adopt_prefix_blocks({"block_size": 8})
    else:
        from paddle_tpu.serving.migrate import migrate_prefix

        with pytest.raises(ValueError, match=names):
            migrate_prefix(eng, _engine(model), _tokens(1, 20))


def test_a_draft_with_state_arrays_is_refused_too(hybrid):
    _, model = hybrid
    paddle.seed(0)
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=1, num_heads=4,
        max_seq_len=512, dropout=0.0, attn_dropout=0.0,
        use_parallel=False))
    with pytest.raises(ValueError, match="draft model's cache layout"):
        serving.SlotEngine(gpt, max_slots=2, block_size=8, spec_len=2,
                           draft_model=model)


def test_counters_gauges_and_spans_are_always_on(hybrid):
    _, model = hybrid
    srv = serving.Server(model, max_slots=2, max_seq_len=256, block_size=8,
                         prefill_chunk=16, snapshot_entries=4)
    eng = srv.engine
    observe.timeline.reset()
    _, first = _stepped(eng, _tokens(1, 37), 6)
    _stepped(eng, np.concatenate([first, _tokens(2, 9)]), 3)
    snap = srv.metrics.snapshot()
    for counter in ("state_snapshots_taken", "state_snapshot_hits",
                    "state_resets", "computed_tokens",
                    "attn_context_tokens", "attn_key_tiles",
                    "attn_key_tiles_max"):
        assert snap["counters"][counter] > 0, counter
    assert snap["counters"]["prefix_tokens_lost_to_state"] == 0
    assert snap["model"]["state_bytes_per_slot"] \
        == 3 * 4 * (4 * 8 * 16 + 3 * 128)
    assert snap["model"]["snapshot_entries"] == 4.0
    assert snap["model"]["kv_bytes_per_token"] == 2 * 16 * 8 * 4
    calls = observe.timeline.aggregates()["snapshot"]["calls"]
    # one reset, one restore, and the takes at block boundaries
    assert calls >= 2 + 1
    text = srv.metrics_prometheus()
    for line in ("paddle_serving_model_state_bytes_per_slot",
                 "paddle_serving_model_snapshot_entries",
                 "state_snapshots_taken", "state_snapshot_hits",
                 "state_resets"):
        assert line in text, line
    # nested in admit and commit: not counted a second time
    assert "snapshot" not in {
        k for k, v in observe.goodput().items() if isinstance(v, str)}


def test_named_scopes_reach_the_lowered_step(hybrid):
    _, model = hybrid
    eng = _engine(model)
    vec = np.zeros((2,), np.int32)
    batch, extras = eng._stage(np.zeros((2, 16), np.int32), vec, vec)
    text = eng._decode.lower(eng._values, batch, eng._held(), extras) \
        .as_text(debug_info=True)
    for scope in ("gdn.filter", "gdn.chunk", "attn.full"):
        assert scope in text, scope
    eager = jax.jit(lambda ids: model(paddle.to_tensor(ids))._value) \
        .lower(jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    assert "gdn.step" in eager


@pytest.mark.dist
def test_state_arrays_shard_over_their_heads_on_a_mesh(hybrid):
    cfg, model = hybrid
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    eng = _engine(model, mesh="dp1.mp2")
    S, tail = eng._state[0]
    assert S.sharding.spec == jax.sharding.PartitionSpec(None, "mp", None, None)
    assert tail.sharding.is_fully_replicated
    got, answer = _stepped(eng, _tokens(1, 37), 5)
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[36:], atol=TOL)
    turn = np.concatenate([answer, _tokens(2, 11)])
    got, answer = _stepped(eng, turn, 3)
    np.testing.assert_allclose(
        got, _reference(model, cfg, answer[:-1])[turn.size - 1:], atol=TOL)
    assert eng.metrics.get("state_snapshot_hits") == 1
    assert eng._state[0][0].sharding.spec \
        == jax.sharding.PartitionSpec(None, "mp", None, None)
    # no warm-up here, and a layout with state never copies on write
    assert eng.compile_counts == {"decode": 1, "snapshot": 1}
