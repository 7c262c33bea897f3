"""The sliding-window + full attention decoder with grouped KV heads
and routed experts (`nlp/transformers/window_moe.py`) against its plain
float32 reference (`nlp/reference/window_moe.py`), at toy sizes on the
CPU: the eager forward, chunked prefill and decode through
`serving.SlotEngine` over a cache layout of TWO block groups (blocks
freed behind the window, prefix hits over both groups, copy-on-write
in both, the allocators' balance), the grouped-head and windowed
key-tile loop, the experts' softmax rule, and the refusals by name."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, serving
from paddle_tpu.engine import state_values
from paddle_tpu.framework import faults
from paddle_tpu.nlp.reference import window_moe as ref
from paddle_tpu.nlp.transformers import (
    HeldExperts, WindowMoEConfig, WindowMoEForCausalLM,
)
from paddle_tpu.nlp.transformers.gpt import _attend_tiles, key_tiling
from paddle_tpu.serving.paging import (
    BlockAllocator, BlockGroup, CacheLayout, PrefixCache, WindowTables,
)

ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 64, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
WINDOW, CHUNK, BS = 32, 16, 8
SIZES = dict(vocab_size=512, hidden_size=64, num_layers=8, num_heads=8,
             num_kv_heads=2, head_dim=16, sliding_window=WINDOW,
             moe_intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, rope_parameters=ROPE, max_seq_len=1024)
# a prompt longer than 2 x window + chunk: blocks ARE freed behind it
PROMPT = 2 * WINDOW + CHUNK + 41


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = WindowMoEForCausalLM(WindowMoEConfig(**SIZES))
    m.eval()
    return m


@pytest.fixture(scope="module")
def values(model):
    return dict(state_values(model))


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(1, 512, (400,)).astype(np.int32)


def _engine(model, **kw):
    kw.setdefault("num_blocks", {"full": 129, "window": 41})
    eng = serving.SlotEngine(model, max_slots=4, max_seq_len=512,
                             block_size=BS, prefill_chunk=CHUNK,
                             cache_dtype=jnp.float32, **kw)
    eng.warmup()
    return eng


def _stepped(eng, prompts, steps=6):
    """Requests through an idle engine, stepped by hand: each one's
    logits after its last prefill step and after each decode step, and
    its tokens."""
    futs = [eng.submit(p, max_new_tokens=steps + 1) for p in prompts]
    eng._admit()
    owner = {id(s.req): i for s in eng._slots if s is not None
             for i, f in enumerate(futs) if f is s.req}
    logits, seen = [[] for _ in prompts], {}
    while eng.active:
        eng._step()
        for s in eng._slots:
            if s is not None and s.state == "decode" \
                    and s.next_logits is not None \
                    and seen.get(id(s.req)) is not s.next_logits:
                seen[id(s.req)] = s.next_logits
                logits[owner[id(s.req)]].append(
                    np.asarray(s.next_logits, np.float32).copy())
    return [np.stack(rows) for rows in logits], \
        [np.asarray(f.result(10)) for f in futs]


def _reference(model, values, tokens, first):
    out = np.asarray(ref.forward(values, vars(model.config), tokens))
    return out[first:]


def _close(got, want):
    """Within 1e-4 of the logit spread."""
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-4 * want.std(), \
        (np.abs(got - want).max(), want.std())


def _balanced(eng):
    """Every block of both groups is free or held by the index alone."""
    if eng._cache is not None:
        eng._cache.clear()
    assert eng._alloc.free_blocks == eng._alloc.usable
    assert eng._window.alloc.free_blocks == eng._window.alloc.usable
    assert eng._window.reserved == 0
    assert (eng._window.table == 0).all() and (eng._window.base == 0).all()


# -- the model against the reference ------------------------------------------


def test_eager_forward_agrees_with_the_reference(model, values, ids):
    tokens = ids[:150]
    eager = np.asarray(model(paddle.to_tensor(tokens[None]))._value)[0]
    _close(eager, _reference(model, values, tokens, 0))


def test_layer_kinds_and_the_layout_of_two_groups(model):
    cfg = model.config
    assert cfg.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention",) + ("sliding_attention",) * 3 \
        + ("full_attention",)
    layout = model.cache_layout()
    full, window = layout.groups
    assert (full.name, full.layers, full.window) == ("full", (3, 7), None)
    assert (window.name, window.layers, window.window) \
        == ("window", (0, 1, 2, 4, 5, 6), WINDOW)
    assert full.arrays == window.arrays == (("k", (32,)), ("v", (32,)))
    assert layout.layers == 8
    assert layout.bytes_per_token(4) == 8 * 2 * 32 * 4
    assert [layout.group_of(i).name for i in range(8)] \
        == ["window"] * 3 + ["full"] + ["window"] * 3 + ["full"]
    assert model.serving_gauges() == {"experts_held": 8.0}


def test_a_window_of_the_whole_sequence_is_a_full_layer(values, ids):
    """A sliding layer whose window no sequence outgrows admits what a
    full layer admits (the rotary group aside): the mask is the only
    difference between the kinds."""
    cfg = dict(vars(WindowMoEConfig(**SIZES)))
    cfg["rope_parameters"] = {k: ROPE["sliding_attention"] for k in ROPE}
    wide = dict(cfg, sliding_window=10_000)
    as_full = dict(cfg, layer_types=("full_attention",) * 8)
    tokens = ids[:90]
    a = np.asarray(ref.forward(values, wide, tokens))
    b = np.asarray(ref.forward(values, as_full, tokens))
    np.testing.assert_allclose(a, b, atol=1e-5)
    # and a window the sequence does outgrow changes the answer
    c = np.asarray(ref.forward(values, cfg, tokens))
    assert np.abs(c[-1] - b[-1]).max() > 1e-3
    np.testing.assert_allclose(c[:WINDOW], b[:WINDOW], atol=1e-5)


def test_held_experts_softmax_rule_against_the_references_loop(model,
                                                               values):
    layer = model.model.layers[0].mlp
    assert isinstance(layer, HeldExperts) and layer.scoring == "softmax"
    assert not hasattr(layer, "router_bias") and layer.shared is None
    h = jnp.asarray(np.random.RandomState(5).randn(37, 64), jnp.float32)
    y, rows = layer(h)
    cfg = vars(model.config)
    with jax.default_matmul_precision("highest"):
        sel, w = ref.route(h, values["model.layers.0.mlp.router.weight"],
                           cfg=cfg)
        want = sum(ref.expert_term(
            h, sel, w, values["model.layers.0.mlp.gate_up"],
            values["model.layers.0.mlp.down"], e) for e in range(8))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    assert np.asarray(rows).sum() == 37 * 2
    np.testing.assert_array_equal(
        np.asarray(rows), np.bincount(np.asarray(sel).ravel(), minlength=8))


def test_an_unknown_scoring_rule_is_refused():
    cfg = WindowMoEConfig(**SIZES)
    cfg.router_scoring = "sparsemax"
    with pytest.raises(ValueError, match="sparsemax"):
        HeldExperts(cfg)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        WindowMoEConfig(**dict(SIZES, norm_topk_prob=False))
    with pytest.raises(ValueError, match="KV heads"):
        WindowMoEConfig(**dict(SIZES, num_kv_heads=3))


# -- rotary: the public "yarn" group -------------------------------------------


def test_rotary_reads_the_public_yarn_group_as_the_reference_does():
    group = dict(ROPE["full_attention"],
                 original_max_position_embeddings=8192)
    rot = nn.RotaryEmbedding(128, group["rope_theta"], group)
    inv_freq, factor = ref.rotary_group(128, group)
    np.testing.assert_allclose(rot.inv_freq, np.asarray(inv_freq),
                               rtol=1e-6)
    assert abs(rot.cos_sin_scale - factor) < 1e-12
    # the stated factor is the computed one
    assert abs(group["attention_factor"]
               - (0.1 * np.log(16.0) + 1.0)) < 1e-6
    unstated = {k: v for k, v in group.items() if k != "attention_factor"}
    assert abs(nn.RotaryEmbedding(128, 500000, unstated).cos_sin_scale
               - group["attention_factor"]) < 1e-6
    assert rot.attention_scale == 1.0       # the softmax scale unchanged
    # a stated factor that differs is used
    assert nn.RotaryEmbedding(
        128, 500000, dict(group, attention_factor=1.5)).cos_sin_scale == 1.5
    # the interpolated and the kept ends of the blend
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(rot.inv_freq[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(rot.inv_freq[-1], plain[-1] / 16, rtol=1e-6)
    x = jnp.asarray(np.random.RandomState(1).randn(5, 3, 128), jnp.float32)
    pos = jnp.arange(5) * 7
    np.testing.assert_allclose(
        np.asarray(rot(x, pos)),
        np.asarray(ref.rotate(x, pos, inv_freq, factor)), atol=1e-5)


def test_rotary_default_group_is_plain_and_deepseek_yarn_reads_as_before():
    plain = nn.RotaryEmbedding(128, 500000, ROPE["sliding_attention"])
    np.testing.assert_allclose(
        plain.inv_freq, 500000.0 ** (-np.arange(0, 128, 2) / 128),
        rtol=1e-6)
    assert plain.cos_sin_scale == 1.0 and plain.attention_scale == 1.0
    # sarvam-105b's group: mscale = mscale_all_dim = 1, so cos and sin
    # carry 1 and the softmax scale carries mscale squared
    sarvam = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
              "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
              "type": "deepseek_yarn"}
    rot = nn.RotaryEmbedding(64, 10000, sarvam)
    m = 0.1 * np.log(40.0) + 1.0
    assert rot.cos_sin_scale == pytest.approx(1.0)
    assert rot.attention_scale == pytest.approx(m * m)


# -- the key-tile loop: grouped heads, a window, a constant length -------------


def _dense(q, k, v, t_idx, window):
    """Every query head against its KV head repeated, a dense mask."""
    g = q.shape[1] // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    sc = np.einsum("bhqd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    j = np.arange(k.shape[1])[None, None, :]
    admit = j <= t_idx[:, :, None]
    if window:
        admit &= j > t_idx[:, :, None] - window
    sc = np.where(admit[:, None], sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bhqd", p, v)


@pytest.mark.parametrize("flat", [False, True],
                         ids=["heads-axis", "heads-side-by-side"])
@pytest.mark.parametrize("nh, nkv", [(8, 2), (4, 4), (6, 1)])
def test_grouped_heads_equal_the_repeated_kv_form(nh, nkv, flat):
    rng = np.random.RandomState(nh)
    b, s, hd, bs, mb = 2, 3, 8, 4, 6
    keys = rng.randn(b, mb * bs, nkv, hd).astype(np.float32)
    vals = rng.randn(b, mb * bs, nkv, hd).astype(np.float32)
    tables = 1 + np.arange(b * mb).reshape(b, mb).astype(np.int32)
    shape = (1 + b * mb, bs) + ((nkv * hd,) if flat else (nkv, hd))
    k_pool, v_pool = np.zeros(shape, np.float32), np.zeros(shape, np.float32)
    for r in range(b):
        k_pool[tables[r]] = keys[r].reshape((mb, bs) + shape[2:])
        v_pool[tables[r]] = vals[r].reshape((mb, bs) + shape[2:])
    q = rng.randn(b, nh, s, hd).astype(np.float32)
    t_idx = np.asarray([[5, 6, 7], [17, 18, 19]], np.int32)
    out, turns = _attend_tiles(q, k_pool, v_pool, tables, t_idx, 2)
    np.testing.assert_allclose(np.asarray(out),
                               _dense(q, keys, vals, t_idx, None),
                               atol=1e-5)
    assert int(turns) == 19 // 8 + 1


@pytest.mark.parametrize("depths", [(3, 40), (100, 250), (0, 399)])
def test_a_window_reads_its_short_table_whole_whatever_the_depth(depths):
    """Rows at any depths: the loop over the window group's table runs
    the layout's constant number of turns, and admits the window's
    keys alone."""
    rng = np.random.RandomState(7)
    b, nh, nkv, hd, bs, window, s = 2, 4, 2, 8, 4, 16, 3
    total = 404
    keys = rng.randn(b, total, nkv, hd).astype(np.float32)
    vals = rng.randn(b, total, nkv, hd).astype(np.float32)
    tabs = WindowTables(BlockAllocator(64), window, bs, s, b)
    k_pool = np.zeros((64, bs, nkv * hd), np.float32)
    v_pool = np.zeros_like(k_pool)
    t_idx = np.asarray([[d, d + 1, d + 2] for d in depths], np.int32)
    for r, d in enumerate(depths):
        held = {}
        for k in range(tabs.first_block(d), (d + s - 1) // bs + 1):
            held[k] = bid = tabs.alloc.alloc()
            k_pool[bid] = keys[r, k * bs:(k + 1) * bs].reshape(bs, -1)
            v_pool[bid] = vals[r, k * bs:(k + 1) * bs].reshape(bs, -1)
        tabs.sync(r, held)
    assert tabs.entries == -(-(window + s) // bs) + 1 == 6
    per_tile, whole = key_tiling(tabs.entries, bs)
    q = rng.randn(b, nh, s, hd).astype(np.float32)
    out, turns = _attend_tiles(q, k_pool, v_pool, tabs.table, t_idx,
                               per_tile, base=tabs.base, window=window)
    assert int(turns) == whole
    np.testing.assert_allclose(np.asarray(out),
                               _dense(q, keys, vals, t_idx, window),
                               atol=1e-5)


# -- the host's bookkeeping ----------------------------------------------------


def test_cache_layout_groups_are_validated():
    arrays = (("k", (8,)),)
    one = CacheLayout("tc", arrays, 3, head_axis=None)
    assert [g.name for g in one.groups] == ["blocks"]
    assert one.groups[0].layers == (0, 1, 2) and one.layers == 3
    assert one.pool_shapes(5, 4) == [(5, 4, 8)]
    with pytest.raises(ValueError, match="at most one windowed"):
        CacheLayout("tc", groups=(BlockGroup("a", [0], arrays, window=4),))
    with pytest.raises(ValueError, match="at most one windowed"):
        CacheLayout("tc", groups=(BlockGroup("a", [0], arrays),
                                  BlockGroup("b", [1], arrays)))
    with pytest.raises(ValueError, match="once each"):
        CacheLayout("tc", groups=(BlockGroup("a", [0, 1], arrays),
                                  BlockGroup("b", [1], arrays, window=4)))


def test_window_tables_move_with_the_position():
    tabs = WindowTables(BlockAllocator(32), 32, 8, 16, 2)
    assert tabs.entries == 7
    assert [tabs.first_block(p) for p in (0, 31, 32, 39, 40, 100)] \
        == [0, 0, 0, 1, 1, 8]
    assert tabs.demand(20) == 3 and tabs.demand(10_000) == 7
    tabs.sync(1, {8: 21, 9: 22, 11: 24})
    assert tabs.base[1] == 64
    assert list(tabs.table[1]) == [21, 22, 0, 24, 0, 0, 0]
    with pytest.raises(AssertionError, match="more than the table"):
        tabs.sync(0, {1: 5, 8: 6})
    tabs.clear(1)
    assert tabs.base[1] == 0 and not tabs.table[1].any()


def _indexed(n_tokens, window_from, bs=4, window=8):
    """A prefix cache that indexed `n_tokens` of one sequence, with
    window-group blocks from block `window_from` on."""
    full, wgroup = BlockAllocator(64), BlockAllocator(64)
    tabs = WindowTables(wgroup, window, bs, 4, 2)
    cache = PrefixCache(full, bs, window=tabs)
    tokens = np.arange(1, n_tokens + 1, dtype=np.int32)
    blocks = [full.alloc() for _ in range(n_tokens // bs)]
    held = {k: wgroup.alloc() for k in range(window_from, n_tokens // bs)}
    cache.insert(tokens, blocks, n_tokens, window=held)
    for bid in blocks:
        full.decref(bid)
    for wbid in held.values():
        wgroup.decref(wbid)
    return cache, tokens, blocks, held


def test_match_window_is_cut_where_the_window_group_cannot_serve():
    cache, tokens, blocks, held = _indexed(40, window_from=5)
    # the full chain matches ten blocks; a query resuming at depth d
    # reads window blocks [first_block(4d), d): present from 5 on, so
    # the deepest usable depth is the whole chain (blocks 8 and 9)
    ask = np.concatenate([tokens, [999, 998]])
    got = cache.match_window(ask, ask.size - 1)
    assert got[0] == blocks and got[1] == 40 and got[4] == 40
    assert got[2] is None and got[3] == {k: held[k] for k in (8, 9)}
    # a shorter ask of the same prefix: depth 6 needs blocks 4 and 5,
    # and 4 is not there: nothing deeper than what needs no window
    # block at all is usable
    short = np.concatenate([tokens[:24], [777]])
    blocks6, n, cow, took, matched = cache.match_window(short, short.size - 1)
    assert (n, matched, took, cow) == (0, 24, {}, None)
    assert blocks6 == []


def test_match_window_copy_on_write_needs_both_groups():
    cache, tokens, blocks, held = _indexed(40, window_from=0)
    ask = np.concatenate([tokens[:34], [555, 556, 557]])
    got_blocks, n, cow, took, matched = cache.match_window(ask, ask.size - 1)
    assert (n, matched) == (32, 32) and got_blocks == blocks[:8]
    assert cow == (blocks[8], 2, held[8])
    assert took == {k: held[k] for k in (6, 7)}
    # without the window group's block under the diverging one: no copy
    cache.window.alloc.incref(held[8])      # keep it from the free list
    cache.window.alloc.decref(cache._wblocks.pop(
        cache._digest(tokens[:36])))
    assert cache.match_window(ask, ask.size - 1)[2] is None


def test_reclaim_window_frees_the_coldest_and_keeps_the_chain():
    cache, tokens, blocks, held = _indexed(40, window_from=0)
    alloc = cache.window.alloc
    assert cache.window_blocks == 10 and alloc.blocks_in_use == 10
    ask = np.concatenate([tokens, [999]])
    cache.match_window(ask, 40)              # touches blocks 8 and 9
    alloc.incref(held[0])                    # a live slot holds block 0
    assert cache.reclaim_window(3) == 3
    assert sorted(cache._wblocks.values()) \
        == sorted(held[k] for k in (0, 4, 5, 6, 7, 8, 9))
    assert len(cache) == 10                  # the full chain stays
    assert cache.match_window(ask, 40)[1] == 40
    # an entry goes with the window-group block recorded on it
    cache.clear()
    assert alloc.blocks_in_use == 1 and cache.window_blocks == 0


def test_incremental_keys_are_the_digests_of_the_whole_prefix():
    cache = PrefixCache(BlockAllocator(8), 4)
    tokens = np.arange(50, 70, dtype=np.int32)
    chain = cache.chain()
    cache._extend(chain, tokens, 2)
    cache._extend(chain, tokens, 5)
    assert chain.keys == [cache._digest(tokens[:4 * (k + 1)])
                          for k in range(5)]


# -- through the engine --------------------------------------------------------


def test_chunked_prefill_and_decode_free_blocks_behind_the_window(
        model, values, ids):
    eng = _engine(model)
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    prompt = ids[:PROMPT]
    (got,), (went,) = _stepped(eng, [prompt], steps=10)
    _close(got, _reference(model, values, went[:-1], PROMPT - 1))
    m = eng.metrics
    assert m.get("window_blocks_freed") \
        == eng._window.first_block(went.size - 2)
    assert m.get("window_blocks_freed") >= 10
    # the window layers' turns a step are the layout's constant
    steps = m.get("steps") - 1          # less the warm-up's
    per_tile, whole = key_tiling(eng._window.entries, BS)
    assert m.get("attn_key_tiles_window") == 6 * whole * (steps + 1)
    assert m.get("attn_key_tiles_max") \
        == 8 * key_tiling(eng.blocks_per_slot, BS)[1] * (steps + 1)
    assert 0 < m.get("attn_key_tiles_full") < m.get("attn_key_tiles_max")
    assert np.asarray(eng.aux_totals["expert_rows"]).shape == (8, 8)
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    assert m.get("pool_inplace_steps") == m.get("steps")
    # every block the request dropped lives on in the index
    assert eng._cache.window_blocks == (went.size - 1) // BS
    assert eng._window.alloc.blocks_in_use == eng._cache.window_blocks
    _balanced(eng)


def test_a_second_ask_hits_both_groups_and_agrees(model, values, ids):
    eng = _engine(model)
    prompt = ids[:PROMPT + 2]
    (first,), _ = _stepped(eng, [prompt])
    before = eng.metrics.get("steps")
    (again,), (went,) = _stepped(eng, [prompt])
    m = eng.metrics
    assert m.get("prefix_hit_tokens") == PROMPT + 1     # blocks + a copy
    assert m.get("cow_splits") == 1
    assert m.get("prefix_tokens_lost_to_window") == 0
    assert m.get("steps") - before == 1 + 6             # one prefill step
    np.testing.assert_allclose(again, first, atol=2e-6)
    _close(again, _reference(model, values, went[:-1], PROMPT + 1))
    # a longer ask over the same prefix, diverging inside a block
    tail = np.concatenate([ids[:PROMPT - 3], ids[300:340]])
    (got,), (went,) = _stepped(eng, [tail])
    assert m.get("cow_splits") == 2
    _close(got, _reference(model, values, went[:-1], tail.size - 1))
    _balanced(eng)


def test_a_prefix_whose_window_blocks_are_gone_hits_shallower(
        model, values, ids):
    eng = _engine(model)
    short, long_ = ids[:48], ids[:PROMPT]
    _stepped(eng, [short], steps=2)
    _stepped(eng, [long_], steps=2)
    cache = eng._cache
    # the coldest window blocks are the long request's deep ones once
    # the short prefix has been touched again
    cache.match_window(np.concatenate([short, [7]]), 48)
    freed = cache.reclaim_window(cache.window_blocks - 6)
    assert freed > 0 and cache.window_blocks == 6
    hits = eng.metrics.get("prefix_hit_tokens")
    (got,), (went,) = _stepped(eng, [long_])
    m = eng.metrics
    assert m.get("prefix_hit_tokens") - hits == 48     # the short one's
    assert m.get("prefix_tokens_lost_to_window") == PROMPT // BS * BS - 48
    _close(got, _reference(model, values, went[:-1], PROMPT - 1))
    _balanced(eng)


def test_short_and_long_rows_in_one_step_agree_with_each_alone(
        model, values, ids):
    eng = _engine(model)
    prompts = [ids[:PROMPT], ids[200:219], ids[100:171]]
    together, went = _stepped(eng, prompts)
    steps = eng.metrics.get("steps")
    whole = key_tiling(eng._window.entries, BS)[1]
    assert eng.metrics.get("attn_key_tiles_window") == 6 * whole * steps
    _balanced(eng)
    for prompt, got, tokens in zip(prompts, together, went):
        _close(got, _reference(model, values, tokens[:-1], prompt.size - 1))
        (alone,), _ = _stepped(_engine(model, prefix_cache=False), [prompt])
        np.testing.assert_allclose(got, alone, atol=2e-6)


def test_without_a_prefix_cache_blocks_go_back_to_the_free_list(
        model, values, ids):
    eng = _engine(model, prefix_cache=False)
    (got,), (went,) = _stepped(eng, [ids[:PROMPT]], steps=3)
    _close(got, _reference(model, values, went[:-1], PROMPT - 1))
    assert eng.metrics.get("window_blocks_freed") > 0
    _balanced(eng)


@pytest.mark.parametrize("how", ["evict", "abort", "failure", "alloc_fault",
                                 "cow_fault"])
def test_every_block_of_both_groups_returns(model, ids, how):
    eng = _engine(model)
    _stepped(eng, [ids[:PROMPT]], steps=2)
    futs = [eng.submit(ids[:PROMPT - 5], max_new_tokens=6),
            eng.submit(ids[120:250], max_new_tokens=6)]
    if how == "cow_fault":
        with faults.inject("serving.cow_split@1:raise"):
            eng._admit()
        with pytest.raises(Exception):
            futs[0].result(1)
    else:
        eng._admit()
    assert eng._window.reserved > 0
    for _ in range(3):
        eng._step()
    if how == "abort":
        for f in futs:
            f.cancel()
        eng._step()
    elif how == "failure":
        with faults.inject("serving.step@1:raise"):
            eng._step()
    elif how == "alloc_fault":
        with faults.inject("serving.alloc_block@1:raise"), \
                pytest.raises(Exception):
            for _ in range(40):
                eng._step()
        eng._survive(RuntimeError("step failed"))
    while eng.active:
        eng._step()
    assert eng.active == 0
    _balanced(eng)


def test_a_request_the_window_pool_cannot_hold_is_refused_or_waits(
        model, ids):
    eng = _engine(model, num_blocks={"full": 129, "window": 12})
    # seven blocks at once a long request: one fits, a second waits
    a = eng.submit(ids[:200], max_new_tokens=4)
    b = eng.submit(ids[100:300], max_new_tokens=4)
    eng._admit()
    assert eng.active == 1 and eng.queue.depth == 1
    while not a.done():
        eng._step()
    eng._admit()
    assert eng.active == 1
    while eng.active:
        eng._step()
    assert b.result(1).size == 204
    _balanced(eng)
    tiny = _engine(model, num_blocks={"full": 129, "window": 5})
    with pytest.raises(serving.CapacityExhaustedError, match="window group"):
        tiny.submit(ids[:200], max_new_tokens=4)


def test_the_gauges_and_the_pools_of_two_groups(model):
    eng = _engine(model)
    snap = eng.metrics.snapshot()
    gauges = snap["gauges"] if "gauges" in snap else eng.metrics._gauges
    assert gauges["kv_bytes_per_token"] == 8 * 2 * 32 * 4
    assert gauges["kv_bytes_per_token_full"] == 2 * 2 * 32 * 4
    assert gauges["kv_bytes_per_token_window"] == 6 * 2 * 32 * 4
    assert gauges["window_tokens"] == WINDOW
    assert gauges["experts_held"] == 8
    shapes = [tuple(a.shape for a in layer) for layer in eng._pools]
    assert shapes[3] == shapes[7] == ((129, BS, 32),) * 2
    assert shapes[0] == shapes[6] == ((41, BS, 32),) * 2
    assert eng.kv_pool_bytes == (129 * 2 + 41 * 6) * BS * 2 * 32 * 4
    assert eng._batch_width == CHUNK + 2 + eng.blocks_per_slot + 7 + 1
    with pytest.raises(ValueError, match="num_blocks names"):
        serving.SlotEngine(model, max_slots=2, max_seq_len=64,
                           num_blocks={"sliding": 9})


# -- the refusals, by name -----------------------------------------------------


def test_what_knows_one_kind_of_block_refuses_two_groups(model, ids,
                                                         tmp_path):
    eng = _engine(model)
    for call in (lambda: eng.export_prefix_blocks(ids[:40]),
                 lambda: eng.adopt_prefix_blocks({})):
        with pytest.raises(ValueError, match=r"'window', 32"):
            call()
    with pytest.raises(ValueError, match="migrate|export_prefix_blocks"):
        serving.migrate_prefix(eng, eng, ids[:40])
    with pytest.raises(ValueError, match="speculation"):
        serving.SlotEngine(model, max_slots=2, max_seq_len=128,
                           block_size=BS, prefill_chunk=CHUNK, spec_len=2)
    with pytest.raises(ValueError, match="the KV spill tier"):
        serving.SlotEngine(model, max_slots=2, max_seq_len=128,
                           block_size=BS, prefill_chunk=CHUNK,
                           spill_dir=str(tmp_path))


def test_a_one_group_layout_takes_none_of_it():
    from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining

    paddle.seed(1)
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=64, use_parallel=False))
    eng = serving.SlotEngine(gpt, max_slots=2, max_seq_len=64, block_size=8,
                             prefill_chunk=8)
    assert eng._window is None
    assert eng._batch_width == 8 + 2 + eng.blocks_per_slot
    assert set(eng._batch_cols) == {"tok", "pos", "nvalid", "tables"}
    assert "window_tokens" not in eng.metrics._gauges
    eng._refuse_block_groups("anything")       # nothing to refuse


def test_blocks_freed_deep_inside_a_prompt_are_the_coldest():
    full, wgroup = BlockAllocator(32), BlockAllocator(32)
    cache = PrefixCache(full, 4, window=WindowTables(wgroup, 8, 4, 4, 2))
    tokens = np.arange(1, 41, dtype=np.int32)
    blocks = [full.alloc() for _ in range(10)]
    held = {k: wgroup.alloc() for k in range(10)}
    chain = cache.chain()
    # a request whose prompt ends at 32 drops blocks 0-3 while it
    # prefills (deep inside the prompt), then 4-9 near and past its end
    cache.insert(tokens, blocks, 16, chain=chain, cold_below=6,
                 window={k: held[k] for k in range(4)})
    cache.insert(tokens, blocks, 40, chain=chain, cold_below=6,
                 window={k: held[k] for k in range(4, 10)})
    order = [cache._wblocks[key] for key in cache._wblocks]
    assert order == [held[k] for k in (5, 4, 3, 2, 1, 0, 6, 7, 8, 9)]
    for wbid in held.values():
        wgroup.decref(wbid)
    assert cache.reclaim_window(6) == 6
    assert list(cache._wblocks.values()) == [held[k] for k in (6, 7, 8, 9)]
    # a resume at the prompt's end still finds its window
    ask = np.concatenate([tokens[:32], [99, 98]])
    assert cache.match_window(ask, ask.size - 1)[1] == 32


@pytest.mark.parametrize("mp, sharded", [(2, True), (4, False)])
def test_a_groups_pools_shard_over_mp_where_mp_divides_its_heads(
        model, mp, sharded):
    from paddle_tpu.serving.sharding import ShardingPlan, resolve_mesh

    plan = ShardingPlan(resolve_mesh(f"dp1.mp{mp}"))
    for group in model.cache_layout().groups:
        assert group.heads == 2 and group.head_axis == 2
        got = plan.pool_sharding(group, (41, BS, 32))
        assert got.is_fully_replicated != sharded, group.name
    # a layout of one group, handed whole as before, reads as its group
    gpt = CacheLayout("thd", (("k", (4, 8)), ("v", (4, 8))), 2, head_axis=2)
    assert not plan.pool_sharding(gpt, (9, 8, 4, 8)).is_fully_replicated
    assert plan.pool_sharding(
        CacheLayout("tc", (("latent", (128,)),), 2),
        (9, 8, 128)).is_fully_replicated


def test_a_meshed_engine_serves_two_groups(model, values, ids):
    eng = serving.SlotEngine(model, max_slots=2, max_seq_len=256,
                             block_size=BS, prefill_chunk=CHUNK,
                             num_blocks={"full": 65, "window": 21},
                             cache_dtype=jnp.float32, mesh="dp1.mp2")
    eng.warmup()
    assert eng.mesh_info()["kv_sharded"]
    (got,), (went,) = _stepped(eng, [ids[:PROMPT]], steps=3)
    want = _reference(model, values, went[:-1], PROMPT - 1)
    assert np.abs(got - want).max() <= 1e-3 * want.std()
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    _balanced(eng)
