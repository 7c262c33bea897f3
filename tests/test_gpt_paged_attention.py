"""`GPTAttention._attend_paged`: the serving step's attention, an
online-softmax loop over key tiles read through the block table that
stops behind the batch's longest live row.

Held here against two references that share no line with it: the dense
vector-`pos` branch of `_attend_cached` (one `[b, nh, max_seq, hd]`
cache a row, whole-context scores) and the uncached forward
(`F.scaled_dot_product_attention`). The tile is cut to a few positions
so that small contexts span one, two and every tile of a table.
"""

from __future__ import annotations

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observe, serving
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining
from paddle_tpu.nlp.transformers import gpt as gpt_mod
from paddle_tpu.nlp.transformers.gpt import GPTAttention, key_tiling

TILE = 16


@pytest.fixture(autouse=True)
def small_tile(monkeypatch):
    monkeypatch.setattr(gpt_mod, "KEY_TILE", TILE)


class _Layout:
    """`slots` rows over a pool of blocks of `bs` positions, `mb` table
    entries a slot. Every block of the pool, the null block too, starts
    as garbage, so a key admitted that should not be shows."""

    def __init__(self, slots, bs, mb, nh, hd, seed=0):
        self.b, self.bs, self.mb, self.nh, self.hd = slots, bs, mb, nh, hd
        self.s_max = bs * mb
        self.rng = np.random.default_rng(seed)
        nb = 1 + slots * mb
        shape = (nb, bs, nh, hd)
        self.k_pool = jnp.asarray(self.rng.normal(0, 2, shape), jnp.float32)
        self.v_pool = jnp.asarray(self.rng.normal(0, 2, shape), jnp.float32)
        self.tables = np.zeros((slots, mb), np.int32)
        self.attn = types.SimpleNamespace(head_dim=hd)

    def allocate(self, lengths):
        """Slot `i` holds blocks for `lengths[i]` positions; the rest of
        its table is the null block. Blocks are dealt out of order."""
        free = list(self.rng.permutation(np.arange(1, 1 + self.b * self.mb)))
        for i, n in enumerate(lengths):
            for j in range(-(-int(n) // self.bs)):
                if self.tables[i, j] == 0:
                    self.tables[i, j] = free.pop()

    def rows(self, s_new):
        shape = (self.b, self.nh, s_new, self.hd)
        return tuple(jnp.asarray(self.rng.normal(0, 1, shape), jnp.float32)
                     for _ in range(3))

    def dense(self):
        """Each slot's logical `[nh, s_max, hd]` cache as the pool holds
        it through the table: what the dense branch attends over."""
        def view(pool):
            got = np.asarray(pool)[self.tables]      # [b, mb, bs, nh, hd]
            got = got.reshape(self.b, self.s_max, self.nh, self.hd)
            return jnp.asarray(got.transpose(0, 2, 1, 3))
        return view(self.k_pool), view(self.v_pool)

    def step(self, pos, s_new):
        """One step at `pos` through both paths, on the same rows; the
        paged pools are kept. Returns ``(paged out, dense out, key
        tiles)``, outs ``[b, nh, s_new, hd]``."""
        pos = jnp.asarray(pos, jnp.int32)
        q, k, v = self.rows(s_new)
        k_dense, v_dense = self.dense()
        want, _ = GPTAttention._attend_cached(
            self.attn, q, k, v, (k_dense, v_dense, pos))
        got, (self.k_pool, self.v_pool, _, tiles) = \
            GPTAttention._attend_paged(
                self.attn, q, k, v, self.k_pool, self.v_pool, pos,
                jnp.asarray(self.tables))
        return np.asarray(got._value), np.asarray(want._value), int(tiles)


def _host_tiles(pos, chunk, s_max=64):
    """The loop's turns from the host's positions: the tile of the last
    column that lies inside the table."""
    return max(min(p + chunk - 1, s_max - 1) if p < s_max else 0
               for p in pos) // TILE + 1


def _assert_real_columns_agree(got, want, pos, nvalid):
    for i, (p, n) in enumerate(zip(pos, nvalid)):
        if n:
            np.testing.assert_allclose(got[i, :, :n], want[i, :, :n],
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"slot {i} at {p}")


# positions a slot, valid columns a slot, chunk; table 8 blocks of 8 =
# 64 positions = 4 tiles of 16
CASES = {
    "one_tile": ([3, 9], [4, 4], 4),
    "two_tiles": ([14, 20], [4, 4], 4),
    "every_tile": ([60, 47], [4, 4], 4),
    "unequal_rows": ([1, 58, 17, 33], [4, 4, 1, 2], 4),
    "idle_slots": ([0, 37, 0, 5], [0, 1, 0, 4], 4),
    "only_idle_slots": ([0, 0], [0, 0], 4),
    "last_position_of_the_table": ([60, 2], [4, 4], 4),
    "padding_columns_past_the_table": ([62, 63, 30], [2, 1, 1], 4),
    "decode_beside_prefill": ([40, 0, 22], [1, 8, 8], 8),
    "a_tile_boundary_inside_the_chunk": ([13, 29], [8, 8], 8),
}


@pytest.mark.parametrize("name", list(CASES))
def test_paged_loop_matches_the_dense_branch(name):
    pos, nvalid, chunk = CASES[name]
    lay = _Layout(len(pos), bs=8, mb=8, nh=4, hd=8)
    lay.allocate([min(p + n, lay.s_max) for p, n in zip(pos, nvalid)])
    got, want, tiles = lay.step(pos, chunk)
    _assert_real_columns_agree(got, want, pos, nvalid)
    assert np.isfinite(got).all()
    assert tiles == _host_tiles(pos, chunk)


@pytest.mark.parametrize("nh,bs", [(3, 8), (4, 4), (2, 16), (5, 2)])
def test_heads_and_block_size_are_independent(nh, bs):
    """No axis of the gathered tile stands in for another: heads of
    any count against blocks of any size (a tile is `TILE // bs`
    blocks; the last one narrower than a block is one block)."""
    lay = _Layout(3, bs=bs, mb=64 // bs, nh=nh, hd=8, seed=nh)
    pos, nvalid = [50, 7, 23], [4, 4, 2]
    lay.allocate([p + n for p, n in zip(pos, nvalid)])
    got, want, tiles = lay.step(pos, 4)
    _assert_real_columns_agree(got, want, pos, nvalid)
    assert tiles == 53 // TILE + 1


def test_a_table_that_is_no_whole_number_of_tiles():
    """5 blocks of 8 under a tile of 2 blocks: the third tile's second
    entry is padding, read as the null block and masked."""
    lay = _Layout(2, bs=8, mb=5, nh=2, hd=8)
    assert key_tiling(5, 8) == (2, 3)
    pos, nvalid = [36, 10], [4, 4]
    lay.allocate([40, 14])
    got, want, tiles = lay.step(pos, 4)
    _assert_real_columns_agree(got, want, pos, nvalid)
    assert tiles == 3


def test_a_recycled_block_keeps_stale_rows_above_the_frontier_unread():
    """A slot that ends hands its blocks, rows and all, to the next
    one: the new request at a short position must not see the long
    one's keys that still lie above it in the same blocks."""
    lay = _Layout(2, bs=8, mb=8, nh=4, hd=8)
    lay.allocate([64, 64])
    lay.step([56, 56], 8)                 # both rows full of real keys
    # slot 0 is evicted and re-admitted at position 3 on the SAME
    # blocks; slot 1 goes idle, its table nulled
    lay.tables[1] = 0
    got, want, tiles = lay.step([3, 0], 4)
    _assert_real_columns_agree(got, want, [3, 0], [4, 0])
    assert tiles == 1
    # and against the answer computed from the live keys alone
    q, k, v = lay.rows(1)
    alone = _Layout(1, bs=8, mb=8, nh=4, hd=8, seed=5)
    alone.tables[0] = lay.tables[0]
    alone.k_pool, alone.v_pool = lay.k_pool, lay.v_pool
    live = np.asarray(alone.dense()[0])[0, :, :7]         # [nh, 7, hd]
    out, _ = GPTAttention._attend_paged(
        alone.attn, q[:1], k[:1], v[:1], lay.k_pool, lay.v_pool,
        jnp.asarray([7], jnp.int32), jnp.asarray(alone.tables))
    keys = np.concatenate([live, np.asarray(k)[0]], axis=1)
    vals = np.concatenate([np.asarray(alone.dense()[1])[0, :, :7],
                           np.asarray(v)[0]], axis=1)
    sc = np.einsum("hd,hkd->hk", np.asarray(q)[0, :, 0], keys) / 8 ** 0.5
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out._value)[0, :, 0],
                               np.einsum("hk,hkd->hd", p, vals),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("accepted", [0, 1, 3])
def test_speculative_verify_columns_with_a_rejected_suffix(accepted):
    """A verify step writes k + 1 = 4 staged columns; `accepted` of
    the proposals survive, and the next step starts behind them: the
    rejected suffix's rows lie above its frontier until overwritten."""
    lay = _Layout(2, bs=8, mb=8, nh=4, hd=8)
    pos = [29, 13]                        # slot 0's columns cross a tile
    lay.allocate([p + 8 for p in pos])
    got, want, _ = lay.step(pos, 4)
    _assert_real_columns_agree(got, want, pos, [4, 4])
    nxt = [p + 1 + accepted for p in pos]
    got, want, tiles = lay.step(nxt, 4)
    _assert_real_columns_agree(got, want, nxt, [4, 4])
    assert tiles == (max(nxt) + 3) // TILE + 1


@pytest.mark.parametrize("pos,chunk", [
    ([0, 0, 0], 4), ([0, 11, 0], 4), ([0, 12, 0], 4), ([12, 0, 0], 5),
    ([15, 0, 0], 1), ([16, 0, 0], 1), ([31, 47, 2], 1), ([31, 48, 2], 1),
    ([60, 0, 0], 4), ([63, 63, 63], 8), ([64, 0, 0], 4), ([64, 64, 20], 2),
])
def test_trip_count_is_the_longest_rows_tile(pos, chunk):
    """`max(pos + chunk - 1) // tile + 1` turns, computed here on the
    host. A column past the table is padding and counts for nothing:
    the draft micro-step parks its idle rows there."""
    lay = _Layout(3, bs=8, mb=8, nh=2, hd=8)
    lay.allocate([min(p + chunk, 64) for p in pos])
    got, want, tiles = lay.step(pos, chunk)
    assert tiles == _host_tiles(pos, chunk)
    _assert_real_columns_agree(got, want, pos,
                               [max(min(chunk, 64 - p), 0) for p in pos])


def test_the_loop_compiles_once_for_every_length():
    """The bound is a value of the trace: one program serves a batch
    at position 0 and one at the table's end."""
    lay = _Layout(2, bs=8, mb=8, nh=2, hd=8)
    lay.allocate([64, 64])
    traces = []

    @jax.jit
    def step(q, k, v, k_pool, v_pool, pos, tables):
        traces.append(1)
        out, (_, _, _, tiles) = GPTAttention._attend_paged(
            lay.attn, q, k, v, k_pool, v_pool, pos, tables)
        return out._value, tiles

    q, k, v = lay.rows(4)
    tables = jnp.asarray(lay.tables)
    counts = [int(step(q, k, v, lay.k_pool, lay.v_pool,
                       jnp.asarray(p, jnp.int32), tables)[1])
              for p in ([0, 0], [20, 3], [60, 60])]
    assert counts == [1, 2, 4] and len(traces) == 1


# -- through the model and the engine ----------------------------------------

VOCAB = 89


@pytest.fixture(scope="module")
def model():
    paddle.seed(23)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=48, num_layers=2,
                    num_heads=3, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def _uncached_logits(m, ids):
    out = m(Tensor(jnp.asarray(np.asarray(ids, np.int32)[None, :])))
    return np.asarray(out._value, np.float32)[0]


@pytest.mark.parametrize("lengths", [(9,), (16, 17), (41, 5, 64), (64, 33)])
def test_paged_forward_matches_the_uncached_forward(model, lengths):
    """Prompts of unequal length fed chunk by chunk through
    `paged_forward` (3 heads, blocks of 8, tiles of 16): every
    position's logits against one uncached causal forward of the whole
    prompt, which never sees a cache, a table or a tile."""
    rng = np.random.default_rng(sum(lengths))
    bs, chunk, b = 8, 8, len(lengths)
    mb = 64 // bs
    prompts = [rng.integers(0, VOCAB, n) for n in lengths]
    tables = np.zeros((b, mb), np.int32)
    free = iter(rng.permutation(np.arange(1, 1 + b * mb)))
    for i, n in enumerate(lengths):
        tables[i, :-(-n // bs)] = [next(free) for _ in range(-(-n // bs))]
    layout = model.cache_layout()
    pools = [tuple(jnp.zeros(shape, jnp.float32)
                   for shape in layout.pool_shapes(1 + b * mb, bs))
             for _ in range(layout.layers)]
    got = [np.zeros((n, VOCAB), np.float32) for n in lengths]
    for at in range(0, max(lengths), chunk):
        nvalid = np.array([min(max(n - at, 0), chunk) for n in lengths],
                          np.int32)
        # a finished row goes idle: position 0, nothing valid
        pos = np.where(nvalid > 0, at, 0).astype(np.int32)
        tok = np.zeros((b, chunk), np.int32)
        for i, n in enumerate(nvalid):
            tok[i, :n] = prompts[i][at:at + n]
        step_tables = np.where(nvalid[:, None] > 0, tables, 0)
        h, pools, aux = model.paged_forward(
            jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(nvalid),
            jnp.asarray(step_tables), pools)
        logits = np.asarray(model.logits(Tensor(h))._value, np.float32)
        for i, n in enumerate(nvalid):
            got[i][at:at + n] = logits[i, :n]
        assert int(aux["attn_key_tiles"]) == (at + chunk - 1) // TILE + 1
        # known from the table's shape: a plain int, not a device value
        assert aux["attn_key_tiles_max"] == 64 // TILE
        assert isinstance(aux["attn_key_tiles_max"], int)
    for ids, g in zip(prompts, got):
        np.testing.assert_allclose(g, _uncached_logits(model, ids),
                                   rtol=2e-4, atol=2e-4)


def test_the_layers_share_one_trace_of_the_loop(model):
    """The tile walk is a jitted function of the module, so a step's
    trace calls ONE jaxpr of it from every layer: a model of 24 layers
    traces and lowers the loop once, not 24 times."""
    bs, chunk, b, mb = 8, 4, 2, 8
    layout = model.cache_layout()
    pools = [tuple(jnp.zeros(shape, jnp.float32)
                   for shape in layout.pool_shapes(1 + b * mb, bs))
             for _ in range(layout.layers)]

    def step(tok, pos, nvalid, tables, pools):
        return model.paged_forward(tok, pos, nvalid, tables, pools)[:2]

    jaxpr = jax.make_jaxpr(step)(
        jnp.zeros((b, chunk), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), jnp.int32), jnp.zeros((b, mb), jnp.int32), pools)
    walks = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")
             and e.params["name"] == "_attend_tiles"]
    assert len(walks) == layout.layers == 2
    assert len({id(e.params["jaxpr"]) for e in walks}) == 1
    # and the loop lives in there, not in the step's own equations
    assert not [e for e in jaxpr.eqns if e.primitive.name == "while"]


def test_key_tile_counters_add_up_over_a_served_run(model):
    """`attn_key_tiles` sums the turns each step's loop ran,
    `attn_key_tiles_max` the turns that would cover the table (a
    constant of the step, added on the host and not an output of the
    program): here every step is replayed on the host from the engine's
    positions."""
    eng = serving.SlotEngine(model, max_slots=3, block_size=8,
                             prefill_chunk=4, prefix_cache=False)
    assert eng.compile_counts == {}
    rng = np.random.default_rng(3)
    futs = [eng.submit(rng.integers(0, VOCAB, n).astype(np.int32),
                       max_new_tokens=m)
            for n, m in ((5, 3), (30, 6), (17, 20), (44, 9))]
    per_tile, tiles_max = key_tiling(64 // 8, 8)
    assert (per_tile * 8, tiles_max) == (TILE, 4)
    want = steps = 0
    eng._admit()
    while eng.active or eng.queue.depth:
        before = eng.metrics.get("steps")
        longest = min(max(int(p) for p in eng._pos)
                      + eng.prefill_chunk - 1, 63)
        eng._step()
        eng._admit()
        if eng.metrics.get("steps") > before:
            steps += 1
            want += longest // TILE + 1
    for f in futs:
        f.result(timeout=60)
    assert steps == eng.metrics.get("steps") > 20
    assert eng.metrics.get("attn_key_tiles") == want
    assert eng.metrics.get("attn_key_tiles_max") == tiles_max * steps
    assert steps < want < tiles_max * steps     # it engaged, and stopped
    assert int(eng.aux_totals["attn_key_tiles"]) == want
    assert eng._aux_const == {"attn_key_tiles_max": tiles_max}
    assert eng.compile_counts == {"decode": 1}
    counters = eng.metrics.snapshot()["counters"]
    assert counters["attn_key_tiles"] == want
    assert counters["attn_key_tiles_max"] == tiles_max * steps
    text = observe.prometheus_text(serving=eng.metrics)
    assert f"paddle_serving_attn_key_tiles_total {want}" in text
    assert f"paddle_serving_attn_key_tiles_max_total {tiles_max * steps}" \
        in text
