"""GPT model family (parity target: FleetX / PaddleNLP GPT-2/3 used by the
reference's hybrid-parallel ladder config; the reference repo itself ships
the layer primitives — nn/layer/transformer.py — and the fleet TP/PP
machinery these models plug into).

TPU-native design:
- decoder blocks use `F.scaled_dot_product_attention` (pallas flash
  attention on TPU, jnp fallback elsewhere);
- TP: q/k/v + mlp projections are Column/RowParallelLinear carrying GSPMD
  specs over 'mp'; vocab embedding sharded over 'mp'; logits stay vocab-
  sharded into ParallelCrossEntropy;
- sequence parallel (megatron-style): optional sharding of the seq axis
  over 'mp' outside the matmul regions (`sequence_parallel=True`);
- PP: blocks are structurally identical -> their params stack into
  [num_layers, ...] leaves, consumed by the scan/ppermute pipeline
  (distributed/hybrid.py).
"""

from __future__ import annotations

import functools
import math

import jax

from ... import nn
from ...core.config import no_grad
from ...core.tensor import Tensor
from ...distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, shard_hint,
)
from ...distributed.topology import DP_AXIS, MP_AXIS
from ...nn import functional as F


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_hidden_size=None, max_seq_len=1024,
                 dropout=0.1, attn_dropout=0.1, layer_norm_eps=1e-5,
                 initializer_range=0.02, use_parallel=True,
                 sequence_parallel=False, tie_word_embeddings=True,
                 recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.attn_dropout = attn_dropout
        self.layer_norm_eps = layer_norm_eps
        self.initializer_range = initializer_range
        self.use_parallel = use_parallel
        self.sequence_parallel = sequence_parallel
        self.tie_word_embeddings = tie_word_embeddings
        self.recompute = recompute


_PRESETS = {
    "gpt2-small": dict(hidden_size=768, num_layers=12, num_heads=12),
    "gpt2-medium": dict(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt2-large": dict(hidden_size=1280, num_layers=36, num_heads=20),
    "gpt3-1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16,
                      max_seq_len=2048),
    "gpt3-6.7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                      max_seq_len=2048),
}


def gpt_config(name, **overrides):
    cfg = dict(_PRESETS[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


#: key positions one turn of the paged attention loop reads
KEY_TILE = 256


def key_tiling(blocks_per_slot, block_size):
    """How `GPTAttention._attend_paged` walks a block table: ``(table
    entries a tile, tiles that cover the table)``."""
    per_tile = max(min(KEY_TILE // block_size, blocks_per_slot), 1)
    return per_tile, -(-blocks_per_slot // per_tile)


@functools.partial(jax.jit, static_argnames=("per_tile", "window"))
def _attend_tiles(q, k_pool, v_pool, tables, t_idx, per_tile, base=None,
                  window=None):
    """Online-softmax attention of queries ``[b, nh, s, hd]``,
    column `c` of row `b` at position ``t_idx[b, c]``, over the pools
    ``[num_blocks, block_size, nkv, hd]`` (or ``[num_blocks,
    block_size, nkv * hd]``, the heads side by side in a row) read
    through `tables` in tiles of `per_tile` entries: ``(out [b, nh, s,
    hd] float32, turns run)``. Jitted on its own so that a model's
    layers share one trace of the loop: inside a step's trace it is a
    call of that trace, not a program of its own.

    The products run in the queries' dtype and accumulate in float32
    (float32 queries widen the cached rows to float32, as GPT's do).
    **Grouped heads**: with `nkv` pool heads for ``nh = g * nkv`` query
    heads, the `g` query heads of a KV head fold into the query axis
    (``[b, nkv, g * s, hd]``), so a K/V tile is gathered once for all
    of them. **A window**: `window` keys are admitted counting the
    query's own (``t - window < key <= t``), `tables` is then the
    slot's SHORT table whose entry 0 holds position ``base[b]``, and
    the loop runs over the whole of it, a number of turns that no
    row's depth changes; without one it runs from tile 0 to the
    batch's longest row."""
    import jax.numpy as jnp
    from jax import lax

    b, nh, s_new, hd = q.shape
    bs, mb = k_pool.shape[1], tables.shape[1]
    nkv = math.prod(k_pool.shape[2:]) // hd
    tile = per_tile * bs
    n_tiles_max = -(-mb // per_tile)
    # a table that is no whole number of tiles ends in the null block
    tiled = jnp.pad(tables, ((0, 0), (0, n_tiles_max * per_tile - mb)))
    f32 = jnp.float32
    scale = 1.0 / (hd ** 0.5)
    if nkv != nh:
        q = q.reshape(b, nkv, (nh // nkv) * s_new, hd)
        t_idx = jnp.tile(t_idx, (1, nh // nkv))

    def body(j, carry):
        m, l, acc = carry
        blocks = lax.dynamic_slice_in_dim(tiled, j * per_tile, per_tile,
                                          axis=1)
        k_tile = k_pool[blocks].reshape(b, tile, nkv, hd)
        v_tile = v_pool[blocks].reshape(b, tile, nkv, hd)
        sc = jnp.einsum("bhqd,bkhd->bhqk", q, k_tile.astype(q.dtype),
                        preferred_element_type=f32) * scale
        k_pos = j * tile + jnp.arange(tile)
        if window is None:
            mask = k_pos[None, None, :] <= t_idx[:, :, None]
        else:
            k_pos = base[:, None, None] + k_pos[None, None, :]
            mask = (k_pos <= t_idx[:, :, None]) \
                & (k_pos > t_idx[:, :, None] - window)
        sc = jnp.where(mask[:, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), v_tile.astype(q.dtype),
            preferred_element_type=f32)
        return m_new, l, acc

    if window is None:
        # every column admits key 0, so after the first turn `m` is a
        # real score and a wholly masked later tile adds exp(-1e30 - m).
        # A column past the table is padding (its row went to the null
        # block, its output is unread) and does not lengthen the loop
        longest = jnp.max(jnp.where(t_idx < mb * bs, t_idx, 0))
        n_tiles = (longest // tile + 1).astype(jnp.int32)
    else:
        # the short table is read whole: a tile with no admitted key
        # before a row's first real score leaves counts that the first
        # real score's `alpha` of exp(-1e30 - m) wipes
        n_tiles = n_tiles_max
    init = (jnp.full(q.shape[:3], -1e30, f32),
            jnp.zeros(q.shape[:3], f32),
            jnp.zeros(q.shape, f32))
    _, l, acc = lax.fori_loop(0, n_tiles, body, init)
    return (acc / l[..., None]).reshape(b, nh, s_new, hd), n_tiles


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, nh = config.hidden_size, config.num_heads
        self.num_heads = nh
        self.head_dim = h // nh
        self.attn_dropout = config.attn_dropout
        init = nn.initializer.Normal(std=config.initializer_range)
        if config.use_parallel:
            self.qkv_proj = ColumnParallelLinear(
                h, 3 * h, weight_attr=init, gather_output=False)
            self.out_proj = RowParallelLinear(
                h, h, weight_attr=init, input_is_parallel=True)
        else:
            self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=init)
            self.out_proj = nn.Linear(h, h, weight_attr=init)

    def forward(self, x, cache=None):
        b, s, h = x.shape
        # single packed transpose (see ernie.py): minimises physical
        # copies around the pallas flash custom-call
        qkv = self.qkv_proj(x).reshape(
            [b, s, 3, self.num_heads, self.head_dim]).transpose(
            [2, 0, 3, 1, 4])
        q, k, v = qkv.unstack(axis=0)
        if cache is not None:
            out, new_cache = self._attend_cached(q, k, v, cache)
            # [b, nh, s, hd] -> [b, s, nh*hd] (sdpa's bhsd mode returns
            # seq-major already; the cached path must match)
            out = out.transpose([0, 2, 1, 3]).reshape([b, s, h])
            return self.out_proj(out), new_cache
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.attn_dropout if self.training else 0.0,
            qkv_layout="bhsd")
        out = out.reshape([b, s, h])
        return self.out_proj(out)

    def _attend_cached(self, q, k, v, cache):
        """Incremental decode attention over a static-shape KV cache
        (ref paddlenlp generation + fused multi_transformer decode
        caches): new keys/values land at `pos` via dynamic_update_slice;
        queries attend to all cached positions <= their own. Inference
        only — jnp math, no tape.

        `pos` may be a scalar (whole batch at one position — generate())
        or a [b] vector of PER-ROW positions (the serving slot engine,
        where each batch row is an independent request mid-decode). The
        per-row causal mask doubles as stale-KV masking: a recycled
        slot's leftover keys live at positions > the new request's pos,
        so they are never attended before being overwritten.

        Paged mode (the serving block-paged pool): `pos` is a tuple
        ``(pos_vec, block_tables)`` and k/v caches are physical block
        pools ``[num_blocks, block_size, nh, hd]`` (token-major: the
        order the step writes and reads them in). Row b's logical
        position t lives at physical row ``(tables[b, t // bs],
        t % bs)``; new KV scatters through the table, and the scores
        read the pool back tile by tile through it, as far as the
        batch's longest live row (`_attend_paged`; this function's
        dense vector-`pos` branch is its reference). Padding rows
        (positions past the sequence / chunk) are routed to reserved
        block 0, so the step shape never depends on how many rows are
        real — the compile-once property survives arbitrary
        chunked-prefill/decode mixes. The same overwrite-
        before-attend invariant makes block recycling and whole-block
        copy-on-write safe without zeroing."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        k_cache, v_cache, pos = cache
        qv = q._value if isinstance(q, Tensor) else q
        kv = k._value if isinstance(k, Tensor) else k
        vv = v._value if isinstance(v, Tensor) else v
        s_new = qv.shape[2]
        if isinstance(pos, tuple):
            return self._attend_paged(qv, kv, vv, k_cache, v_cache,
                                      pos[0], pos[1])
        s_max = k_cache.shape[2]
        key_idx = jnp.arange(s_max)
        pos_vec = getattr(pos, "ndim", 0) == 1
        if pos_vec:
            b = qv.shape[0]
            row = jnp.arange(b)[:, None]              # [b, 1]
            t_idx = pos[:, None] + jnp.arange(s_new)  # [b, s_new]
            # advanced-index scatter: rows land at their own positions
            k_cache = k_cache.at[row, :, t_idx, :].set(
                jnp.swapaxes(kv, 1, 2).astype(k_cache.dtype))
            v_cache = v_cache.at[row, :, t_idx, :].set(
                jnp.swapaxes(vv, 1, 2).astype(v_cache.dtype))
        else:
            k_cache = lax.dynamic_update_slice(
                k_cache, kv.astype(k_cache.dtype), (0, 0, pos, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache, vv.astype(v_cache.dtype), (0, 0, pos, 0))
        scale = 1.0 / (self.head_dim ** 0.5)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qv.astype(jnp.float32),
                            k_cache.astype(jnp.float32)) * scale
        if pos_vec:
            mask = key_idx[None, None, :] <= t_idx[:, :, None]
            scores = jnp.where(mask[:, None], scores, -1e30)
        else:
            q_pos = pos + jnp.arange(s_new)
            mask = key_idx[None, :] <= q_pos[:, None]  # [s_new, s_max]
            scores = jnp.where(mask[None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p,
                         v_cache.astype(jnp.float32)).astype(qv.dtype)
        return Tensor(out), (k_cache, v_cache, pos + s_new)

    def _attend_paged(self, qv, kv, vv, k_pool, v_pool, pos, tables):
        """Paged variant of the vector-pos branch: scatter the new KV
        through per-row block tables into the physical pool, then attend
        over the pool tile by tile through the table, as far as the
        batch's longest live row. Out-of-range rows (padding past
        max_seq) write into the reserved null block 0; table entries
        past a slot's allocation are 0 too, and both stay unattended
        because a key is admitted only at a position <= the column's
        own.

        The read (`_attend_tiles`, one trace shared by every layer) is
        an online-softmax loop over tiles of `KEY_TILE` positions: turn
        `j` takes its entries of `tables`, gathers their blocks of both
        pools to ``[b, tile, nh, hd]``, scores them against the step's
        queries and carries running max, sum and accumulator in
        float32. The loop runs ``max(t_idx) // tile
        + 1`` turns over the columns that lie inside the table, a
        value of the trace and not a shape (one compiled program
        whatever the batch holds): no ``[b, max_seq, nh, hd]`` view and
        no ``[b, nh, chunk, max_seq]`` score tensor exists, and a step
        costs what its longest row costs. Idle slots sit at position 0
        (the engine's) or past the table (the draft's) and do not
        lengthen it. The operands are those of the dense branch
        (float32 queries and probabilities, cached rows widened to
        float32), so the result differs from it by the order of float32
        additions alone.

        Speculative decoding rides the same scatter: a verify step
        bulk-writes all k+1 staged columns (next token + proposals) in
        this one dispatch, and a rejected suffix's pool rows are just
        more garbage-above-the-frontier — masked out by position now,
        overwritten by the next round's staging before the coverage
        frontier reaches them.

        The pool is ``[num_blocks, block_size, nh, hd]`` so that the
        scatter indexes its two LEADING axes and a gathered tile feeds
        the contraction by reshape alone: a step that donates the pools
        then updates them in place. A scatter over axes that are not
        adjacent makes the TPU compiler relayout the whole pool round
        it, donated or not (tests/test_v5e_compile.py holds the
        compiled step to this).

        Returns ``(out, (k_pool, v_pool, (pos + s_new, tables),
        key_tiles))``, `key_tiles` the int32 count of turns the loop
        ran."""
        import jax.numpy as jnp

        b, s_new = qv.shape[0], qv.shape[2]
        bs = k_pool.shape[1]
        mb = tables.shape[1]
        s_max = mb * bs
        row = jnp.arange(b)[:, None]                  # [b, 1]
        t_idx = pos[:, None] + jnp.arange(s_new)      # [b, s_new]
        safe_t = jnp.minimum(t_idx, s_max - 1)
        blk = jnp.where(t_idx >= s_max, 0,
                        tables[row, safe_t // bs])    # [b, s_new]
        off = safe_t % bs
        # advanced-index scatter through the tables: value rows land at
        # (physical block, in-block offset) of their logical position
        k_pool = k_pool.at[blk, off].set(
            jnp.swapaxes(kv, 1, 2).astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(
            jnp.swapaxes(vv, 1, 2).astype(v_pool.dtype))

        per_tile, _ = key_tiling(mb, bs)
        out, n_tiles = _attend_tiles(
            qv.astype(jnp.float32), k_pool, v_pool, tables, t_idx, per_tile)
        out = out.astype(qv.dtype)
        return Tensor(out), (k_pool, v_pool, (pos + s_new, tables), n_tiles)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, f = config.hidden_size, config.ffn_hidden_size
        init = nn.initializer.Normal(std=config.initializer_range)
        if config.use_parallel:
            self.fc1 = ColumnParallelLinear(h, f, weight_attr=init,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(f, h, weight_attr=init,
                                         input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(h, f, weight_attr=init)
            self.fc2 = nn.Linear(f, h, weight_attr=init)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    """Pre-LN decoder block. All blocks are structurally identical so
    their params stack for the pipeline scan."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.norm1 = nn.LayerNorm(config.hidden_size,
                                  epsilon=config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.norm2 = nn.LayerNorm(config.hidden_size,
                                  epsilon=config.layer_norm_eps)
        self.mlp = GPTMLP(config)
        self.dropout = config.dropout
        self.sequence_parallel = config.sequence_parallel

    def _sp(self, x):
        if self.sequence_parallel:
            # megatron sequence parallelism: outside matmul regions the
            # activations shard their seq axis over 'mp'
            return shard_hint(x, DP_AXIS, MP_AXIS, None)
        return shard_hint(x, DP_AXIS, None, None)

    def forward(self, x, cache=None):
        x = self._sp(x)
        if cache is not None:
            h, new_cache = self.attn(self.norm1(x), cache)
        else:
            h = self.attn(self.norm1(x))
        h = F.dropout(h, self.dropout, training=self.training)
        x = x + h
        x = self._sp(x)
        h = self.mlp(self.norm2(x))
        h = F.dropout(h, self.dropout, training=self.training)
        x = x + h
        if cache is not None:
            return x, new_cache
        return x


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(std=config.initializer_range)
        if config.use_parallel:
            self.word_embeddings = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size, weight_attr=init)
        else:
            self.word_embeddings = nn.Embedding(
                config.vocab_size, config.hidden_size, weight_attr=init)
        self.position_embeddings = nn.Embedding(
            config.max_seq_len, config.hidden_size, weight_attr=init)
        self.dropout = config.dropout

    def forward(self, input_ids, position_ids=None):
        import jax.numpy as jnp

        if position_ids is None:
            s = input_ids.shape[-1]
            position_ids = Tensor(jnp.arange(s, dtype=jnp.int32))
        x = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        return F.dropout(x, self.dropout, training=self.training)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.final_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None):
        x = self.embeddings(input_ids, position_ids)
        if caches is not None:
            new_caches = []
            for layer, c in zip(self.layers, caches):
                x, nc = layer(x, c)
                new_caches.append(nc)
            return self.final_norm(x), new_caches
        if self.config.recompute and self.training:
            # per-block rematerialisation: activations recomputed in the
            # backward, trading FLOPs for the memory that puts billion-
            # parameter configs on one chip (ref recompute strategy)
            from ...distributed.fleet.utils.recompute import recompute

            for layer in self.layers:
                x = recompute(layer, x)
        else:
            for layer in self.layers:
                x = layer(x)
        return self.final_norm(x)

    def init_caches(self, batch_size, max_len, dtype=None):
        """Zeroed static-shape KV caches for incremental decode."""
        import jax.numpy as jnp

        cfg = self.config
        hd = cfg.hidden_size // cfg.num_heads
        dtype = dtype or jnp.bfloat16
        shape = (batch_size, cfg.num_heads, max_len, hd)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), 0)
                for _ in range(cfg.num_layers)]


class GPTForPretraining(nn.Layer):
    """LM-head model; logits = h @ E^T (tied) stay vocab-sharded over
    'mp' and feed ParallelCrossEntropy."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, position_ids=None):
        h = self.gpt(input_ids, position_ids)
        return self.logits(h)

    @no_grad()
    def generate(self, input_ids, *, max_new_tokens=20, do_sample=False,
                 top_k=50, temperature=1.0, eos_token_id=None, seed=0):
        """Autoregressive decoding with a static-shape KV cache (ref
        paddlenlp GenerationMixin.generate greedy/sampling): one prefill
        pass over the prompt, then one single-token step per new token —
        O(1) attention work per step instead of re-running the prompt.
        Returns [batch, prompt + max_new_tokens] ids; positions after an
        eos repeat eos."""
        import jax
        import jax.numpy as jnp

        was_training = self.training
        self.eval()
        try:
            ids = input_ids._value if isinstance(input_ids, Tensor) \
                else jnp.asarray(input_ids)
            ids = jnp.asarray(ids, jnp.int32)
            b, s0 = ids.shape
            max_len = s0 + max_new_tokens
            if max_len > self.config.max_seq_len:
                raise ValueError(
                    f"prompt + max_new_tokens = {max_len} exceeds "
                    f"max_seq_len {self.config.max_seq_len}")
            caches = self.gpt.init_caches(b, max_len)
            key = jax.random.PRNGKey(seed)
            done = jnp.zeros((b,), bool)

            def step(tok_ids, pos_ids, caches):
                h, caches = self.gpt(Tensor(tok_ids),
                                     Tensor(pos_ids), caches)
                # only the last position feeds sampling: skip the
                # full-vocab projection of the rest of the prompt
                logits = self.logits(h[:, -1:])
                lv = logits._value if isinstance(logits, Tensor) \
                    else logits
                return lv[:, 0, :].astype(jnp.float32), caches

            logits, caches = step(ids, jnp.arange(s0, dtype=jnp.int32),
                                  caches)
            out = [ids]
            for t in range(max_new_tokens):
                if do_sample:
                    scaled = logits / max(temperature, 1e-6)
                    if top_k:
                        kth = jax.lax.top_k(scaled,
                                            min(top_k,
                                                scaled.shape[-1]))[0]
                        scaled = jnp.where(
                            scaled < kth[:, -1:], -jnp.inf, scaled)
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, scaled, axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(jnp.int32)
                if eos_token_id is not None:
                    nxt = jnp.where(done, eos_token_id, nxt)
                    done = done | (nxt == eos_token_id)
                out.append(nxt[:, None])
                if t == max_new_tokens - 1:
                    break
                if eos_token_id is not None and bool(done.all()):
                    # pad the remainder with eos and stop early
                    rest = max_new_tokens - t - 1
                    out.append(jnp.full((b, rest), eos_token_id,
                                        jnp.int32))
                    break
                pos = jnp.asarray([s0 + t], jnp.int32)
                logits, caches = step(nxt[:, None], pos, caches)
            return Tensor(jnp.concatenate(out, axis=1))
        finally:
            if was_training:
                self.train()

    # -- the serving seam (serving.SlotEngine) --------------------------------

    def cache_layout(self):
        """K and V rows ``[nh, hd]`` a token a layer, blocks token-major
        (`paging.BLOCK_ROW_ORDER`); the head axis may shard over mp."""
        from ...serving.paging import BLOCK_ROW_ORDER, CacheLayout

        cfg = self.config
        row = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
        return CacheLayout(BLOCK_ROW_ORDER, (("k", row), ("v", row)),
                           cfg.num_layers, head_axis=2)

    def paged_forward(self, tok, pos, nvalid, tables, pools):
        """One serving step: `tok` ``[slots, chunk]``, slot `b`'s
        columns at positions ``pos[b] + column``; `pools` one ``(k, v)``
        a layer, scattered into and attended through `tables`
        (`GPTAttention._attend_paged`). Returns ``(hidden, pools,
        aux)`` with aux ``{"attn_key_tiles": turns the attention loop
        ran this step (an int32 scalar; every layer runs the same
        count), "attn_key_tiles_max": turns that cover the whole table
        (a plain int, known from the table's shape)}``: their ratio is
        the share of the table the step read."""
        import jax.numpy as jnp

        # clamp padding rows' position ids into the embedding table;
        # their KV writes route to the null block regardless
        posmat = jnp.minimum(pos[:, None] + jnp.arange(tok.shape[1]),
                             self.config.max_seq_len - 1)
        caches = [(k, v, (pos, tables)) for k, v in pools]
        h, new_caches = self.gpt(Tensor(tok), Tensor(posmat),
                                 caches=caches)
        hv = h._value if isinstance(h, Tensor) else h
        *_, key_tiles = new_caches[0]
        _, tiles_max = key_tiling(tables.shape[1], pools[0][0].shape[1])
        aux = {"attn_key_tiles": key_tiles, "attn_key_tiles_max": tiles_max}
        return hv, [(c[0], c[1]) for c in new_caches], aux

    def logits(self, h):
        from ...core.dispatch import apply

        if self.config.tie_word_embeddings:
            w = self.gpt.embeddings.word_embeddings.weight
            logits = apply("matmul_v2", h, w, trans_y=True)
            if self.config.use_parallel:
                logits = shard_hint(logits, DP_AXIS, None, MP_AXIS)
            return logits
        return self.lm_head(h)


def lora_logits_delta(hrows, aid, lora_a, lora_b):
    """Batched low-rank LM-head delta for multi-adapter serving
    (ISSUE 20): each slot's hidden rows pick up ``B[aid] @ A[aid] @ h``
    with its own adapter gathered by index — row 0 is the base model's
    all-zero pair, so base slots add exactly ``0.0`` and stay bitwise.

    ``hrows`` is ``[S, H]`` (one row per slot) or ``[S, C, H]`` (the
    speculative verify columns); ``aid`` is ``[S]`` int32;
    ``lora_a`` is ``[n_adapters, r, H]`` and ``lora_b`` is
    ``[n_adapters, V, r]``. Returns f32 logits deltas shaped like the
    head's output (``[S, V]`` / ``[S, C, V]``). Pure jnp — traced
    inside the engine's ONE compiled step; the gather keeps shapes
    static so adding adapters to a slot never retraces."""
    import jax.numpy as jnp

    h = jnp.asarray(hrows).astype(jnp.float32)
    a = jnp.take(jnp.asarray(lora_a), jnp.asarray(aid), axis=0)
    b = jnp.take(jnp.asarray(lora_b), jnp.asarray(aid), axis=0)
    if h.ndim == 2:          # [S, H] x [S, r, H] -> [S, r] -> [S, V]
        low = jnp.einsum("sh,srh->sr", h, a)
        return jnp.einsum("sr,svr->sv", low, b)
    # [S, C, H] x [S, r, H] -> [S, C, r] -> [S, C, V]
    low = jnp.einsum("sch,srh->scr", h, a)
    return jnp.einsum("scr,svr->scv", low, b)


class GPTPretrainingCriterion(nn.Layer):
    def __init__(self, config: GPTConfig = None, ignore_index=-100):
        super().__init__()
        use_parallel = config.use_parallel if config is not None else False
        self.loss_fn = ParallelCrossEntropy(ignore_index=ignore_index) \
            if use_parallel else None
        self.ignore_index = ignore_index

    def forward(self, logits, labels, loss_mask=None):
        if self.loss_fn is not None:
            loss = self.loss_fn(logits, labels)
            loss = loss.squeeze(-1)
        else:
            loss = F.cross_entropy(logits, labels, reduction="none",
                                   ignore_index=self.ignore_index)
        if loss_mask is not None:
            m = loss_mask.reshape(loss.shape).astype("float32")
            return (loss * m).sum() / m.sum().clip(min=1.0)
        return loss.mean()
