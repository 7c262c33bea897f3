"""Decoder family that mixes sliding-window and full attention layers
over grouped KV heads, with routed experts in every layer.

The block of `model_type` ``mellum`` in a public `config.json`:
`layer_types` says of each layer whether a query admits every earlier
key (``full_attention``) or only the last `sliding_window` keys, itself
among them (``sliding_attention``); `mlp_layer_types` is all
``sparse``. Every layer, pre-norm:

    x <- x + Attn(RMSNorm(x));   x <- x + MoE(RMSNorm(x))

and a final RMSNorm before the untied head.

- **Attention, both kinds.** ``[q | k | v] = h W_qkv`` (`num_heads`
  query heads and `num_kv_heads` K/V heads of `head_dim`, no bias);
  ``q_i = RMSNorm(q_i)``, ``k_j = RMSNorm(k_j)`` a head, one learned
  scale of `head_dim` each; rotary on all of `head_dim` in pairs ``(i,
  i + head_dim / 2)``; query head `i` reads KV head ``i // (num_heads
  // num_kv_heads)``; scores ``q . k * head_dim ** -0.5``, softmax in
  float32; ``W_o``. A sliding layer takes plain rotary and admits key
  `j` for a query at `t` iff ``t - sliding_window < j <= t``; a full
  layer takes the YaRN group of `rope_parameters` (cos and sin carry
  its `attention_factor`, the softmax scale is unchanged) and admits
  ``j <= t``.
- **Experts.** ``p = softmax(h W_r)`` over all `num_experts` in
  float32, the `num_experts_per_tok` largest, ``w = p / sum(picked
  p)`` (`norm_topk_prob`); ``y = sum_i w_i SwiGLU_i(h)``. No shared
  expert, no scaling factor, every expert held: `latent_moe.
  HeldExperts` with the softmax rule, its grouped product unchanged.

Serving: the model states a cache layout of TWO block groups
(`cache_layout`): the full layers' K and V blocks are kept for a
slot's life, the sliding layers' are freed behind the window, so a
long context costs the full layers' rows a token plus a fixed window
a slot. Both keep the `num_kv_heads` heads of a token side by side in
one row of ``num_kv_heads * head_dim`` columns (a row of 4 heads pads
to 16 in the chip's bfloat16 tiles; 512 columns do not pad). The
attention over either is GPT's key-tile loop (`gpt._attend_tiles`):
grouped heads folded into the query axis, and for a sliding layer a
fixed number of turns over the slot's short table, whatever any row's
depth. `paged_forward` takes ``{group: (table, base)}`` and one ``(k,
v)`` of pools a layer; `serving.SlotEngine` carries the arrays, moves
the window group's tables and matches prefixes over both groups.
"""

from __future__ import annotations

from ... import nn
from ...core.tensor import Tensor
from .gpt import _attend_tiles, key_tiling
from .latent_moe import HeldExperts

__all__ = ["WindowMoEConfig", "WindowAttention", "WindowMoEDecoderLayer",
           "WindowMoEModel", "WindowMoEForCausalLM"]

LAYER_PERIOD = ("sliding_attention", "sliding_attention",
                "sliding_attention", "full_attention")
#: the block groups' names in the cache layout, by layer kind
GROUP_OF = {"full_attention": "full", "sliding_attention": "window"}


class WindowMoEConfig:
    """Sizes under this repo's names (a public `config.json` says
    `num_hidden_layers`, `num_attention_heads`, `num_key_value_heads`
    for what is `num_layers`, `num_heads`, `num_kv_heads` here, and
    `max_position_embeddings` for `max_seq_len`). `layer_types` longer
    than `num_layers` is cut to its first entries: the layers held.
    `rope_parameters` maps a layer kind to its rotary group
    (``{"rope_theta", "rope_type", ...}``, `nn.RotaryEmbedding`'s
    `scaling`)."""

    def __init__(self, vocab_size=98304, hidden_size=2304, num_layers=28,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 layer_types=None, sliding_window=1024,
                 moe_intermediate_size=896, num_experts=64,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 rms_norm_eps=1e-6, rope_parameters=None,
                 max_seq_len=131072, initializer_range=0.02):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{num_kv_heads} KV heads do not divide "
                             f"{num_heads} query heads")
        if layer_types is None:
            layer_types = LAYER_PERIOD * -(-self.num_layers // 4)
        if len(layer_types) < self.num_layers:
            raise ValueError(f"{len(layer_types)} layer types for "
                             f"{num_layers} layers")
        self.layer_types = tuple(layer_types[:self.num_layers])
        unknown = set(self.layer_types) - set(GROUP_OF)
        if unknown:
            raise ValueError(f"layer types {sorted(unknown)} are not of "
                             f"{sorted(GROUP_OF)}")
        self.sliding_window = int(sliding_window)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        if not norm_topk_prob:
            raise ValueError("picked scores that are not normalised "
                             "(norm_topk_prob false) are not supported")
        self.norm_topk_prob = True
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_parameters = {
            kind: dict((rope_parameters or {}).get(kind) or {})
            for kind in GROUP_OF}
        self.max_seq_len = int(max_seq_len)
        self.initializer_range = float(initializer_range)
        self.tie_word_embeddings = False
        # what `HeldExperts` asks of a config: every routed expert is
        # held here, scored by softmax, with no scaling and no shared
        # expert
        self.router_experts = self.num_experts
        self.ep_rank, self.ep_size = 0, 1
        self.router_scoring = "softmax"
        self.routed_scaling_factor = 1.0
        self.num_shared_experts = 0

    def count(self, kind):
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def kv_row(self):
        """Columns of one token's K (or V) row: the KV heads side by
        side."""
        return self.num_kv_heads * self.head_dim


def _v(x):
    return x._value if isinstance(x, Tensor) else x


def _proj(linear, x):
    return _v(linear(Tensor(x)))


class WindowAttention(nn.Layer):
    """One layer's attention; `window` None = a full layer."""

    def __init__(self, config: WindowMoEConfig, kind):
        super().__init__()
        c = config
        self.nh, self.nkv, self.hd = c.num_heads, c.num_kv_heads, c.head_dim
        self.kind = kind
        self.window = c.sliding_window if kind == "sliding_attention" \
            else None
        init = nn.initializer.Normal(std=c.initializer_range)
        self.qkv_proj = nn.Linear(c.hidden_size,
                                  (self.nh + 2 * self.nkv) * self.hd,
                                  weight_attr=init, bias_attr=False)
        self.q_norm = nn.RMSNorm(self.hd, epsilon=c.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.hd, epsilon=c.rms_norm_eps)
        self.o_proj = nn.Linear(self.nh * self.hd, c.hidden_size,
                                weight_attr=init, bias_attr=False)
        group = c.rope_parameters[kind]
        self.rotary = nn.RotaryEmbedding(
            self.hd, group.get("rope_theta", 10000.0), group)

    def _project(self, x, positions):
        """``q [b, s, nh, hd]``, ``k`` and ``v [b, s, nkv, hd]`` of
        rows ``[b, s, H]`` at `positions` ``[b, s]``: normed a head,
        then rotated."""
        b, s, _ = x.shape
        qkv = _proj(self.qkv_proj, x).reshape(b, s, -1, self.hd)
        q, k, v = (qkv[:, :, :self.nh], qkv[:, :, self.nh:-self.nkv],
                   qkv[:, :, -self.nkv:])
        q = self.rotary(_v(self.q_norm(Tensor(q))), positions)
        k = self.rotary(_v(self.k_norm(Tensor(k))), positions)
        return q, k, v

    def forward(self, x, positions):
        """Full causal forward of ``[b, s, H]`` (no cache): every query
        head against its KV head repeated, a dense mask."""
        import jax
        import jax.numpy as jnp

        xv, pv = _v(x), _v(positions)
        b, s, _ = xv.shape
        q, k, v = self._project(xv, pv)
        g = self.nh // self.nkv
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) \
                * self.hd ** -0.5
            t, j = pv[:, :, None], pv[:, None, :]
            admit = j <= t
            if self.window:
                admit = admit & (j > t - self.window)
            p = jax.nn.softmax(jnp.where(admit[:, None], scores, -1e30),
                               axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
        return self.o_proj(Tensor(o.astype(xv.dtype).reshape(b, s, -1)))

    def forward_paged(self, x, pos, table, base, pools):
        """One serving step's columns ``[b, s, H]``, slot `b` starting
        at position ``pos[b]``: K and V rows (the KV heads side by
        side) scatter through `table`, whose entry 0 holds position
        ``base[b]`` (None = position 0), into `pools` ``(k, v)`` of
        ``[num_blocks, block_size, nkv * hd]``; positions outside the
        table, i.e. padding, go to the null block. Then the key-tile
        loop attends over them. Returns ``(out [b, s, H], pools, key
        tiles run)``."""
        import jax
        import jax.numpy as jnp

        k_pool, v_pool = pools
        b, s, _ = x.shape
        bs, mb = k_pool.shape[1], table.shape[1]
        t_idx = pos[:, None] + jnp.arange(s)
        q, k, v = self._project(x, t_idx)
        rel = t_idx if base is None else t_idx - base[:, None]
        entry = rel // bs
        outside = (rel < 0) | (entry >= mb)
        blk = jnp.where(outside, 0,
                        table[jnp.arange(b)[:, None],
                              jnp.clip(entry, 0, mb - 1)])
        off = rel % bs
        k_pool = k_pool.at[blk, off].set(
            k.reshape(b, s, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(
            v.reshape(b, s, -1).astype(v_pool.dtype))
        per_tile, whole = key_tiling(mb, bs)
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            o, n_tiles = _attend_tiles(
                jnp.swapaxes(q, 1, 2), k_pool, v_pool, table, t_idx,
                per_tile, base=base, window=self.window)
        if self.window:
            n_tiles = whole     # the short table is read whole: a constant
        o = jnp.swapaxes(o, 1, 2).reshape(b, s, -1).astype(x.dtype)
        return _proj(self.o_proj, o), (k_pool, v_pool), n_tiles


class WindowMoEDecoderLayer(nn.Layer):
    def __init__(self, config: WindowMoEConfig, index):
        super().__init__()
        self.kind = config.layer_types[index]
        self.input_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)
        self.attn = WindowAttention(config, self.kind)
        self.post_norm = nn.RMSNorm(config.hidden_size,
                                    epsilon=config.rms_norm_eps)
        self.mlp = HeldExperts(config)

    def _experts(self, x, valid):
        y, rows = self.mlp(self.post_norm(Tensor(x)), valid)
        return x + y, rows

    def forward(self, x, positions):
        xv = _v(x)
        xv = xv + _v(self.attn(self.input_norm(Tensor(xv)), positions))
        return self._experts(xv, None)

    def forward_paged(self, x, pos, valid, table, base, pools):
        a, pools, n_tiles = self.attn.forward_paged(
            _v(self.input_norm(Tensor(x))), pos, table, base, pools)
        x, rows = self._experts(x + a, valid)
        return x, rows, pools, n_tiles


class WindowMoEModel(nn.Layer):
    def __init__(self, config: WindowMoEConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(std=config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=init)
        self.layers = nn.LayerList(
            [WindowMoEDecoderLayer(config, i)
             for i in range(config.num_layers)])
        self.final_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None):
        """Full causal forward ``[b, s]`` -> ``(hidden [b, s, H],
        expert rows [layers, num_experts])``. Inference only: array
        math, no tape."""
        import jax.numpy as jnp

        ids = _v(input_ids)
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(ids.shape[-1], dtype=jnp.int32), ids.shape)
        x = _v(self.embed_tokens(Tensor(ids)))
        rows = []
        for layer in self.layers:
            x, r = layer(x, _v(position_ids))
            rows.append(r)
        return self.final_norm(Tensor(x)), jnp.stack(rows)


class WindowMoEForCausalLM(nn.Layer):
    """Untied head over the whole vocabulary."""

    def __init__(self, config: WindowMoEConfig):
        super().__init__()
        self.config = config
        self.model = WindowMoEModel(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=nn.initializer.Normal(
                std=config.initializer_range))

    def forward(self, input_ids, position_ids=None):
        h, _ = self.model(input_ids, position_ids)
        return self.logits(h)

    def logits(self, h):
        """Hidden rows ``[..., H]`` to float32 logits ``[..., V]``: the
        product accumulates in float32 and is not rounded to the
        weights' dtype on the way out."""
        import jax.numpy as jnp

        out = jnp.matmul(_v(h), _v(self.lm_head.weight),
                         preferred_element_type=jnp.float32)
        return Tensor(out) if isinstance(h, Tensor) else out

    # -- the serving seam (serving.SlotEngine) --------------------------------

    def cache_layout(self):
        """Two block groups, K and V rows of `kv_row` columns a token a
        layer each (the KV heads side by side; the row may shard over
        mp where mp divides the heads): ``"full"`` holds the full
        layers' pools and keeps every block, ``"window"`` the sliding
        layers' and is freed behind `sliding_window` keys."""
        from ...serving.paging import BlockGroup, CacheLayout

        cfg = self.config
        arrays = (("k", (cfg.kv_row,)), ("v", (cfg.kv_row,)))
        kinds = cfg.layer_types
        return CacheLayout("tc", groups=tuple(
            BlockGroup(GROUP_OF[kind],
                       [i for i, t in enumerate(kinds) if t == kind],
                       arrays, window=window, head_axis=2,
                       heads=cfg.num_kv_heads)
            for kind, window in (("full_attention", None),
                                 ("sliding_attention", cfg.sliding_window))
            if kind == "full_attention" or cfg.count(kind)))

    def serving_gauges(self):
        return {"experts_held": float(self.config.num_experts)}

    def paged_forward(self, tok, pos, nvalid, tables, pools):
        """One serving step: `tok` ``[slots, chunk]``, slot `b`'s
        columns at positions ``pos[b] + column``, the first
        ``nvalid[b]`` of them real; `tables` ``{group: (table, base)}``
        (a plain table where the layout has the one group), `pools`
        one ``(k, v)`` a layer. Returns ``(hidden [slots, chunk, H],
        pools, aux)``: `expert_rows` ``[layers, num_experts]`` as the
        latent family's; `attn_key_tiles_full` the turns the full
        layers' loops ran, summed over them (a value of the step: they
        stop behind the batch's longest row); `attn_key_tiles_window`
        the sliding layers' (a plain int: each reads its short table
        whole, whatever any row's depth) and `attn_key_tiles_max` what
        every layer would run as a full layer over the whole table."""
        import jax.numpy as jnp

        m = self.model
        if not isinstance(tables, dict):
            tables = {"full": (tables, None)}
        valid = jnp.arange(tok.shape[1])[None, :] < nvalid[:, None]
        x = _v(m.embed_tokens(Tensor(tok)))
        new_pools, rows = [], []
        turns = {"full": jnp.int32(0), "window": 0}
        for layer, held in zip(m.layers, pools):
            group = GROUP_OF[layer.kind]
            x, r, held, n_tiles = layer.forward_paged(
                x, pos, valid, *tables[group], held)
            new_pools.append(held)
            rows.append(r)
            turns[group] = turns[group] + n_tiles
        h = _v(m.final_norm(Tensor(x)))
        full_table = tables["full"][0]
        _, tiles_max = key_tiling(full_table.shape[1], pools[0][0].shape[1])
        return h, new_pools, {
            "expert_rows": jnp.stack(rows),
            "attn_key_tiles_full": turns["full"],
            "attn_key_tiles_window": turns["window"],
            "attn_key_tiles_max": tiles_max * len(m.layers)}
