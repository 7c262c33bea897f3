"""Decoder family with latent attention and held routed experts.

The block public mixture-of-experts models with multi-head latent
attention share (`model_type` ``sarvam_mla`` / ``deepseek_v2`` /
``deepseek_v3`` in their `config.json`); per layer, ``h = RMSNorm(x)``:

- **Latent attention.** ``q = h W_q`` -> heads of ``[q_nope | q_rope]``;
  ``[c | k_r] = h W_kva``; ``c = RMSNorm(c)``; rotary (YaRN) on
  `q_rope` and on the ONE `k_r` all heads share. What a token leaves in
  the cache is the row ``[c | rot(k_r)]`` (``kv_lora_rank +
  qk_rope_head_dim`` wide, no head axis; stored padded with zeros to a
  multiple of 128 lanes, `LatentMoEConfig.cache_row_stored`). Expanded
  form: ``[k_nope | v] = c W_kvb``, ``score = (q_nope . k_nope + q_rope
  . k_r) * scale``.
  Absorbed form, the same numbers: ``q_lat = q_nope W_kvb,k^T``,
  ``score = (q_lat . c + q_rope . k_r) * scale``, ``ctx = P c``, ``o =
  ctx W_kvb,v``. A full forward (no cache) runs the expanded form; the
  serving step runs the absorbed form over the paged latent pool as an
  online-softmax loop over key tiles read through the block table, as
  far as the batch's longest live row (`latent_attend_paged`): no
  ``[slots, heads, chunk, max_seq]`` tensor exists.
- **Experts.** ``s = sigmoid(h W_r)`` over ALL `router_experts`;
  ``sel = top_k(s + bias)`` (the bias selects only); ``w = s[sel] /
  sum(s[sel]) * routed_scaling_factor``; ``y = sum_i w_i SwiGLU_i(h) +
  SwiGLU_shared(h)``. `HeldExperts` is told its share ``(ep_rank,
  ep_size)``: it holds experts ``[ep_rank * n, (ep_rank + 1) * n)``,
  routes over all of them and computes the picks that land on its own;
  what absent experts would have added is left out (their chips would
  add it in a deployment). The grouped product (`grouped_product`) runs
  over the picks sorted by held expert: padding columns of a serving
  step and picks of absent experts sort behind every group and are
  multiplied by nothing. A program lowered for a TPU runs JAX's Pallas
  `megablox.gmm` on bfloat16 operands, one turn a (non-empty expert,
  row tile) pair with tiles chosen from the product's shape
  (`GMM_TILES`), so a step pays for the bytes of the experts it picked;
  any other platform, dtype or row count runs `lax.ragged_dot`. The
  first `first_k_dense_replace` layers are a plain SwiGLU.

Serving: the model states its cache layout (`cache_layout`: one
``[block_size, cache_row_stored]`` array a layer) and
owns the scatter through the block table and the attention over it
(`paged_forward`); `serving.SlotEngine` carries the arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...core.tensor import Tensor

__all__ = ["LatentMoEConfig", "LatentAttention", "HeldExperts",
           "LatentMoEDecoderLayer", "LatentMoEModel", "LatentMoEForCausalLM",
           "latent_attend_expanded", "latent_attend_paged",
           "latent_scatter"]

#: key positions one turn of the paged attention loop reads
KEY_TILE = 512


class LatentMoEConfig:
    """Sizes under this repo's names (a public `config.json` says
    `num_hidden_layers`, `num_attention_heads`, `num_experts` for what
    is `num_layers`, `num_heads`, `router_experts` here, and
    `max_position_embeddings` for `max_seq_len`). `num_experts` is how
    many routed experts THIS
    model holds (``router_experts // ep_size``), `vocab_size` the rows
    of the embedding and the head it holds."""

    def __init__(self, vocab_size=262144, hidden_size=4096, num_layers=32,
                 num_heads=64, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=16384, moe_intermediate_size=2048,
                 router_experts=128, num_experts=None,
                 num_experts_per_tok=8, num_shared_experts=1,
                 first_k_dense_replace=1, routed_scaling_factor=2.5,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_seq_len=131072, ep_rank=0, ep_size=1,
                 initializer_range=0.02):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.router_experts = int(router_experts)
        self.ep_rank, self.ep_size = int(ep_rank), int(ep_size)
        if self.router_experts % self.ep_size \
                or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"share ({ep_rank}, {ep_size}) does not divide "
                f"{router_experts} routed experts")
        held = self.router_experts // self.ep_size
        if num_experts is not None and int(num_experts) != held:
            raise ValueError(
                f"num_experts {num_experts} held, but {router_experts} "
                f"routed experts over {ep_size} shares is {held}")
        self.num_experts = held
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.num_shared_experts = int(num_shared_experts)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {})
        self.max_seq_len = int(max_seq_len)
        self.initializer_range = float(initializer_range)
        self.tie_word_embeddings = False

    @property
    def cache_row(self):
        """Columns of one token's cache row: ``[c | rot(k_r)]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_stored(self):
        """Columns a pool keeps for a row: `cache_row` rounded up as
        `paging.stored_width` says, the rest zeros."""
        from ...serving.paging import stored_width

        return stored_width(self.cache_row)


def _v(x):
    return x._value if isinstance(x, Tensor) else x


# -- attention, as functions of arrays ----------------------------------------


def latent_attend_expanded(q_nope, q_rope, c, k_r, w_kvb, scale):
    """Causal attention of ``[b, s]`` tokens over themselves, expanded
    form. `q_nope` ``[b, s, nh, dn]``, `q_rope` ``[b, s, nh, dr]`` and
    `k_r` ``[b, s, dr]`` already rotated, `c` ``[b, s, r]`` already
    normed, `w_kvb` ``[r, nh * (dn + dv)]``. Returns ``[b, s, nh, dv]``.
    Scores and softmax in float32."""
    import jax
    import jax.numpy as jnp

    b, s, nh, dn = q_nope.shape
    kv = (c @ w_kvb).reshape(b, s, nh, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    f32 = jnp.float32
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                        preferred_element_type=f32) \
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r,
                     preferred_element_type=f32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, scores * scale, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=f32).astype(q_nope.dtype)


def latent_scatter(pool, rows, tables, t_idx):
    """Write this step's cache rows ``[b, s, width]`` at positions
    `t_idx` ``[b, s]`` through the block tables into `pool`
    ``[num_blocks, block_size, width]``; positions past a slot's table
    (padding) go to the null block. The scatter indexes the pool's two
    leading axes, so a donated pool is updated in place."""
    import jax.numpy as jnp

    bs = pool.shape[1]
    s_max = tables.shape[1] * bs
    safe_t = jnp.minimum(t_idx, s_max - 1)
    blk = jnp.where(t_idx >= s_max, 0,
                    tables[jnp.arange(rows.shape[0])[:, None],
                           safe_t // bs])
    return pool.at[blk, safe_t % bs].set(rows.astype(pool.dtype))


def latent_attend_paged(q_cat, pool, tables, t_idx, rank, scale):
    """Absorbed attention of a serving step's columns over the paged
    latent pool. `q_cat` ``[b, s, nh, width]`` is ``[q_lat |
    rot(q_rope) | 0]``, `pool` ``[num_blocks, block_size, width]`` already
    holds this step's rows, `tables` ``[b, blocks_per_slot]``, `t_idx`
    ``[b, s]`` each column's position. Returns the context in latent
    space, ``[b, s, nh, r]`` float32 (the caller applies ``W_kvb,v``).

    An online-softmax loop over tiles of `KEY_TILE` positions, each
    gathered through the table, that stops behind the batch's longest
    live row: the largest score tensor is ``[b, s * nh, KEY_TILE]``.
    A key is admitted when its position is <= the column's own, which
    also hides stale rows of recycled blocks and the null block."""
    import jax.numpy as jnp
    from jax import lax

    b, s, nh, width = q_cat.shape
    bs = pool.shape[1]
    per_tile = max(KEY_TILE // bs, 1)
    tile = per_tile * bs
    n_tiles_max = -(-tables.shape[1] // per_tile)
    pad = n_tiles_max * per_tile - tables.shape[1]
    if pad:
        tables = jnp.pad(tables, ((0, 0), (0, pad)))   # the null block
    q = q_cat.reshape(b, s * nh, width)
    q_pos = jnp.repeat(t_idx, nh, axis=1)              # [b, s * nh]
    f32 = jnp.float32

    def body(j, carry):
        m, l, acc = carry
        blocks = lax.dynamic_slice_in_dim(tables, j * per_tile, per_tile,
                                          axis=1)
        rows = pool[blocks].reshape(b, tile, width)
        sc = jnp.einsum("bqd,bkd->bqk", q, rows.astype(q.dtype),
                        preferred_element_type=f32) * scale
        k_pos = j * tile + jnp.arange(tile)
        sc = jnp.where(k_pos[None, None, :] <= q_pos[:, :, None], sc,
                       -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqk,bkc->bqc", p.astype(rows.dtype), rows[..., :rank],
            preferred_element_type=f32)
        return m_new, l, acc

    n_tiles = jnp.minimum(jnp.max(t_idx) // tile + 1, n_tiles_max)
    init = (jnp.full((b, s * nh), -1e30, f32),
            jnp.zeros((b, s * nh), f32),
            jnp.zeros((b, s * nh, rank), f32))
    _, l, acc = lax.fori_loop(0, n_tiles, body, init)
    return (acc / l[..., None]).reshape(b, s, nh, rank)


class LatentAttention(nn.Layer):
    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.nope, self.rope_dim = c.qk_nope_head_dim, c.qk_rope_head_dim
        self.v_dim, self.rank = c.v_head_dim, c.kv_lora_rank
        init = nn.initializer.Normal(std=c.initializer_range)
        h, nh = c.hidden_size, c.num_heads
        self.q_proj = nn.Linear(h, nh * (self.nope + self.rope_dim),
                                weight_attr=init, bias_attr=False)
        self.kv_a_proj = nn.Linear(h, self.rank + self.rope_dim,
                                   weight_attr=init, bias_attr=False)
        self.kv_a_norm = nn.RMSNorm(self.rank, epsilon=c.rms_norm_eps)
        self.kv_b_proj = nn.Linear(self.rank,
                                   nh * (self.nope + self.v_dim),
                                   weight_attr=init, bias_attr=False)
        self.o_proj = nn.Linear(nh * self.v_dim, h, weight_attr=init,
                                bias_attr=False)
        self.rotary = nn.RotaryEmbedding(self.rope_dim, c.rope_theta,
                                         c.rope_scaling)
        self.scale = (self.nope + self.rope_dim) ** -0.5 \
            * self.rotary.attention_scale

    def _project(self, x, positions):
        """``q_nope, rot(q_rope), c, rot(k_r)`` of hidden rows
        ``[b, s, H]`` at `positions` ``[b, s]``."""
        b, s, _ = x.shape
        q = _v(self.q_proj(Tensor(x))).reshape(b, s, self.num_heads, -1)
        q_nope, q_rope = q[..., :self.nope], q[..., self.nope:]
        ckr = _v(self.kv_a_proj(Tensor(x)))
        c = _v(self.kv_a_norm(Tensor(ckr[..., :self.rank])))
        q_rope = self.rotary(q_rope, positions)
        k_r = self.rotary(ckr[..., self.rank:], positions, heads=False)
        return q_nope, q_rope, c, k_r

    def forward(self, x, positions):
        """Full causal forward of ``[b, s, H]`` (no cache), expanded
        form."""
        import jax

        xv = _v(x)
        b, s, _ = xv.shape
        q_nope, q_rope, c, k_r = self._project(xv, _v(positions))
        with jax.named_scope("latent.attend"):
            o = latent_attend_expanded(q_nope, q_rope, c, k_r,
                                       _v(self.kv_b_proj.weight),
                                       self.scale)
        return self.o_proj(Tensor(o.reshape(b, s, -1)))

    def forward_paged(self, x, pos, tables, pool):
        """One serving step's columns ``[b, s, H]``, slot `b` starting
        at position ``pos[b]``: this step's cache rows scatter through
        `tables` into `pool` (positions past the table, i.e. padding,
        into the null block), then the absorbed form attends over the
        pool. Returns ``(out [b, s, H], pool)``."""
        import jax
        import jax.numpy as jnp

        b, s, _ = x.shape
        nh, r = self.num_heads, self.rank
        t_idx = pos[:, None] + jnp.arange(s)
        q_nope, q_rope, c, k_r = self._project(x, t_idx)
        # [c | rot(k_r) | 0] and [q_lat | rot(q_rope) | 0]: the pad
        # columns of the stored row meet zeros in the query
        pad = pool.shape[-1] - r - self.rope_dim
        zeros = jnp.zeros((b, s, pad), x.dtype)
        pool = latent_scatter(
            pool, jnp.concatenate([c, k_r, zeros], axis=-1), tables, t_idx)
        w_kvb = _v(self.kv_b_proj.weight).reshape(r, nh, -1)
        with jax.named_scope("latent.attend"):
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope,
                               w_kvb[..., :self.nope])
            q_cat = jnp.concatenate(
                [q_lat, q_rope,
                 jnp.broadcast_to(zeros[:, :, None], (b, s, nh, pad))],
                axis=-1)
            ctx = latent_attend_paged(q_cat, pool, tables, t_idx, r,
                                      self.scale)
            o = jnp.einsum("bshr,rhd->bshd", ctx.astype(x.dtype),
                           w_kvb[..., self.nope:])
        out = _v(self.o_proj(Tensor(o.reshape(b, s, -1))))
        return out, pool


# -- experts ------------------------------------------------------------------

#: rows of one turn of the Pallas grouped product. A group of a few rows
#: pays for a whole tile on the MXU, but its turn is bound by the bytes
#: of its expert, not by the tile's FLOPs (64 and 256 measured within
#: 5 % of 128).
GMM_ROW_TILE = 128
#: elements of an expert's ``[k, n]`` matrix one turn reads: 2 MiB of
#: bfloat16, two of them in flight. The most at which the backward's
#: kernel (`tgmm`, whose float32 accumulator is a whole weight tile)
#: still fits the 16 MiB of fast memory a kernel may use; 4 MiB tiles
#: measured 1-3 % faster forward and are refused backward.
GMM_WEIGHT_TILE = 1024 * 1024
#: ``(k, n) -> (tm, tk, tn)`` of the four products swept on a TPU v5e
#: (PERF.md section 6, PR 36: the gate-up and down products of experts
#: 2304 x 896 and 4096 x 2048 wide, 4,096 sorted rows of which 5-7 %
#: are real): the best measured under `GMM_WEIGHT_TILE`. They are what
#: `gmm_tiling`'s rule gives today; the table keeps the cells' tiles
#: where they were measured if the rule is changed for another shape.
GMM_TILES = {
    (2304, 1792): (128, 2304, 384),
    (896, 2304): (128, 896, 1152),
    (4096, 4096): (128, 4096, 256),
    (2048, 4096): (128, 2048, 512),
}


def gmm_tiling(m, k, n):
    """``(tm, tk, tn)`` of `megablox.gmm` for ``[m, k] x [groups, k,
    n]``. The work is one read of ``k x n`` per (non-empty group, row
    tile) pair, so: the whole `k` where a tile 256 columns wide holds
    it (the accumulator is then written once: a split `k` measured
    5-25 % slower), and `tn` as wide as `GMM_WEIGHT_TILE` allows, in
    whole lanes."""
    del m
    if (k, n) in GMM_TILES:
        return GMM_TILES[k, n]
    tk = min(k, GMM_WEIGHT_TILE // 256)
    tn = n if tk * n <= GMM_WEIGHT_TILE \
        else max(GMM_WEIGHT_TILE // tk // 128, 1) * 128
    return GMM_ROW_TILE, tk, tn


def _ragged_product(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes)


@jax.custom_vjp
def _real_rows_gradient(x, real):
    """`x`, with a gradient that is zero where `real` is false: costs
    the forward nothing (a `where` on the rows was one more pass over
    them a layer)."""
    return x


_real_rows_gradient.defvjp(
    lambda x, real: (x, real),
    lambda real, g: (jnp.where(real, g, 0), None))


def _pallas_product(x, w, sizes, interpret=False):
    """`megablox.gmm` (with its `tgmm` backward). The kernel writes no
    row behind the groups, in its result and in the gradient it hands
    back for `x` alike: the caller masks the first (`routed` does),
    `_real_rows_gradient` the second."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    real = (jnp.arange(x.shape[0]) < sizes.sum())[:, None]
    return megablox.gmm(_real_rows_gradient(x, real), w, sizes, x.dtype,
                        gmm_tiling, interpret=interpret)


def grouped_product(x, w, sizes):
    """``x[rows of group g] @ w[g]`` for rows ``[m, k]`` sorted by
    group, ``w [groups, k, n]`` and ``sizes [groups]`` int32 that may
    sum to less than `m`: float32 accumulation, `x`'s dtype out. Which
    form runs follows what the program is lowered for and the operands:
    bfloat16 rows in whole row tiles on a TPU take the Pallas kernel,
    everything else `lax.ragged_dot`."""
    bf16 = x.dtype == w.dtype == jnp.bfloat16
    if not bf16 or x.shape[0] % gmm_tiling(x.shape[0], *w.shape[1:])[0]:
        return _ragged_product(x, w, sizes)
    return jax.lax.platform_dependent(x, w, sizes, tpu=_pallas_product,
                                      default=_ragged_product)


class HeldExperts(nn.Layer):
    """The routed-expert layer of ONE share of an expert-parallel
    deployment, plus the shared expert every share computes alike.

    ``forward(h, valid=None)`` takes hidden rows ``[..., H]`` and a
    mask of the rows that are real (a serving step's padding columns
    are not) and returns ``(y, rows)``: `y` what this share adds, `rows`
    ``[num_experts]`` int32 the rows of the grouped product each held
    expert computed.

    The scoring rule is the model's (`config.router_scoring`, absent =
    ``"sigmoid"``): ``"sigmoid"`` scores each expert alone and selects
    by score + bias (the latent family's); ``"softmax"`` scores over
    all routed experts and selects the largest, no bias (a parameter
    the layer then does not have). Either way the picked scores are
    normalised to sum 1 and scaled by `routed_scaling_factor`, and the
    grouped product (`grouped_product`: `megablox.gmm` in a program
    lowered for a TPU, `lax.ragged_dot` elsewhere) is the one piece of
    code under both."""

    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        c = config
        self.top_k = c.num_experts_per_tok
        self.held = c.num_experts
        self.first = c.ep_rank * c.num_experts
        self.scaling = c.routed_scaling_factor
        self.inter = c.moe_intermediate_size
        self.scoring = getattr(c, "router_scoring", "sigmoid")
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"router scoring {self.scoring!r} is neither "
                             f"'sigmoid' nor 'softmax'")
        init = nn.initializer.Normal(std=c.initializer_range)
        self.router = nn.Linear(c.hidden_size, c.router_experts,
                                weight_attr=init, bias_attr=False)
        if self.scoring == "sigmoid":
            # the selection bias of auxiliary-loss-free balancing:
            # float32 whatever the weights' dtype, zeros from the seed
            self.router_bias = self.create_parameter(
                [c.router_experts], dtype="float32", is_bias=True)
        self.gate_up = self.create_parameter(
            [self.held, c.hidden_size, 2 * self.inter],
            default_initializer=init)
        self.down = self.create_parameter(
            [self.held, self.inter, c.hidden_size],
            default_initializer=init)
        self.shared = nn.SwiGLU(
            c.hidden_size, self.inter * c.num_shared_experts,
            weight_attr=init) if c.num_shared_experts else None

    def route(self, h):
        """``(sel [T, k] int32, w [T, k] float32)`` over ALL routed
        experts, in float32: a selection made in bfloat16 flips picks
        that tie within its rounding."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        f32 = jnp.float32
        logits = jnp.matmul(h.astype(f32), _v(self.router.weight).astype(f32),
                            precision=lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, sel = lax.top_k(s, self.top_k)
        else:
            s = jax.nn.sigmoid(logits)
            _, sel = lax.top_k(s + _v(self.router_bias), self.top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / w.sum(axis=-1, keepdims=True) * self.scaling
        return sel.astype(jnp.int32), w

    def routed(self, h, valid=None):
        """This share's part of ``sum_i w_i SwiGLU_i(h)`` for rows
        ``[T, H]``."""
        import jax
        import jax.numpy as jnp

        n, k, held = h.shape[0], self.top_k, self.held
        with jax.named_scope("moe.route"):
            sel, w = self.route(h)
            local = sel - self.first
            mine = (local >= 0) & (local < held)
            if valid is not None:
                mine = mine & valid[:, None]
            # picks sorted by held expert; the rest behind every group
            key = jnp.where(mine, local, held).reshape(n * k)
            order = jnp.argsort(key)
            sizes = jnp.bincount(key, length=held + 1)[:held] \
                .astype(jnp.int32)
        with jax.named_scope("moe.experts"):
            x = h[order // k]
            gu = grouped_product(x, _v(self.gate_up), sizes)
            act = jax.nn.silu(gu[:, :self.inter]) * gu[:, self.inter:]
            out = grouped_product(act, _v(self.down), sizes)
            # rows behind the groups are whatever the product left there
            out = jnp.where((jnp.arange(n * k) < sizes.sum())[:, None],
                            out, 0)
            picks = out[jnp.argsort(order)].reshape(n, k, -1)
            y = jnp.einsum("tkh,tk->th", picks.astype(jnp.float32),
                           jnp.where(mine, w, 0.0))
        return y.astype(h.dtype), sizes

    def forward(self, h, valid=None):
        import jax

        hv = _v(h)
        flat = hv.reshape(-1, hv.shape[-1])
        y, rows = self.routed(
            flat, None if valid is None else _v(valid).reshape(-1))
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                y = y + _v(self.shared(Tensor(flat)))
        return y.reshape(hv.shape), rows


class LatentMoEDecoderLayer(nn.Layer):
    def __init__(self, config: LatentMoEConfig, index):
        super().__init__()
        self.input_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)
        self.attn = LatentAttention(config)
        self.post_norm = nn.RMSNorm(config.hidden_size,
                                    epsilon=config.rms_norm_eps)
        self.is_dense = index < config.first_k_dense_replace
        if self.is_dense:
            self.mlp = nn.SwiGLU(
                config.hidden_size, config.intermediate_size,
                weight_attr=nn.initializer.Normal(
                    std=config.initializer_range))
        else:
            self.mlp = HeldExperts(config)

    def _feed_forward(self, x, valid):
        h = self.post_norm(Tensor(x))
        if self.is_dense:
            return x + _v(self.mlp(h)), None
        y, rows = self.mlp(h, valid)
        return x + y, rows

    def forward(self, x, positions):
        xv = _v(x)
        xv = xv + _v(self.attn(self.input_norm(Tensor(xv)), positions))
        return self._feed_forward(xv, None)

    def forward_paged(self, x, pos, valid, tables, pool):
        a, pool = self.attn.forward_paged(
            _v(self.input_norm(Tensor(x))), pos, tables, pool)
        x, rows = self._feed_forward(x + a, valid)
        return x, rows, pool


class LatentMoEModel(nn.Layer):
    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(std=config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=init)
        self.layers = nn.LayerList(
            [LatentMoEDecoderLayer(config, i)
             for i in range(config.num_layers)])
        self.final_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None):
        """Full causal forward ``[b, s]`` -> ``(hidden [b, s, H],
        expert rows [expert layers, num_experts])``. Inference only:
        array math, no tape."""
        import jax.numpy as jnp

        ids = _v(input_ids)
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(ids.shape[-1], dtype=jnp.int32), ids.shape)
        x = _v(self.embed_tokens(Tensor(ids)))
        rows = []
        for layer in self.layers:
            x, r = layer(x, _v(position_ids))
            if r is not None:
                rows.append(r)
        return self.final_norm(Tensor(x)), _stack_rows(rows, self.config)


def _stack_rows(rows, config):
    import jax.numpy as jnp

    if not rows:
        return jnp.zeros((0, config.num_experts), jnp.int32)
    return jnp.stack(rows)


class LatentMoEForCausalLM(nn.Layer):
    """Untied head over the vocabulary rows held here."""

    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        self.config = config
        self.model = LatentMoEModel(config)
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
        from ...serving.paging import CacheLayout

        return CacheLayout(
            "tc", (("latent", (self.config.cache_row_stored,)),),
            self.config.num_layers)

    def serving_gauges(self):
        c = self.config
        return {"experts_held": float(c.num_experts)}

    def paged_forward(self, tok, pos, nvalid, tables, pools):
        """One serving step: `tok` ``[slots, chunk]``, slot `b`'s
        columns at positions ``pos[b] + column``, the first
        ``nvalid[b]`` of them real; `pools` one ``(latent,)`` a layer.
        Returns ``(hidden [slots, chunk, H], pools, aux)`` with aux
        ``{"expert_rows": [expert layers, num_experts] int32}``."""
        import jax.numpy as jnp

        m = self.model
        valid = jnp.arange(tok.shape[1])[None, :] < nvalid[:, None]
        x = _v(m.embed_tokens(Tensor(tok)))
        new_pools, rows = [], []
        for layer, (pool,) in zip(m.layers, pools):
            x, r, pool = layer.forward_paged(x, pos, valid, tables, pool)
            new_pools.append((pool,))
            if r is not None:
                rows.append(r)
        h = _v(m.final_norm(Tensor(x)))
        return h, new_pools, {"expert_rows": _stack_rows(rows, self.config)}
