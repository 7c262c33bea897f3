"""Decoder family that mixes linear-attention and full-attention layers.

The block of `model_type` ``olmo_hybrid`` in a public `config.json`:
`layer_types` says of each layer whether its mixer is softmax attention
over every earlier token (``full_attention``) or a Gated DeltaNet
(``linear_attention``, the `linear_*` keys; Yang, Kautz, Hatamizadeh,
arXiv:2412.06464), which keeps a fixed-size recurrent state in place
of rows a token. Every layer, whatever its mixer (the Olmo family's
residual path, the norm AFTER the mixer and none before it):

    x <- x + RMSNorm(mixer(x));   x <- x + RMSNorm(SwiGLU(x))

and a final RMSNorm before the untied head. The residual stream is
float32 whatever the weights' dtype; a large product takes its inputs
in the weights' dtype and hands on float32.

- **Full attention.** ``[q | k | v] = x W_in``; ``q = RMSNorm(q)``,
  ``k = RMSNorm(k)`` over the whole projection before the split into
  heads; causal softmax attention, scale ``head_dim ** -0.5``; ``W_o``.
  No rotary embedding (the recurrent layers carry position). K and V
  rows go to the paged pool as GPT's do and the attention over them is
  GPT's key-tile loop (`gpt._attend_tiles`).
- **Linear attention**, heads of ``(d_k, d_v)``, per token ``t``:
  ``[q | k | v] = SiLU(filter(x W_in))``, a causal depthwise filter of
  `linear_conv_kernel_dim` taps down every column; ``q, k`` scaled to
  unit length a head and ``q`` by ``d_k ** -0.5``; ``[a | b] = x
  W_ab``, ``beta = 2 sigmoid(b)`` (`linear_allow_neg_eigval`: the
  factor 2), ``g = -exp(A_log) softplus(a + dt_bias)``, ``alpha =
  exp(g)``; the state ``S [d_k, d_v]`` a head, float32, from zero:

      S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  then ``y = concat_h(RMSNorm(o_h) SiLU((x W_gate)_h)) W_o``.

  Two forms of the recurrence, which agree (tests/test_hybrid_linear.py):
  `gated_delta_step` is the definition, one token at a time (the eager
  `forward` scans it); `gated_delta_chunk` takes a whole chunk of
  columns at once (the intra-chunk triangular system, then one state
  update) and is what the serving step runs for every row, a decoding
  row being a chunk with one valid column. A padding column takes
  ``beta = 0, g = 0`` and feeds the filter nothing, so it leaves the
  state and the filter's tail as they were.

Serving: the model states its cache layout (`cache_layout`): K and V
pools for the full layers, and per-slot STATE arrays for the linear
ones (``S [heads, d_k, d_v]`` float32 and the filter's tail, the last
``taps - 1`` inputs of every column); `paged_forward` takes and returns
both, `serving.SlotEngine` carries the arrays and snapshots the state.
"""

from __future__ import annotations

import math

from ... import nn
from ...core.tensor import Tensor
from .gpt import _attend_tiles, key_tiling

__all__ = ["HybridLinearConfig", "GatedDeltaNet", "HybridFullAttention",
           "HybridDecoderLayer", "HybridLinearModel",
           "HybridLinearForCausalLM", "gated_delta_chunk",
           "gated_delta_step", "filter_chunk"]

LAYER_PERIOD = ("linear_attention", "linear_attention", "linear_attention",
                "full_attention")
#: under the root of the L2 norms of q and k
NORM_EPS = 1e-6


class HybridLinearConfig:
    """Sizes under this repo's names (a public `config.json` says
    `num_hidden_layers`, `num_attention_heads`, `linear_num_value_heads`
    for what is `num_layers`, `num_heads`, `linear_num_heads` here, and
    `max_position_embeddings` for `max_seq_len`). `layer_types` longer
    than `num_layers` is cut to its first entries: the layers held."""

    def __init__(self, vocab_size=100352, hidden_size=3840, num_layers=32,
                 num_heads=30, intermediate_size=11008, layer_types=None,
                 linear_num_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
                 max_seq_len=65536, initializer_range=0.02):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        if self.hidden_size % self.num_heads:
            raise ValueError(f"{num_heads} heads do not divide a hidden "
                             f"size of {hidden_size}")
        self.intermediate_size = int(intermediate_size)
        if layer_types is None:
            layer_types = LAYER_PERIOD * -(-self.num_layers // 4)
        if len(layer_types) < self.num_layers:
            raise ValueError(f"{len(layer_types)} layer types for "
                             f"{num_layers} layers")
        self.layer_types = tuple(layer_types[:self.num_layers])
        unknown = set(self.layer_types) - set(LAYER_PERIOD)
        if unknown:
            raise ValueError(f"layer types {sorted(unknown)} are not of "
                             f"{sorted(set(LAYER_PERIOD))}")
        self.linear_num_heads = int(linear_num_heads)
        self.linear_key_head_dim = int(linear_key_head_dim)
        self.linear_value_head_dim = int(linear_value_head_dim)
        self.linear_conv_kernel_dim = int(linear_conv_kernel_dim)
        self.linear_allow_neg_eigval = bool(linear_allow_neg_eigval)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_seq_len)
        self.initializer_range = float(initializer_range)
        self.tie_word_embeddings = False

    @property
    def filter_columns(self):
        """Columns the depthwise filter runs down: q, k and v."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)

    def count(self, kind):
        return sum(1 for t in self.layer_types if t == kind)


def _v(x):
    return x._value if isinstance(x, Tensor) else x


def stored_heads(heads):
    """Heads a K or V pool keeps for a model of `heads`: rounded up to
    the 16 rows of the chip's bfloat16 tiles, the rest zeros (as
    `paging.stored_width` rounds a headless row's columns)."""
    return -(-int(heads) // 16) * 16


def _proj(linear, x):
    """Rows `x` through a bias-free `nn.Linear`: the inputs in the
    weights' dtype, the product accumulated and handed on in float32
    (the residual stream and what is added to it stay float32: with
    weights drawn from a seed the decay gates amplify every rounding
    of an activation, PERF.md section 6, PR 33)."""
    import jax.numpy as jnp

    w = _v(linear.weight)
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _swiglu(mlp, x):
    """`nn.SwiGLU`'s weights with float32 between its two products."""
    import jax

    gu = _proj(mlp.gate_up_proj, x)
    i = mlp.intermediate_size
    return _proj(mlp.down_proj, jax.nn.silu(gu[..., :i]) * gu[..., i:])


# -- the gated delta rule, as functions of arrays -----------------------------


def gated_delta_step(q, k, v, g, beta, S):
    """One token of the recurrence, as it is defined: `q`, `k`
    ``[..., d_k]``, `v` ``[..., d_v]``, `g` (log alpha) and `beta`
    ``[...]``, `S` ``[..., d_k, d_v]`` float32. Returns ``(o [...,
    d_v], S)``."""
    import jax.numpy as jnp

    b = beta[..., None, None]
    kS = jnp.einsum("...k,...kv->...v", k, S)
    S = jnp.exp(g)[..., None, None] * (
        S - b * k[..., :, None] * kS[..., None, :]) \
        + b * k[..., :, None] * v[..., None, :]
    return jnp.einsum("...kv,...k->...v", S, q), S


def solve_unit_lower(A, rhs, block=16):
    """`U` with ``(I + A) U = rhs`` for strictly lower-triangular `A`
    ``[..., C, C]`` and `rhs` ``[..., C, n]``, by forward substitution
    in two levels: the diagonal blocks of `block` rows are inverted row
    by row (unrolled: each row of an inverse is the unit row less the
    rows before it weighted by `A`'s), then the block rows are
    substituted in order, a product against the `U` found so far and
    one by the block's inverse. Every step is a batched elementwise
    pass or a small product, where the TPU's own triangular solve
    inverts one 64 x 64 matrix after another (16 ms of a 62 ms step at
    the published sizes: PERF.md, PR 33). A `C` that is no multiple of
    `block` is one block."""
    import jax.numpy as jnp

    C = A.shape[-1]
    if C % block:
        block = C
    eye = jnp.eye(block, dtype=A.dtype)
    done = []
    for b in range(C // block):
        rows = slice(b * block, (b + 1) * block)
        D = A[..., rows, rows]
        inverse = [jnp.broadcast_to(eye[0], D.shape[:-2] + (block,))]
        for r in range(1, block):
            before = jnp.stack(inverse, axis=-2)            # [.., r, block]
            inverse.append(eye[r] - jnp.einsum(
                "...i,...ij->...j", D[..., r, :r], before))
        T = jnp.stack(inverse, axis=-2)
        r_b = rhs[..., rows, :]
        if done:
            r_b = r_b - jnp.einsum("...ti,...iv->...tv",
                                   A[..., rows, :b * block],
                                   jnp.concatenate(done, axis=-2))
        done.append(jnp.einsum("...ti,...iv->...tv", T, r_b))
    return jnp.concatenate(done, axis=-2)


def gated_delta_chunk(q, k, v, g, beta, S):
    """A chunk of `C` tokens at once: `q`, `k` ``[..., C, d_k]``, `v`
    ``[..., C, d_v]``, `g`, `beta` ``[..., C]``, `S` ``[..., d_k, d_v]``
    float32; the same numbers as `C` calls of `gated_delta_step`.

    With ``G_t = g_1 + .. + g_t`` and ``u_t = beta_t (v_t - alpha_t
    S_{t-1}^T k_t)`` the recurrence reads ``S_t = alpha_t S_{t-1} + k_t
    u_t^T``, so ``S_t = e^{G_t} S_0 + sum_{i<=t} e^{G_t - G_i} k_i
    u_i^T`` and the rows `u` solve the unit lower-triangular system

        (I + A) U = diag(beta) (V - diag(e^G) K S_0),
        A[t, i] = beta_t e^{G_t - G_i} k_t . k_i   (i < t)

    after which ``O = diag(e^G) Q S_0 + (e^{G_t - G_i} q_t . k_i)_{i<=t}
    U`` and ``S_C = e^{G_C} S_0 + (e^{G_C - G_i} k_i)^T U``. Every
    exponent is of a difference that is <= 0. A column with ``beta = 0,
    g = 0`` has ``u = 0`` and moves nothing: that is a padding
    column."""
    import jax.numpy as jnp

    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-1)
    at_or_before = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(at_or_before,
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    from_start = jnp.exp(G)[..., None]
    A = jnp.tril(beta[..., :, None] * decay
                 * jnp.einsum("...td,...id->...ti", k, k), -1)
    # the old state is read once, by q and k together: a second reader
    # the compiler may schedule behind the update, and then it copies
    # the state array whole (tests/test_v5e_compile.py)
    through = from_start[..., None, :, :] * jnp.einsum(
        "...td,...dv->...tv", jnp.concatenate([q, k], axis=-2),
        S).reshape(q.shape[:-2] + (2, C, S.shape[-1]))
    rhs = beta[..., None] * (v - through[..., 1, :, :])
    U = solve_unit_lower(A, rhs)
    o = through[..., 0, :, :] + jnp.einsum(
        "...ti,...iv->...tv",
        decay * jnp.einsum("...td,...id->...ti", q, k), U)
    to_end = jnp.exp(G[..., -1:] - G)[..., None]
    S = jnp.exp(G[..., -1])[..., None, None] * S \
        + jnp.einsum("...id,...iv->...dv", k * to_end, U)
    return o, S


def filter_chunk(x, tail, taps, nvalid):
    """The causal depthwise filter over a chunk: `x` ``[b, C, c]`` the
    chunk's inputs, the first ``nvalid[b]`` of them real, `tail` ``[b,
    n - 1, c]`` the inputs before it, `taps` ``[n, c]`` (the last on
    the current token). Returns ``(y [b, C, c] float32, tail)``: the
    new tail is the last ``n - 1`` REAL inputs, so padding feeds the
    filter nothing."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n, C = taps.shape[0], x.shape[1]
    seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wide = seen.astype(jnp.float32)
    y = sum(taps[j].astype(jnp.float32) * wide[:, j:j + C]
            for j in range(n))
    tail = jax.vmap(lambda rows, at: lax.dynamic_slice_in_dim(
        rows, at, n - 1, axis=0))(seen, nvalid)
    return y, tail


# -- the two mixers -----------------------------------------------------------


class _DecayInit(nn.initializer.Initializer):
    """The Gated DeltaNet layer's published initialiser of its decay:
    ``A_log = log A`` with `A` uniform in (0, 16], and ``dt_bias =
    softplus^-1(dt)`` with `dt` log-uniform in [0.001, 0.1]."""

    def __init__(self, what):
        self.what = what

    def __call__(self, shape, dtype="float32"):
        import jax.numpy as jnp

        u = _v(nn.initializer.Uniform(0.0, 1.0)(shape, "float32"))
        if self.what == "A_log":
            return jnp.log(16.0 * (1.0 - u))          # A in (0, 16]
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                     + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1


class GatedDeltaNet(nn.Layer):
    def __init__(self, config: HybridLinearConfig):
        super().__init__()
        c = config
        self.nh, self.dk = c.linear_num_heads, c.linear_key_head_dim
        self.dv, self.taps = c.linear_value_head_dim, \
            c.linear_conv_kernel_dim
        self.beta_scale = 2.0 if c.linear_allow_neg_eigval else 1.0
        init = nn.initializer.Normal(std=c.initializer_range)
        h, cols = c.hidden_size, c.filter_columns
        self.in_proj = nn.Linear(h, cols, weight_attr=init,
                                 bias_attr=False)
        self.conv_weight = self.create_parameter(
            [self.taps, cols], default_initializer=init)
        self.ab_proj = nn.Linear(h, 2 * self.nh, weight_attr=init,
                                 bias_attr=False)
        # the decay's own parameters stay float32 whatever the weights
        self.A_log = self.create_parameter(
            [self.nh], dtype="float32",
            default_initializer=_DecayInit("A_log"))
        self.dt_bias = self.create_parameter(
            [self.nh], dtype="float32",
            default_initializer=_DecayInit("dt_bias"))
        self.gate_proj = nn.Linear(h, self.nh * self.dv, weight_attr=init,
                                   bias_attr=False)
        self.out_norm = nn.RMSNorm(self.dv, epsilon=c.rms_norm_eps)
        self.o_proj = nn.Linear(self.nh * self.dv, h, weight_attr=init,
                                bias_attr=False)

    def state_arrays(self):
        """What a slot keeps of this layer, ``(name, shape, dtype)``:
        the float32 state and the filter's tail in the weights' dtype."""
        w = _v(self.in_proj.weight)
        return (("S", (self.nh, self.dk, self.dv), "float32"),
                ("tail", (self.taps - 1, w.shape[1]), str(w.dtype)))

    def _filter_inputs(self, x):
        """``x W_in`` in the weights' dtype: what the filter reads and
        what its tail keeps of a chunk for the next."""
        w = _v(self.in_proj.weight)
        return _v(self.in_proj(Tensor(x.astype(w.dtype))))

    def _heads(self, qkv):
        """Filtered columns ``[b, s, c]`` float32 to ``q, k, v`` of
        ``[b, heads, s, d]``: unit q and k, q scaled."""
        import jax
        import jax.numpy as jnp

        b, s, _ = qkv.shape
        nh, dk = self.nh, self.dk
        qkv = jax.nn.silu(qkv)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + NORM_EPS)

        q = unit(qkv[..., :nh * dk].reshape(b, s, nh, dk)) * dk ** -0.5
        k = unit(qkv[..., nh * dk:2 * nh * dk].reshape(b, s, nh, dk))
        v = qkv[..., 2 * nh * dk:].reshape(b, s, nh, self.dv)
        return tuple(jnp.swapaxes(a, 1, 2) for a in (q, k, v))

    def _decay(self, x):
        """``(g, beta)`` of rows `x` ``[b, s, H]``, ``[b, heads, s]``
        float32 each."""
        import jax
        import jax.numpy as jnp

        # 60 columns: float32 through and through, as a router's scores
        ab = jnp.matmul(x.astype(jnp.float32),
                        _v(self.ab_proj.weight).astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
        g = -jnp.exp(_v(self.A_log)) * jax.nn.softplus(
            ab[..., :self.nh] + _v(self.dt_bias))
        beta = self.beta_scale * jax.nn.sigmoid(ab[..., self.nh:])
        return jnp.swapaxes(g, 1, 2), jnp.swapaxes(beta, 1, 2)

    def _out(self, x, o):
        """`o` ``[b, heads, s, d_v]`` float32 through the per-head norm,
        the gate and ``W_o``."""
        import jax
        import jax.numpy as jnp

        b, s, _ = x.shape
        o = jnp.swapaxes(o, 1, 2)
        gate = _proj(self.gate_proj, x).reshape(b, s, self.nh, self.dv)
        o = _v(self.out_norm(Tensor(o))) * jax.nn.silu(gate)
        return _proj(self.o_proj, o.reshape(b, s, -1))

    def forward(self, x):
        """Full forward of ``[b, s, H]`` from an empty state: the
        one-token form, scanned."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        xv = _v(x)
        b = xv.shape[0]
        inputs = self._filter_inputs(xv)
        zeros = jnp.zeros((b, self.taps - 1, inputs.shape[-1]),
                          inputs.dtype)
        with jax.named_scope("gdn.filter"):
            qkv, _ = filter_chunk(
                inputs, zeros, _v(self.conv_weight),
                jnp.full((b,), xv.shape[1], jnp.int32))
        q, k, v = self._heads(qkv)
        g, beta = self._decay(xv)

        def step(S, t):
            o, S = gated_delta_step(*t, S)
            return S, o

        with jax.named_scope("gdn.step"):
            S0 = jnp.zeros((b, self.nh, self.dk, self.dv), jnp.float32)
            per_token = tuple(jnp.moveaxis(a, 2, 0)
                              for a in (q, k, v, g, beta))
            _, o = lax.scan(step, S0, per_token)
        return Tensor(self._out(xv, jnp.moveaxis(o, 0, 2)))

    def forward_paged(self, x, valid, nvalid, state):
        """One serving step's columns ``[b, s, H]``, `valid` ``[b, s]``
        marking the real ones (the first ``nvalid[b]``); `state` this
        layer's ``(S, tail)`` a slot. The chunked form. Returns ``(out
        [b, s, H], state)``."""
        import jax
        import jax.numpy as jnp

        S, tail = state
        with jax.named_scope("gdn.filter"):
            qkv, tail = filter_chunk(self._filter_inputs(x), tail,
                                     _v(self.conv_weight), nvalid)
        q, k, v = self._heads(qkv)
        g, beta = self._decay(x)
        real = valid[:, None, :]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
        # small float32 products round the float32 state's neighbours:
        # in one bfloat16 pass the first layer's state ends 0.45-0.50 %
        # from the reference's where a bfloat16 STATE ends 0.9-1.2 %;
        # at the highest precision 0.27 % (PERF.md section 6, PR 33)
        with jax.named_scope("gdn.chunk"), \
                jax.default_matmul_precision("highest"):
            o, S = gated_delta_chunk(q, k, v, g, beta, S)
        return self._out(x, o), (S, tail)


class HybridFullAttention(nn.Layer):
    def __init__(self, config: HybridLinearConfig):
        super().__init__()
        c = config
        self.nh = c.num_heads
        init = nn.initializer.Normal(std=c.initializer_range)
        h = c.hidden_size
        self.in_proj = nn.Linear(h, 3 * h, weight_attr=init,
                                 bias_attr=False)
        self.q_norm = nn.RMSNorm(h, epsilon=c.rms_norm_eps)
        self.k_norm = nn.RMSNorm(h, epsilon=c.rms_norm_eps)
        self.o_proj = nn.Linear(h, h, weight_attr=init, bias_attr=False)

    def _project(self, x):
        """``q, k, v`` of ``[b, s, H]`` (head-major columns) from rows
        ``[b, s, H]``: the QK-norm is over the whole projection, before
        any split into heads."""
        h = x.shape[-1]
        qkv = _proj(self.in_proj, x)
        return (_v(self.q_norm(Tensor(qkv[..., :h]))),
                _v(self.k_norm(Tensor(qkv[..., h:2 * h]))),
                qkv[..., 2 * h:])

    def forward(self, x):
        """Full causal forward of ``[b, s, H]`` (no cache)."""
        import jax
        import jax.numpy as jnp

        xv = _v(x)
        b, s, _ = xv.shape
        q, k, v = (a.astype(jnp.float32).reshape(b, s, self.nh, -1)
                   for a in self._project(xv))
        with jax.named_scope("attn.full"):
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                * q.shape[-1] ** -0.5
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return Tensor(_proj(self.o_proj, o.reshape(b, s, -1)))

    def forward_paged(self, x, pos, tables, pools):
        """One serving step's columns ``[b, s, H]``, slot `b` starting
        at position ``pos[b]``: K and V rows scatter through `tables`
        into `pools` ``(k, v)`` of ``[num_blocks, block_size, stored
        heads, d]`` (positions past the table, i.e. padding, into the
        null block), then GPT's key-tile loop attends over them. The
        pools keep `stored_heads` heads (`cache_layout`): the heads
        past the model's own hold zeros, meet zero queries and are cut
        from the result. Returns ``(out [b, s, H], pools, key tiles
        run)``."""
        import jax
        import jax.numpy as jnp

        k_pool, v_pool = pools
        b, s, _ = x.shape
        bs, mb = k_pool.shape[1], tables.shape[1]
        q, k, v = self._project(x)
        t_idx = pos[:, None] + jnp.arange(s)
        safe_t = jnp.minimum(t_idx, mb * bs - 1)
        blk = jnp.where(t_idx >= mb * bs, 0,
                        tables[jnp.arange(b)[:, None], safe_t // bs])
        off = safe_t % bs
        spare = k_pool.shape[2] - self.nh

        def heads(a):
            a = a.reshape(b, s, self.nh, -1)
            return jnp.pad(a, ((0, 0), (0, 0), (0, spare), (0, 0)))

        k_pool = k_pool.at[blk, off].set(heads(k).astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(heads(v).astype(v_pool.dtype))
        per_tile, _ = key_tiling(mb, bs)
        with jax.named_scope("attn.full"):
            o, n_tiles = _attend_tiles(
                jnp.swapaxes(heads(q), 1, 2).astype(jnp.float32), k_pool,
                v_pool, tables, t_idx, per_tile)
        o = jnp.swapaxes(o[:, :self.nh], 1, 2).reshape(b, s, -1)
        return _proj(self.o_proj, o), (k_pool, v_pool), n_tiles


# -- the decoder --------------------------------------------------------------


class HybridDecoderLayer(nn.Layer):
    def __init__(self, config: HybridLinearConfig, index):
        super().__init__()
        self.is_linear = config.layer_types[index] == "linear_attention"
        self.mixer = GatedDeltaNet(config) if self.is_linear \
            else HybridFullAttention(config)
        self.mixer_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)
        self.mlp = nn.SwiGLU(
            config.hidden_size, config.intermediate_size,
            weight_attr=nn.initializer.Normal(
                std=config.initializer_range))
        self.mlp_norm = nn.RMSNorm(config.hidden_size,
                                   epsilon=config.rms_norm_eps)

    def _residuals(self, x, mixed):
        x = x + _v(self.mixer_norm(Tensor(mixed))).astype(x.dtype)
        return x + _v(self.mlp_norm(Tensor(_swiglu(self.mlp, x)))) \
            .astype(x.dtype)

    def forward(self, x):
        xv = _v(x)
        return self._residuals(xv, _v(self.mixer(Tensor(xv))))


class HybridLinearModel(nn.Layer):
    def __init__(self, config: HybridLinearConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(std=config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=init)
        self.layers = nn.LayerList(
            [HybridDecoderLayer(config, i)
             for i in range(config.num_layers)])
        self.final_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)

    def embed(self, ids):
        """The residual stream's first rows: float32 from here on,
        whatever the weights' dtype."""
        import jax.numpy as jnp

        return _v(self.embed_tokens(Tensor(ids))).astype(jnp.float32)

    def forward(self, input_ids):
        """Full causal forward ``[b, s]`` -> hidden ``[b, s, H]``.
        Inference only: array math, no tape."""
        x = self.embed(_v(input_ids))
        for layer in self.layers:
            x = layer(x)
        return self.final_norm(Tensor(x))


class HybridLinearForCausalLM(nn.Layer):
    """Untied head over the whole vocabulary."""

    def __init__(self, config: HybridLinearConfig):
        super().__init__()
        self.config = config
        self.model = HybridLinearModel(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=nn.initializer.Normal(
                std=config.initializer_range))

    def forward(self, input_ids):
        return self.logits(self.model(input_ids))

    def logits(self, h):
        """Hidden rows ``[..., H]`` to float32 logits ``[..., V]``: the
        product accumulates in float32 and is not rounded to the
        weights' dtype on the way out."""
        import jax.numpy as jnp

        w = _v(self.lm_head.weight)
        out = jnp.matmul(_v(h).astype(w.dtype), w,
                         preferred_element_type=jnp.float32)
        return Tensor(out) if isinstance(h, Tensor) else out

    # -- the serving seam (serving.SlotEngine) --------------------------------

    def cache_layout(self):
        """K and V rows ``[stored heads, d]`` a token a FULL layer, as
        GPT's (`paging.BLOCK_ROW_ORDER`), the heads rounded up to a
        multiple of 16 (`stored_heads`): 30 heads pad to 32 in the
        chip's tiles anyway, and declared as 30 the TPU compiler keeps
        the pools in a layout of its own and copies each whole round
        every layer's scatter (tests/test_v5e_compile.py). And a slot's
        state a LINEAR layer: ``S`` (its head axis may shard over mp)
        and the filter's tail in the weights' dtype."""
        from ...serving.paging import BLOCK_ROW_ORDER, CacheLayout

        cfg = self.config
        row = (stored_heads(cfg.num_heads),
               cfg.hidden_size // cfg.num_heads)
        linear = [la for la in self.model.layers if la.is_linear]
        state = linear[0].mixer.state_arrays() if linear else ()
        return CacheLayout(
            BLOCK_ROW_ORDER, (("k", row), ("v", row)),
            cfg.count("full_attention"), head_axis=2,
            state=state, state_layers=len(linear),
            state_head_axis={"S": 1})

    def paged_forward(self, tok, pos, nvalid, tables, pools, state):
        """One serving step: `tok` ``[slots, chunk]``, slot `b`'s
        columns at positions ``pos[b] + column``, the first
        ``nvalid[b]`` of them real; `pools` one ``(k, v)`` a full
        layer, `state` one ``(S, tail)`` a linear layer, ``[slots,
        ...]`` each. Returns ``(hidden [slots, chunk, H], pools, state,
        aux)`` with GPT's aux: the turns the attention loop ran and the
        turns that cover the whole table."""
        import jax.numpy as jnp

        m = self.model
        valid = jnp.arange(tok.shape[1])[None, :] < nvalid[:, None]
        x = m.embed(tok)
        pools, state = iter(pools), iter(state)
        new_pools, new_state, key_tiles = [], [], jnp.int32(0)
        for layer in m.layers:
            if layer.is_linear:
                mixed, held = layer.mixer.forward_paged(
                    x, valid, nvalid, next(state))
                new_state.append(held)
            else:
                mixed, held, key_tiles = layer.mixer.forward_paged(
                    x, pos, tables, next(pools))
                new_pools.append(held)
            x = layer._residuals(x, mixed)
        h = _v(m.final_norm(Tensor(x)))
        aux = {"attn_key_tiles": key_tiles}
        if new_pools:
            aux["attn_key_tiles_max"] = key_tiling(
                tables.shape[1], new_pools[0][0].shape[1])[1]
        return h, new_pools, new_state, aux
