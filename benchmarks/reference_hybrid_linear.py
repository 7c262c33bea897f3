"""Plain reference of the hybrid linear-attention decoder
(`nlp/transformers/hybrid_linear.py`): the full forward of ONE sequence
in float32 `jax.numpy` under `jax.default_matmul_precision("highest")`.
No cache, no chunking, no batching, no kernels: the gated delta rule
runs token by token exactly as it is written below, softmax attention
over the whole sequence at once.

It follows the published description of `model_type: olmo_hybrid`
(`layer_types` chooses each layer's mixer; the `linear_*` keys are
those of the Gated DeltaNet layer, arXiv:2412.06464). Every layer:

    x <- x + RMSNorm(mixer(x))          (norm AFTER the mixer)
    x <- x + RMSNorm(SwiGLU(x))

`full_attention`:

    [q | k | v] = x W_in;  q = RMSNorm(q), k = RMSNorm(k) over the whole
    projection, then heads; causal softmax(q k^T / sqrt(d)) v;  W_o.
    No rotary embedding (`rope_theta` is null).

`linear_attention`, heads h of (d_k, d_v), per token t:

    [q | k | v] = SiLU(filter(x W_in))   causal depthwise filter, 4 taps
    q_h, k_h <- q_h / |q_h|, k_h / |k_h|;  q_h <- q_h d_k^-1/2
    [a | b] = x W_ab
    beta = 2 sigmoid(b)                  (`linear_allow_neg_eigval`)
    alpha = exp(-exp(A_log) softplus(a + dt_bias))
    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y = concat_h(RMSNorm(o_h) SiLU((x W_gate)_h)) W_o

then a final RMSNorm and the untied head.

Departures from the published code, each shared with the program it is
compared with, none of which a comparison on seeded weights can see:

- the projections q, k, v are the column blocks of one matrix `W_in`
  (q first), `a` and `b` those of one `W_ab`, gate and up of the SwiGLU
  those of one `[H, 2I]` matrix (gate first);
- the filter's taps are ``conv_weight [taps, columns]``, the last tap on
  the current token; it has no bias;
- the L2 norms and the RMS norms add their epsilon under the root;
- **the cut**: `cfg["num_layers"]` layers are held (the first entries
  of `layer_types`); the final norm and the head are applied to their
  output, where a deployment would apply them behind the later layers;
- assumed, as the configuration file lists: norm after the mixer,
  whole-projection QK-norm, no rotary, the per-head output norm gated
  by SiLU, a float32 recurrent state.

`params` maps the program's state-dict names to arrays of any float
dtype (cast up where used, a layer at a time, so that the reference
fits beside bfloat16 weights on a chip); `cfg` is a dict of the sizes
under the names `HybridLinearConfig` gives them. `wrap` is applied to
each leaf function (`jax.jit` makes a chip run compile each once).
`state_round`, None wherever the program is judged, rounds the
recurrent state after every token: a state kept in a lower precision,
read against this reference, is the control that has to fail. A list
given as `states` receives each linear layer's state after the last
token, so that a comparison can hold the program's own state arrays to
it. `rows`
picks the positions whose logits are returned (all of them when None);
the head runs over `head_block` columns of the vocabulary at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
NORM_EPS = 1e-6     # under the root of the L2 norms of q and k


def _f32(a):
    return jnp.asarray(a).astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(weight)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + NORM_EPS)


def swiglu(x, gate_up, down):
    gate_up, down = _f32(gate_up), _f32(down)
    inter = down.shape[0]
    gu = x @ gate_up
    return (jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) @ down


def full_attention(x, in_w, q_norm_w, k_norm_w, o_w, *, cfg):
    """Causal softmax attention of rows `x` ``[s, H]``."""
    s, hidden = x.shape
    nh = cfg["num_heads"]
    qkv = x @ _f32(in_w)
    q = rms_norm(qkv[:, :hidden], q_norm_w, cfg["rms_norm_eps"])
    k = rms_norm(qkv[:, hidden:2 * hidden], k_norm_w, cfg["rms_norm_eps"])
    q, k, v = (a.reshape(s, nh, -1)
               for a in (q, k, qkv[:, 2 * hidden:]))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1) @ _f32(o_w)


def causal_filter(x, taps):
    """``y_t = sum_j taps[j] * x_{t - (n - 1) + j}`` down every column
    of `x` ``[s, c]``, zeros before the first token."""
    taps = _f32(taps)
    n = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), F32), x])
    return sum(taps[j] * padded[j:j + x.shape[0]] for j in range(n))


def delta_rule(q, k, v, alpha, beta, state_round=None):
    """The gated delta rule token by token: `q`, `k` ``[s, h, d_k]``,
    `v` ``[s, h, d_v]``, `alpha`, `beta` ``[s, h]``; ``(o [s, h, d_v],
    S [h, d_k, d_v])`` from ``S_0 = 0``."""
    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        # (I - beta k k^T) S  =  S - beta k (k^T S)
        kS = jnp.einsum("hk,hkv->hv", k_t, S)
        S = a_t[:, None, None] * (
            S - b_t[:, None, None] * k_t[:, :, None] * kS[:, None, :]) \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        if state_round is not None:
            S = state_round(S)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    S, o = jax.lax.scan(step, S0, (q, k, v, alpha, beta))
    return o, S


def linear_attention(x, in_w, conv_w, ab_w, a_log, dt_bias, gate_w,
                     out_norm_w, o_w, *, cfg, state_round=None):
    """The Gated DeltaNet mixer of rows `x` ``[s, H]``: ``(y [s, H],
    S [h, d_k, d_v])``, `S` the state after the last row."""
    s = x.shape[0]
    nh, dk, dv = (cfg["linear_num_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    qkv = jax.nn.silu(causal_filter(x @ _f32(in_w), conv_w))
    q = l2_norm(qkv[:, :nh * dk].reshape(s, nh, dk)) * dk ** -0.5
    k = l2_norm(qkv[:, nh * dk:2 * nh * dk].reshape(s, nh, dk))
    v = qkv[:, 2 * nh * dk:].reshape(s, nh, dv)
    ab = x @ _f32(ab_w)
    beta = 2.0 * jax.nn.sigmoid(ab[:, nh:])
    alpha = jnp.exp(-jnp.exp(_f32(a_log))
                    * jax.nn.softplus(ab[:, :nh] + _f32(dt_bias)))
    o, S = delta_rule(q, k, v, alpha, beta, state_round)
    gate = (x @ _f32(gate_w)).reshape(s, nh, dv)
    o = rms_norm(o, out_norm_w, cfg["rms_norm_eps"]) * jax.nn.silu(gate)
    return o.reshape(s, -1) @ _f32(o_w), S


def head(rows, weight, block):
    """``rows @ weight`` over `block` columns of the vocabulary at a
    time: the whole head cast up at once is 1.5 GB at the published
    sizes."""
    return jnp.concatenate(
        [rows @ _f32(weight[:, at:at + block])
         for at in range(0, weight.shape[1], block)], axis=-1)


def forward(params, cfg, tokens, wrap=None, state_round=None, rows=None,
            head_block=16384, states=None):
    """Logits ``[s, V]`` float32 of the token sequence `tokens` ``[s]``
    (``[len(rows), V]`` of the positions `rows`, if given); each linear
    layer's state after the last token is appended to `states`, if
    given."""
    wrap = wrap or (lambda f: f)
    eps = cfg["rms_norm_eps"]
    full = wrap(functools.partial(full_attention, cfg=cfg))
    linear = wrap(functools.partial(linear_attention, cfg=cfg,
                                    state_round=state_round))
    ffn, norm = wrap(swiglu), wrap(rms_norm)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["model.embed_tokens.weight"][tokens])
        for i in range(cfg["num_layers"]):
            p = functools.partial(_layer_param, params, i)
            if cfg["layer_types"][i] == "full_attention":
                mixed = full(x, p("mixer.in_proj.weight"),
                             p("mixer.q_norm.weight"),
                             p("mixer.k_norm.weight"),
                             p("mixer.o_proj.weight"))
            else:
                mixed, S = linear(x, p("mixer.in_proj.weight"),
                                  p("mixer.conv_weight"),
                                  p("mixer.ab_proj.weight"),
                                  p("mixer.A_log"), p("mixer.dt_bias"),
                                  p("mixer.gate_proj.weight"),
                                  p("mixer.out_norm.weight"),
                                  p("mixer.o_proj.weight"))
                if states is not None:
                    states.append(S)
            x = x + norm(mixed, p("mixer_norm.weight"), eps)
            x = x + norm(ffn(x, p("mlp.gate_up_proj.weight"),
                             p("mlp.down_proj.weight")),
                         p("mlp_norm.weight"), eps)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        x = norm(x, params["model.final_norm.weight"], eps)
        return wrap(functools.partial(head, block=head_block))(
            x, params["lm_head.weight"])


def _layer_param(params, i, name):
    return params[f"model.layers.{i}.{name}"]
