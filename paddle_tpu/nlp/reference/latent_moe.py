"""Plain reference of the latent-attention + held-experts decoder
(`nlp/transformers/latent_moe.py`): the full forward of ONE sequence in
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`.
No cache, no kernels, no batching, no grouped product: attention in
the expanded form over the whole sequence, experts as a Python loop.

It follows the published description of `model_type: sarvam_mla`
(the DeepSeek-V2/V3 block). Per layer, ``h = RMSNorm(x)``:

    q = h W_q                      -> heads of [q_nope | q_rope]
    [c | k_r] = h W_kva;  c = RMSNorm(c)
    rotary (YaRN) on q_rope and on the one shared k_r
    [k_nope | v] = c W_kvb         -> heads of dn + dv
    score = (q_nope . k_nope + q_rope . k_r) * scale, causal softmax
    x <- x + concat_heads(P v) W_o
    s = sigmoid(h W_r);  sel = top_k(s + bias);  w = s[sel]/sum * factor
    x <- x + sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h)   (layer 0: SwiGLU)

Departures from the published code, each shared with the program it is
compared with, none of which a comparison on seeded weights can see:

- rotary pairs are ``(i, i + d/2)``; the published code first
  de-interleaves ``(2i, 2i + 1)`` into that order, a fixed permutation
  of columns that weights drawn from a seed absorb;
- gate and up projections are the two halves of one ``[H, 2I]`` matrix
  (gate first), routed experts are stacked ``[held, ...]``;
- **the share**: `cfg["num_experts"]` experts are held, those from
  ``cfg["ep_rank"] * num_experts`` on; the router scores all
  `router_experts`, and what the absent ones would have added is left
  out (their chips add it in the deployment). The shared expert is
  whole. The vocabulary is whatever rows `embed_tokens` / `lm_head`
  hold: a slice is a smaller vocabulary;
- assumed, as the configuration file lists: sigmoid scores with
  normalised top-k weights, no group-limited selection, RMSNorm on the
  compressed KV only.

`params` maps the program's state-dict names to arrays of any float
dtype (cast up where used, one layer and one expert at a time, so that
the reference fits beside bfloat16 weights on a chip); `cfg` is a dict
of the sizes under the names `LatentMoEConfig` gives them. `wrap` is
applied to each leaf function (`jax.jit` makes a chip run compile each
once); `latent_round`, None wherever the program is judged, rounds what
a cache would hold (``c`` and the rotated ``k_r``): a lower-precision
cache read against this reference is the control that has to fail. A
list given as `picks` receives each expert layer's ``sel [s, k]``, the
routed experts every position picked, so that a comparison can tell a
position whose picks the program shares from one where a tie fell the
other way.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(a):
    return jnp.asarray(a).astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(weight)


def yarn(dim, theta, scaling):
    """``(inv_freq [dim/2], cos_sin_factor, softmax_factor)`` of
    "deepseek_yarn" rotary scaling; plain RoPE without `scaling`."""
    exponent = jnp.arange(0, dim, 2, dtype=F32) / dim
    original = 1.0 / theta ** exponent
    if not scaling or scaling.get("factor", 1.0) <= 1.0:
        return original, 1.0, 1.0
    factor = float(scaling["factor"])
    span = float(scaling["original_max_position_embeddings"])

    def pair_turning(turns):
        # the pair whose wavelength fits `turns` times into the span
        return dim * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = original / factor * ramp + original * (1.0 - ramp)

    def magnitude(m):
        return 0.1 * m * math.log(factor) + 1.0 if m else 1.0

    m_all = magnitude(scaling.get("mscale_all_dim", 0))
    return (inv_freq, magnitude(scaling.get("mscale", 1)) / m_all,
            m_all * m_all)


def rotate(x, positions, inv_freq, factor):
    """Rotary on the last axis of `x` ``[s, ..., d]``, pairs
    ``(i, i + d/2)``, at integer `positions` ``[s]``."""
    angle = positions.astype(F32)[:, None] * inv_freq
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def swiglu(x, gate_up, down):
    gate_up, down = _f32(gate_up), _f32(down)
    inter = down.shape[0]
    gu = x @ gate_up
    return (jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) @ down


def attention(h, positions, q_w, kva_w, kva_norm_w, kvb_w, o_w, *, cfg,
              latent_round=None):
    """Causal latent attention of normed rows `h` ``[s, H]``, expanded
    form."""
    s = h.shape[0]
    nh, dn = cfg["num_heads"], cfg["qk_nope_head_dim"]
    dr, rank = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    inv_freq, rot_factor, softmax_factor = yarn(
        dr, cfg["rope_theta"], cfg["rope_scaling"])
    q = (h @ _f32(q_w)).reshape(s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ckr = h @ _f32(kva_w)
    c = rms_norm(ckr[:, :rank], kva_norm_w, cfg["rms_norm_eps"])
    q_rope = rotate(q_rope, positions, inv_freq, rot_factor)
    k_r = rotate(ckr[:, rank:], positions, inv_freq, rot_factor)
    if latent_round is not None:
        c, k_r = latent_round(c), latent_round(k_r)
    kv = (c @ _f32(kvb_w)).reshape(s, nh, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5 * softmax_factor
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * scale
    causal = positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1) @ _f32(o_w)


def route(h, router_w, router_bias, *, cfg):
    """``(sel [s, k], w [s, k])`` over all routed experts."""
    score = jax.nn.sigmoid(h @ _f32(router_w))
    _, sel = jax.lax.top_k(score + _f32(router_bias),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(score, sel, axis=-1)
    return sel, w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def expert_term(h, sel, w, gate_up_stack, down_stack, e, first):
    """What held expert `e` (global index ``first + e``) adds: its
    SwiGLU of every row, weighted by the row's pick of it (0 for rows
    that did not pick it)."""
    mine = jnp.where(sel == first + e, w, 0.0).sum(-1)
    gate_up = jax.lax.dynamic_index_in_dim(gate_up_stack, e, 0, False)
    down = jax.lax.dynamic_index_in_dim(down_stack, e, 0, False)
    return mine[:, None] * swiglu(h, gate_up, down)


def forward(params, cfg, tokens, wrap=None, latent_round=None, picks=None):
    """Logits ``[s, V]`` float32 of the token sequence `tokens` ``[s]``;
    each expert layer's picks are appended to `picks`, if given."""
    wrap = wrap or (lambda f: f)
    eps = cfg["rms_norm_eps"]
    attend = wrap(functools.partial(attention, cfg=cfg,
                                    latent_round=latent_round))
    router = wrap(functools.partial(route, cfg=cfg))
    term, ffn, norm = wrap(expert_term), wrap(swiglu), wrap(rms_norm)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["model.embed_tokens.weight"][tokens])
        for i in range(cfg["num_layers"]):
            p = functools.partial(_layer_param, params, i)
            h = norm(x, p("input_norm.weight"), eps)
            x = x + attend(h, positions, p("attn.q_proj.weight"),
                           p("attn.kv_a_proj.weight"),
                           p("attn.kv_a_norm.weight"),
                           p("attn.kv_b_proj.weight"),
                           p("attn.o_proj.weight"))
            h = norm(x, p("post_norm.weight"), eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + ffn(h, p("mlp.gate_up_proj.weight"),
                            p("mlp.down_proj.weight"))
                continue
            sel, w = router(h, p("mlp.router.weight"),
                            p("mlp.router_bias"))
            if picks is not None:
                picks.append(sel)
            first = cfg["ep_rank"] * cfg["num_experts"]
            for e in range(cfg["num_experts"]):
                x = x + term(h, sel, w, p("mlp.gate_up"), p("mlp.down"),
                             e, first)
            if cfg["num_shared_experts"]:
                x = x + ffn(h, p("mlp.shared.gate_up_proj.weight"),
                            p("mlp.shared.down_proj.weight"))
        x = norm(x, params["model.final_norm.weight"], eps)
        return wrap(lambda rows, head: rows @ _f32(head))(
            x, params["lm_head.weight"])


def _layer_param(params, i, name):
    return params[f"model.layers.{i}.{name}"]
