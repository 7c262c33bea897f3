"""Plain reference of the sliding-window + full attention decoder with
grouped KV heads and routed experts in every layer (`model_type:
mellum`): the full forward of ONE sequence in float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`. No cache, no kernels, no
batching, no grouped product: a dense ``[T, T]`` mask a layer kind,
the query heads of one KV head at a time against that head, the experts
as a Python loop over all of them.

Written from the published description and the config's keys, not from
the program it is compared with. Per layer `l`, pre-norm:

    h = RMSNorm(x)
    [q | k | v] = h W_qkv         q: 32 heads, k and v: 4 heads, of 128
    q_i = RMSNorm_128(q_i);  k_j = RMSNorm_128(k_j)     (learned scale)
    rotary on all 128 columns, pairs (i, i + 64):
        sliding layer  plain, theta 500,000
        full layer     YaRN: theta 500,000, factor 16 over 8,192,
                       beta_fast 32, beta_slow 1; cos and sin times
                       attention_factor (0.1 ln 16 + 1); the softmax
                       scale is unchanged
    query head i reads KV head i // 8 (a Python loop over the KV heads)
    score = q . k * 128^-1/2, softmax over the admitted keys:
        sliding layer  t - sliding_window < j <= t
        full layer     j <= t
    x <- x + concat_heads(P v) W_o
    h = RMSNorm(x)
    p = softmax_64(h W_r);  sel = the 8 largest;  w = p[sel] / sum p[sel]
    x <- x + sum_i w_i W_down,i (SiLU(W_gate,i h) * W_up,i h)

then a final RMSNorm and the untied head.

Departures from the published code, each shared with the program it is
compared with, none of which a comparison on seeded weights can see:
rotary pairs are ``(i, i + d/2)``; the three attention projections are
the column blocks of one ``[H, (32 + 4 + 4) * 128]`` matrix (q first,
then k, then v); gate and up are the two halves of one ``[H, 2I]``
matrix (gate first) and the experts are stacked ``[64, ...]``.
Assumed, as the configuration file lists: the pre-norm residual path
and the per-head QK norm (the config has no key for either),
softmax-then-top-k scoring, the window's own key counted among its
`sliding_window`.

`params` maps the program's state-dict names to arrays of any float
dtype (cast up where used, one layer and one expert at a time, so that
the reference fits beside bfloat16 weights on a chip); `cfg` is a dict
of the sizes under the names `WindowMoEConfig` gives them. `wrap` is
applied to each leaf function (`jax.jit` makes a chip run compile each
once). `weight_round`, None wherever the program is judged, rounds
every matrix as it is cast up: weights in a lower precision read
against this reference are the control that has to fail. A list given
as `picks` receives each layer's ``sel [s, k]``, the experts every
position picked, so that a comparison can tell a position whose picks
the program shares from one where a tie fell the other way. `rows`
(positions) limits the final norm and the head to those rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(a, weight_round=None):
    a = jnp.asarray(a).astype(F32)
    return a if weight_round is None else weight_round(a)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(weight)


def rotary_group(dim, group):
    """``(inv_freq [dim/2], cos_sin_factor)`` of one `rope_parameters`
    group: plain RoPE for ``rope_type: "default"``, else YaRN as the
    public ``rope_type: "yarn"`` computes it: per pair a blend of the
    interpolated (``/ factor``) and the unscaled inverse frequency,
    linear in the pair index over the correction range, and the
    `attention_factor` on cos and sin (``0.1 ln(factor) + 1`` when the
    group does not state it)."""
    theta = float(group["rope_theta"])
    exponent = jnp.arange(0, dim, 2, dtype=F32) / dim
    unscaled = 1.0 / theta ** exponent
    if group.get("rope_type", "default") == "default":
        return unscaled, 1.0
    factor = float(group["factor"])
    span = float(group["original_max_position_embeddings"])

    def pair_turning(turns):
        # the pair whose wavelength fits `turns` times into the span
        return dim * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_turning(group["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(group["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = unscaled / factor * ramp + unscaled * (1.0 - ramp)
    stated = group.get("attention_factor")
    return inv_freq, float(stated) if stated is not None \
        else 0.1 * math.log(factor) + 1.0


def rotate(x, positions, inv_freq, factor):
    """Rotary on the last axis of `x` ``[s, heads, d]``, pairs ``(i, i
    + d/2)``, at integer `positions` ``[s]``."""
    angle = positions.astype(F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def project(h, positions, qkv_w, q_norm_w, k_norm_w, *, cfg, kind,
            weight_round=None):
    """``q [s, nh, hd]``, ``k`` and ``v [s, nkv, hd]`` of normed rows
    `h` ``[s, H]`` of a layer of `kind`: normed a head, then rotated."""
    s = h.shape[0]
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    qkv = (h @ _f32(qkv_w, weight_round)).reshape(s, nh + 2 * nkv, hd)
    q, k, v = qkv[:, :nh], qkv[:, nh:nh + nkv], qkv[:, nh + nkv:]
    inv_freq, factor = rotary_group(hd, cfg["rope_parameters"][kind])
    q = rotate(rms_norm(q, q_norm_w, cfg["rms_norm_eps"]), positions,
               inv_freq, factor)
    k = rotate(rms_norm(k, k_norm_w, cfg["rms_norm_eps"]), positions,
               inv_freq, factor)
    return q, k, v


def attend(q, k, v, positions, *, window):
    """The query heads ``q [s, g, hd]`` that read ONE KV head ``k, v
    [s, hd]``, over a dense ``[s, s]`` mask: key `j` is admitted for
    the query at `t` iff ``j <= t`` and, with a `window`, ``j > t -
    window``. Returns ``[s, g, hd]``."""
    scores = jnp.einsum("qgd,kd->gqk", q, k) * q.shape[-1] ** -0.5
    t, j = positions[:, None], positions[None, :]
    admitted = j <= t
    if window is not None:
        admitted = admitted & (j > t - window)
    p = jax.nn.softmax(jnp.where(admitted[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->qgd", p, v)


def route(h, router_w, *, cfg, weight_round=None):
    """``(sel [s, k], w [s, k])``: softmax over all experts, the `k`
    largest, their scores normalised to sum 1."""
    p = jax.nn.softmax(h @ _f32(router_w, weight_round), axis=-1)
    w, sel = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return sel, w / w.sum(-1, keepdims=True)


def expert_term(h, sel, w, gate_up_stack, down_stack, e, weight_round=None):
    """What expert `e` adds: its SwiGLU of every row, weighted by the
    row's pick of it (0 for rows that did not pick it)."""
    mine = jnp.where(sel == e, w, 0.0).sum(-1)
    gate_up = _f32(jax.lax.dynamic_index_in_dim(gate_up_stack, e, 0, False),
                   weight_round)
    down = _f32(jax.lax.dynamic_index_in_dim(down_stack, e, 0, False),
                weight_round)
    gu = h @ gate_up
    inter = down.shape[0]
    return mine[:, None] * ((jax.nn.silu(gu[:, :inter]) * gu[:, inter:])
                            @ down)


def forward(params, cfg, tokens, wrap=None, weight_round=None, picks=None,
            rows=None):
    """Logits ``[s, V]`` (``[len(rows), V]`` with `rows`) float32 of the
    token sequence `tokens` ``[s]``; each layer's picks are appended to
    `picks`, if given."""
    wrap = wrap or (lambda f: f)
    eps = cfg["rms_norm_eps"]
    kinds = set(cfg["layer_types"][:cfg["num_layers"]])
    projections = {kind: wrap(functools.partial(
        project, cfg=cfg, kind=kind, weight_round=weight_round))
        for kind in kinds}
    attends = {kind: wrap(functools.partial(
        attend, window=cfg["sliding_window"]
        if kind == "sliding_attention" else None)) for kind in kinds}
    out_proj = wrap(lambda o, o_w: o @ _f32(o_w, weight_round))
    group = cfg["num_heads"] // cfg["num_kv_heads"]
    router = wrap(functools.partial(route, cfg=cfg,
                                    weight_round=weight_round))
    term = wrap(functools.partial(expert_term, weight_round=weight_round))
    norm = wrap(rms_norm)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["model.embed_tokens.weight"][tokens], weight_round)
        for i in range(cfg["num_layers"]):
            p = functools.partial(_layer_param, params, i)
            h = norm(x, p("input_norm.weight"), eps)
            kind = cfg["layer_types"][i]
            q, k, v = projections[kind](
                h, positions, p("attn.qkv_proj.weight"),
                p("attn.q_norm.weight"), p("attn.k_norm.weight"))
            # query head i reads KV head i // group: one KV head, and
            # the heads that read it, at a time
            o = jnp.concatenate([
                attends[kind](q[:, j * group:(j + 1) * group], k[:, j],
                              v[:, j], positions)
                for j in range(cfg["num_kv_heads"])], axis=1)
            x = x + out_proj(o.reshape(o.shape[0], -1),
                             p("attn.o_proj.weight"))
            h = norm(x, p("post_norm.weight"), eps)
            sel, w = router(h, p("mlp.router.weight"))
            if picks is not None:
                picks.append(sel)
            for e in range(cfg["num_experts"]):
                x = x + term(h, sel, w, p("mlp.gate_up"), p("mlp.down"), e)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = norm(x, params["model.final_norm.weight"], eps)
        return wrap(lambda hidden, head: hidden @ _f32(head, weight_round))(
            x, params["lm_head.weight"])


def _layer_param(params, i, name):
    return params[f"model.layers.{i}.{name}"]
