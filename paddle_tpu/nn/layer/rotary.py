"""Rotary position embedding with YaRN frequency scaling, and the SwiGLU
feed-forward block: TPU-native additions shared by the decoder families
under `nlp/transformers` (the reference framework ships neither).

Angles are computed from the position (`position * inv_freq`), never
read from a `[max_positions, dim]` table, so a model that declares
131,072 positions holds no table of them.
"""

from __future__ import annotations

import math

import numpy as np

from ...core.tensor import Tensor
from .. import functional as F
from .common import Linear
from .layers import Layer

__all__ = ["RotaryEmbedding", "SwiGLU", "yarn_inv_freq", "yarn_mscale",
           "rotate_half_pairs"]


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention-magnitude correction: ``0.1 * mscale *
    ln(factor) + 1`` for a context stretched by `factor` (1 when it is
    not stretched)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations, dim, theta, original_max):
    """The (fractional) pair index whose wavelength makes `rotations`
    turns over the original context."""
    return dim * math.log(original_max / (rotations * 2.0 * math.pi)) \
        / (2.0 * math.log(theta))


def yarn_inv_freq(dim, theta=10000.0, factor=1.0, original_max=4096,
                  beta_fast=32.0, beta_slow=1.0):
    """Inverse frequencies ``[dim // 2]`` (float32) of a rotary
    embedding over `dim` columns. `factor` 1 is plain RoPE
    (``theta ** (-2i / dim)``). Otherwise YaRN ("deepseek_yarn" in
    public configs): pairs that turn more than `beta_fast` times over
    the original context keep their frequency, pairs that turn fewer
    than `beta_slow` times are interpolated (divided by `factor`), and
    a linear ramp over the pair index blends the two between."""
    half = dim // 2
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return freq.astype(np.float32)
    low = max(math.floor(_correction_dim(beta_fast, dim, theta,
                                         original_max)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, theta,
                                         original_max)), dim - 1)
    span = max(high - low, 1e-3)     # low == high: a step, not 0 / 0
    # 0 = the original frequency kept, 1 = interpolated
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / span, 0, 1)
    return (freq * (1.0 - ramp) + freq / factor * ramp).astype(np.float32)


def rotate_half_pairs(x, cos, sin):
    """Rotate the pairs ``(x[..., i], x[..., i + d/2])`` by the angles
    behind `cos` / `sin` (``[..., d/2]``, broadcast against `x`); the
    product runs in float32 and returns in `x`'s dtype."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class RotaryEmbedding(Layer):
    """Rotary positions over the last `dim` columns of a head, with
    optional YaRN scaling (`scaling`: a public config's `rope_scaling`
    group, ``{"factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}``, the
    "deepseek_yarn" spelling; or the ``rope_type: "yarn"`` spelling,
    the same frequencies with an optional `attention_factor` that cos
    and sin carry, ``0.1 ln(factor) + 1`` when it is not given: what
    `cos_sin_scale` already is where `mscale_all_dim` is absent. A
    group of ``rope_type: "default"`` or with no `factor` is plain
    RoPE).

    ``forward(x, positions)``: `x` is ``[..., s, n, dim]`` (or
    ``[..., s, dim]`` with `heads=False`), `positions` ``[..., s]``.
    `attention_scale` is the factor a YaRN model multiplies its softmax
    scale by (``mscale(factor, mscale_all_dim) ** 2``); cos and sin
    carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
    No parameters: the frequencies are a constant of the trace."""

    def __init__(self, dim, theta=10000.0, scaling=None):
        super().__init__()
        s = dict(scaling or {})
        factor = float(s.get("factor", 1.0))
        self.dim = int(dim)
        self.inv_freq = yarn_inv_freq(
            dim, float(theta), factor,
            int(s.get("original_max_position_embeddings", 4096)),
            float(s.get("beta_fast", 32.0)), float(s.get("beta_slow", 1.0)))
        all_dim = float(s.get("mscale_all_dim", 0.0) or 0.0)
        m_all = yarn_mscale(factor, all_dim) if all_dim else 1.0
        self.cos_sin_scale = yarn_mscale(factor, float(s.get("mscale", 1.0))) \
            / m_all if factor > 1.0 else 1.0
        if s.get("attention_factor") is not None:
            # the public "yarn" group states the factor outright
            self.cos_sin_scale = float(s["attention_factor"])
        self.attention_scale = m_all * m_all

    def angles(self, positions):
        """``[..., dim // 2]`` float32 angles of integer positions."""
        import jax.numpy as jnp

        return jnp.asarray(positions)[..., None].astype(jnp.float32) \
            * jnp.asarray(self.inv_freq)

    def forward(self, x, positions, heads=True):
        import jax.numpy as jnp

        xv = x._value if isinstance(x, Tensor) else x
        pv = positions._value if isinstance(positions, Tensor) \
            else positions
        ang = self.angles(pv)
        if heads:
            ang = ang[..., None, :]
        cos = jnp.cos(ang) * self.cos_sin_scale
        sin = jnp.sin(ang) * self.cos_sin_scale
        out = rotate_half_pairs(xv, cos, sin)
        return Tensor(out) if isinstance(x, Tensor) else out


class SwiGLU(Layer):
    """``down(silu(gate(x)) * up(x))`` without biases. Gate and up are
    one ``[hidden, 2 * intermediate]`` product (gate the first half of
    its columns), so a token's hidden row is read once."""

    def __init__(self, hidden_size, intermediate_size, weight_attr=None):
        super().__init__()
        self.intermediate_size = int(intermediate_size)
        self.gate_up_proj = Linear(hidden_size, 2 * intermediate_size,
                                   weight_attr=weight_attr, bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                weight_attr=weight_attr, bias_attr=False)

    def forward(self, x):
        gu = self.gate_up_proj(x)
        i = self.intermediate_size
        return self.down_proj(F.silu(gu[..., :i]) * gu[..., i:])
