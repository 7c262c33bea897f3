"""Shared helpers for op implementations."""

from __future__ import annotations

import jax.numpy as jnp


def align_for_axis_broadcast(x, y, axis=-1):
    """Paddle legacy elementwise `axis` attr: broadcast y starting at `axis`
    of x (ref: paddle/fluid/operators/elementwise/elementwise_op.h)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if axis == -1 or y.ndim == 0 or x.ndim == y.ndim:
        return x, y
    if y.ndim > x.ndim:
        return x, y
    shape = [1] * axis + list(y.shape)
    shape += [1] * (x.ndim - len(shape))
    return x, y.reshape(shape)


def normalize_axis(axis, ndim):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(normalize_axis(a, ndim) for a in axis)
    axis = int(axis)
    return axis + ndim if axis < 0 else axis


def keep_mask_u16(key, shape, dropout_p):
    """bool dropout keep-mask from a u16 threshold compare.

    16 random bits per element: half the traffic of a u32 stream and no
    int->float conversion (vs bernoulli's f32 uniform); the keep rate
    quantises to 1/65536 (error <= 1.5e-5 of the requested p — far below
    training noise). Shared by ops/nn_ops.dropout and the attention
    paths in ops/fused_ops.

    The bits come from XLA's bit generator (`lax.rng_bit_generator`,
    its default algorithm), keyed by the site's two-word Threefry key
    written twice, as `jax.random`'s `rbg` seeds a four-word key. To
    XLA a Threefry draw is cheap integer arithmetic that it re-derives
    inside every fusion that reads the mask, forward and backward, 126
    integer ops an element each time; a generator op cannot be cloned,
    so a mask is drawn once. The barrier makes the one-byte mask what
    is kept for the backward, not the 16-bit draw. A mask is a function
    of its key alone (a recomputed block draws the same one), but of
    the platform too: the generator's algorithm is the backend's.

    Every draw is counted where it is traced (`framework.monitor`:
    `dropout_masks_traced`, `dropout_mask_elements_traced`), so inside
    `jit` once a compile: the train step's compile event reports the
    difference across its trace.
    """
    from jax import lax

    from ..framework import monitor

    if key.shape[-1] == 2:
        # a four-word key is the generator's own (a process that set
        # `jax_default_prng_impl` to `rbg`, as bench.py does)
        key = jnp.concatenate([key, key])
    _, bits = lax.rng_bit_generator(key, tuple(shape), jnp.uint16)
    thresh = jnp.uint16(min(int(round((1.0 - dropout_p) * 2.0 ** 16)),
                            2 ** 16 - 1))
    monitor.stat_add("dropout_masks_traced")
    monitor.stat_add("dropout_mask_elements_traced", bits.size)
    return lax.optimization_barrier(bits < thresh)
