"""RNG state management.

Ref parity: paddle/fluid/framework/generator.h (seeded per-device Philox
Generator). TPU-native: JAX threaded PRNG keys. A global default Generator
serves the eager API (`paddle_tpu.seed`); inside jit capture (functional
engine) a *traced* base key is installed with `rng_scope(key)` so random ops
fold into the compiled program instead of baking in constants.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp


class Generator:
    """Counter-based key stream (split-free: fold_in on a monotone counter)."""

    def __init__(self, seed=0):
        # lazy: building a PRNGKey initialises the XLA backend, which must
        # not happen at import time (jax.distributed.initialize comes first
        # in multi-process jobs)
        self._seed = seed
        self._base_cache = None
        self._counter = 0

    @property
    def _base(self):
        if self._base_cache is None:
            self._base_cache = jax.random.PRNGKey(self._seed)
        return self._base_cache

    @_base.setter
    def _base(self, value):
        self._base_cache = value

    def manual_seed(self, seed):
        self._seed = int(seed)
        self._base_cache = None
        self._counter = 0
        return self

    seed = manual_seed

    def initial_seed(self):
        return self._seed

    def next_key(self):
        self._counter += 1
        return jax.random.fold_in(self._base, self._counter)

    def get_state(self):
        return (self._seed, self._counter)

    def set_state(self, state):
        self._seed, self._counter = state
        self._base = jax.random.PRNGKey(self._seed)


default_generator = Generator(0)

_tls = threading.local()


@contextlib.contextmanager
def rng_scope(key):
    """Install a (possibly traced) base key; random ops inside draw from it.

    Used by the functional engine: the train-step's input key becomes the
    base so dropout masks differ per step and are part of the compiled fn.
    """
    gen = Generator(0)
    gen._base = jnp.asarray(key)
    prev = getattr(_tls, "scoped", None)
    _tls.scoped = gen
    try:
        yield gen
    finally:
        _tls.scoped = prev


def next_key():
    gen = getattr(_tls, "scoped", None)
    if gen is not None:
        return gen.next_key()
    return default_generator.next_key()


def seed(s):
    """paddle.seed: reseed the default generator and restart its site
    counter. Keys, parameter initialisers, sampling and `random_ops`
    draw from JAX's Threefry, whose values are the same on every
    platform. A dropout keep-mask takes its key from here too, but its
    bits from XLA's bit generator (`ops/_common.keep_mask_u16`): a
    fixed seed gives the same masks run after run on ONE platform, and
    other masks, as fair, on another (a meshed step's compiler may also
    draw a sharded mask shard by shard; the CPU's does not)."""
    default_generator.manual_seed(s)
    return default_generator


def get_rng_state():
    gen = getattr(_tls, "scoped", None) or default_generator
    return [gen.get_state()]


def set_rng_state(state):
    default_generator.set_state(state[0])
