"""What a dropout keep-mask is, whatever draws its bits
(`ops/_common.keep_mask_u16`, behind `F.dropout` and the fallback
attention's mask): a function of its key alone, a different one for
every site, step and seed, kept at the rate asked for, and in the
backward the mask the forward made. The values themselves belong to the
platform's bit generator and are pinned nowhere.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observe
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import monitor, random as _random
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import _common
from paddle_tpu.ops.nn_ops import dropout as dropout_op

SHAPE = (64, 256)


def _ones():
    return Tensor(np.ones(SHAPE, np.float32))


def _draw(seed, calls=1):
    """The outputs of `calls` dropout sites one after the other behind
    `paddle.seed(seed)`."""
    paddle.seed(seed)
    return [F.dropout(_ones(), p=0.5, training=True).numpy()
            for _ in range(calls)]


def test_same_seed_same_mask():
    (a,), (b,) = _draw(7), _draw(7)
    np.testing.assert_array_equal(a, b)
    assert set(np.unique(a)) == {0.0, 2.0}


@pytest.mark.parametrize("what", ["site", "step", "seed"])
def test_another_site_step_or_seed_draws_another_mask(what):
    """Two masks of fair bits agree at about half of their positions;
    a mask shared between sites, steps or seeds would agree at all."""
    if what == "site":
        a, b = _draw(7, calls=2)
    elif what == "seed":
        (a,), (b,) = _draw(7), _draw(8)
    else:
        # the engine's way: one base key a step, installed by rng_scope,
        # the site's counter folded into it
        def step(key):
            with _random.rng_scope(key):
                return F.dropout(_ones(), p=0.5, training=True)._value

        base = jax.random.PRNGKey(3)
        a, b = (np.asarray(step(jax.random.fold_in(base, i)))
                for i in (1, 2))
    agree = ((a == 0) == (b == 0)).mean()
    assert 0.45 < agree < 0.55, agree


@pytest.mark.parametrize("p", [0.1, 0.5])
def test_keep_rate_over_four_million_elements(p):
    mask = _common.keep_mask_u16(jax.random.PRNGKey(11), (2048, 2048), p)
    assert mask.dtype == jnp.bool_ and mask.size == 2 ** 22
    assert abs(float(mask.mean()) - (1.0 - p)) < 0.002


def _grad_and_out(fn, x, key):
    out, vjp = jax.vjp(lambda x: fn(x, key), x)
    (dx,) = vjp(jnp.ones_like(out))
    return out, dx


@pytest.mark.parametrize("wrap", ["plain", "jit", "checkpoint",
                                  "checkpoint_in_jit"])
def test_backward_reads_the_mask_the_forward_made(wrap):
    """d out / d x is exactly 0 where the output is 0 and 1 / keep
    elsewhere. Under `jax.checkpoint` the block is run again in the
    backward: the generator is a function of its key, so it draws the
    same mask."""
    p = 0.25

    def fn(x, key):
        return dropout_op(x * 3.0, key, p=p, training=True)

    if wrap.startswith("checkpoint"):
        fn = jax.checkpoint(fn)
    x = jnp.full(SHAPE, 2.0, jnp.float32)
    key = jax.random.PRNGKey(5)
    run = _grad_and_out
    if wrap.endswith("jit"):
        run = jax.jit(_grad_and_out, static_argnums=0)
    out, dx = map(np.asarray, run(fn, x, key))
    dropped = out == 0
    assert 0.2 < dropped.mean() < 0.3
    np.testing.assert_array_equal(dx[dropped], 0.0)
    np.testing.assert_allclose(dx[~dropped], 3.0 / (1.0 - p), rtol=1e-6)
    np.testing.assert_allclose(out[~dropped], 6.0 / (1.0 - p), rtol=1e-6)


def test_eager_backward_reads_the_forward_mask():
    paddle.seed(21)
    x = paddle.to_tensor(np.full(SHAPE, 2.0, np.float32),
                         stop_gradient=False)
    y = F.dropout(x, p=0.5, training=True)
    y.sum().backward()
    out, dx = y.numpy(), x.grad.numpy()
    np.testing.assert_array_equal(dx[out == 0], 0.0)
    np.testing.assert_array_equal(dx[out != 0], 2.0)


def test_traced_key_under_jit_is_not_baked_in():
    """The engine's step takes its key as an argument: one compiled
    program, a new mask for every key, the same mask for the same."""
    traces = []

    @jax.jit
    def step(key):
        traces.append(1)
        with _random.rng_scope(key):
            a = F.dropout(_ones(), p=0.5, training=True)._value
            b = F.dropout(_ones(), p=0.5, training=True)._value
        return a, b

    a1, b1 = step(jax.random.PRNGKey(1))
    a2, b2 = step(jax.random.PRNGKey(2))
    a3, b3 = step(jax.random.PRNGKey(1))
    assert len(traces) == 1
    np.testing.assert_array_equal(a1, a3)
    np.testing.assert_array_equal(b1, b3)
    assert (np.asarray(a1) != np.asarray(a2)).mean() > 0.4
    assert (np.asarray(a1) != np.asarray(b1)).mean() > 0.4


@pytest.mark.parametrize("training", [True, False])
def test_downscale_in_infer(training):
    paddle.seed(4)
    y = F.dropout(_ones(), p=0.25, training=training,
                  mode="downscale_in_infer").numpy()
    if training:
        # kept values pass unscaled, a quarter of them are dropped
        assert set(np.unique(y)) == {0.0, 1.0}
        assert 0.2 < (y == 0).mean() < 0.3
    else:
        np.testing.assert_array_equal(y, 0.75)


def test_inference_draws_nothing():
    before = monitor.stat_get("dropout_masks_traced")
    x = _ones()
    assert F.dropout(x, p=0.5, training=False) is x
    assert F.dropout(x, p=0.0, training=True) is x
    assert monitor.stat_get("dropout_masks_traced") == before
    F.dropout(x, p=0.5, training=True)
    assert monitor.stat_get("dropout_masks_traced") == before + 1


def test_bits_come_from_the_generator_op_not_threefry():
    """The `[b, s, h]`-sized draw is one `rng_bit_generator`; the only
    Threefry left in the program is the site's two-word key."""
    def fn(x, key):
        with _random.rng_scope(key):
            return F.dropout(Tensor(x), p=0.1, training=True)._value

    jaxpr = str(jax.make_jaxpr(fn)(jnp.ones(SHAPE), jax.random.PRNGKey(0)))
    assert jaxpr.count("rng_bit_generator") == 1
    assert "optimization_barrier" in jaxpr
    sized = f"[{SHAPE[0]},{SHAPE[1]}]"
    for line in jaxpr.splitlines():
        if "threefry" in line or "shift_right_logical" in line:
            assert sized not in line, line


def test_fallback_attention_mask_is_the_same_forward_and_backward():
    """`fused_ops`' jnp path keeps no mask: the forward draws it from
    the layer's seed and the backward draws it again from the same
    seed, so one seed must give one mask."""
    from paddle_tpu.ops import fused_ops

    seed = jnp.asarray(9, jnp.int32)
    a = fused_ops._jnp_keep_mask(seed, (2, 16, 16), 0.5)
    b = fused_ops._jnp_keep_mask(seed, (2, 16, 16), 0.5)
    c = fused_ops._jnp_keep_mask(seed + 1, (2, 16, 16), 0.5)
    np.testing.assert_array_equal(a, b)
    assert (np.asarray(a) != np.asarray(c)).any()


def test_a_four_word_key_is_taken_as_it_is():
    """bench.py, bench_attrib.py and bench_ops.py switch the process to
    `rbg` keys, four words each: the generator's own key."""
    key = jnp.arange(4, dtype=jnp.uint32)
    a = _common.keep_mask_u16(key, (64, 64), 0.5)
    b = _common.keep_mask_u16(key, (64, 64), 0.5)
    c = _common.keep_mask_u16(key + 1, (64, 64), 0.5)
    np.testing.assert_array_equal(a, b)
    assert 0.4 < (np.asarray(a) != np.asarray(c)).mean() < 0.6


def test_batched_keys_draw_one_mask_a_row():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    masks = jax.vmap(
        lambda k: _common.keep_mask_u16(k, (4096,), 0.5))(keys)
    assert masks.shape == (4, 4096)
    m = np.asarray(masks)
    for i in range(3):
        assert 0.4 < (m[i] != m[i + 1]).mean() < 0.6


def _tiny_engine(dropout):
    from paddle_tpu.engine import Engine
    from paddle_tpu.nlp.transformers import (
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )

    cfg = ErnieConfig(use_parallel=False, vocab_size=128, hidden_size=32,
                      num_layers=2, num_heads=2, ffn_hidden_size=64,
                      max_seq_len=16, dropout=dropout, attn_dropout=0.0)
    paddle.seed(2)
    model = ErnieForPretraining(cfg)
    criterion = ErniePretrainingCriterion(cfg)
    optimizer = paddle.optimizer.AdamW(learning_rate=1e-3,
                                       parameters=model.parameters())
    return Engine(model, optimizer,
                  lambda out, mlm: criterion(out[0], out[1], mlm))


@pytest.mark.parametrize("dropout, masks", [(0.1, 5), (0.0, 0)])
def test_compile_event_counts_the_step_s_masks(dropout, masks):
    """1 site in the embeddings and 2 a layer, each drawn once whatever
    reads it forward and backward; counted where the step is traced, so
    a second step adds no event and no count."""
    engine = _tiny_engine(dropout)
    ids = np.random.RandomState(0).randint(0, 128, (4, 16)).astype(np.int32)
    n0 = len(observe.compile_events("train_step"))
    losses = [float(engine.train_batch(ids, ids).numpy()) for _ in range(3)]
    events = observe.compile_events("train_step")[n0:]
    assert len(events) == 1
    assert events[0]["dropout_masks"] == masks
    assert events[0]["dropout_mask_elements"] == masks * 4 * 16 * 32
    assert np.isfinite(losses).all()
    if dropout:
        # another step, another mask: the same batch does not give the
        # same loss twice even with the update's effect taken out
        assert len(set(losses)) == 3


def test_engine_steps_repeat_under_one_seed():
    ids = np.random.RandomState(0).randint(0, 128, (4, 16)).astype(np.int32)

    def losses():
        engine = _tiny_engine(0.1)
        paddle.seed(13)
        return [float(engine.train_batch(ids, ids).numpy())
                for _ in range(3)]

    assert losses() == losses()
