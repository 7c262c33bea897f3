"""On-chip tier (VERDICT r3 item 2): the CPU-mesh suite never touches
the TPU, so bf16-on-MXU numerics, VMEM limits, the non-interpreted
Pallas kernels, and compiled-engine behaviour on hardware are verified
only here.  These tests run the load-bearing paths on the chip — each
kernel the default flags can select, compiled by Mosaic at the shapes
the models use, plus the trainer and the serving engine at test size:

    PADDLE_TPU_TESTS_TPU=1 python -m pytest tests/ -m tpu

Without that variable the tier skips (conftest); with it and no TPU the
session fails at start.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tpu


def _mosaic_calls(fn, *args):
    """Compile `fn(*args)` and count the Mosaic kernels in the program
    the chip runs."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _lax_twin(monkeypatch, env, fn, *args):
    """`fn(*args)` with the kernel's lax twin selected.  jit caches
    traces by function identity, so the twin runs through a FRESH
    function object — re-jitting `fn` itself would replay the kernel
    trace and compare it with itself."""
    monkeypatch.setenv(env, "lax")
    try:
        return jax.jit(lambda *a: fn(*a))(*args)
    finally:
        monkeypatch.delenv(env)


def _sdpa_ref(q, k, v, causal, scale=None):
    import math
    d = q.shape[-1]
    s = scale or 1.0 / math.sqrt(d)
    # precision='highest': full-f32 MXU passes so the reference error is
    # well below the kernel tolerance being checked
    logits = jnp.einsum("bhqd,bhkd->bhqk",
                        q.astype(jnp.float32), k.astype(jnp.float32),
                        precision="highest") * s
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision="highest")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-2),
                                       (jnp.bfloat16, 4e-2)])
def test_flash_attention_pallas_on_chip(causal, dtype, tol):
    """The ACTUAL Pallas kernels (not interpreter): fwd + bwd vs the jnp
    softmax reference, fp32 and bf16.

    Tolerance note: the kernel's scores matmul runs at the MXU's DEFAULT
    f32 precision (bf16 multiply passes, f32 accumulate) — that IS the
    product being shipped, so the f32 band is ~1e-2 with rare per-element
    outliers, not ulp-exact.  Exact-math certification of the same
    kernels lives in the CPU interpret-mode tests
    (test_flash_attention.py) and the fd sweep; this test certifies
    on-chip structure: masking, lse, block boundaries, dropout plumbing.
    A masking/boundary bug shifts whole rows by O(1), far outside the
    band."""
    from paddle_tpu.ops import fused_ops

    rng = np.random.default_rng(0)
    shape = (2, 4, 256, 64)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
               for _ in range(3))
    os.environ["PADDLE_TPU_FLASH_FORCE"] = "pallas"
    try:
        got = fused_ops.flash_attention(q, k, v, is_causal=causal)
        gq, gk, gv = jax.grad(
            lambda a, b, c: jnp.sum(
                fused_ops.flash_attention(
                    a, b, c, is_causal=causal).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    finally:
        os.environ.pop("PADDLE_TPU_FLASH_FORCE", None)

    want = _sdpa_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)

    rq, rk, rv = jax.grad(
        lambda a, b, c: jnp.sum(_sdpa_ref(a, b, c, causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gtol = 3 * tol  # bwd chains two more reduced-precision matmuls
    for g, r in zip((gq, gk, gv), (rq, rk, rv)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32),
            rtol=gtol, atol=gtol)


def test_engine_train_step_on_chip():
    """One compiled Engine train step sequence on hardware: loss falls,
    params move, everything stays finite under bf16 autocast."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.engine import Engine

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(32, 64), nn.GELU(), nn.Linear(64, 8))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    eng = Engine(model, opt, lambda out, y: ((out - y) ** 2).mean())

    rng = np.random.RandomState(0)
    x = rng.randn(16, 32).astype(np.float32)
    y = rng.randn(16, 8).astype(np.float32)
    losses = []
    for _ in range(8):
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            losses.append(float(np.asarray(eng.train_batch(x, y)._value)))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] * 0.7, losses
    w = np.asarray(eng.state.params[next(iter(eng.state.params))])
    assert np.isfinite(w).all()


def test_static_executor_on_chip():
    """Static-graph Executor: build, minimize, run feed/fetch on the
    chip; loss must drop on a fit-a-line problem."""
    import paddle_tpu as paddle
    from paddle_tpu import static

    paddle.enable_static()
    try:
        main = static.Program()
        startup = static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [None, 4], "float32")
            y = static.data("y", [None, 1], "float32")
            pred = static.nn.fc(x, size=1)
            loss = paddle.mean((pred - y) ** 2)
            opt = paddle.optimizer.SGD(learning_rate=0.05)
            opt.minimize(loss)
        exe = static.Executor()
        exe.run(startup)
        rng = np.random.RandomState(1)
        xv = rng.randn(64, 4).astype(np.float32)
        yv = (xv @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
              + 0.1).astype(np.float32)
        first = None
        for _ in range(30):
            (lv,) = exe.run(main, feed={"x": xv, "y": yv},
                            fetch_list=[loss])
            if first is None:
                first = float(lv)
        assert float(lv) < 0.1 * first, (first, float(lv))
    finally:
        paddle.disable_static()


def test_bf16_matmul_mxu_tolerance():
    """bf16 on the MXU must stay within the expected error band of the
    f64 reference — catches accidental fp8/truncation regressions in
    default matmul precision."""
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(256, 512), jnp.bfloat16)
    b = jnp.asarray(rng.randn(512, 128), jnp.bfloat16)
    # reference: the SAME bf16-rounded inputs accumulated exactly in
    # f64 on host — isolates the MXU accumulation error from input
    # quantization (which any bf16 pipeline pays identically)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    got = np.asarray(a @ b, np.float64)
    # MXU accumulates bf16 products in f32: per-output error should be
    # far below one bf16 ulp of the O(sqrt(512)) outputs
    denom = np.maximum(np.abs(ref), 1.0)
    assert (np.abs(got - ref) / denom).max() < 1e-2


def test_dropout_rbg_prng_on_chip():
    """Dropout on the chip's bit generator (`keep_mask_u16` draws
    from `lax.rng_bit_generator`, the TPU's own algorithm, since PR
    39): masks are deterministic for a fixed key and differ across
    keys."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.core.tensor import Tensor
    import paddle_tpu as paddle

    x = Tensor(np.ones((64, 64), np.float32))
    paddle.seed(7)
    a = F.dropout(x, p=0.5, training=True).numpy()
    paddle.seed(7)
    b = F.dropout(x, p=0.5, training=True).numpy()
    paddle.seed(8)
    c = F.dropout(x, p=0.5, training=True).numpy()
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    frac = (a == 0).mean()
    assert 0.35 < frac < 0.65, frac


def test_max_pool_with_index_exact_on_chip():
    """Pool-with-index values must be bitwise the input elements the
    indices name, on the real chip: the patch-extraction conv runs at
    HIGHEST precision and out is gathered from x (ADVICE/code-review r5
    — default MXU precision quantized patch values)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor

    x = (np.random.RandomState(0).randn(2, 3, 33, 33)
         .astype(np.float32) * 4 - 4)
    out, idx = F.max_pool2d(Tensor(x), 3, stride=2, padding=1,
                            return_mask=True)
    o = np.asarray(out.numpy())
    i = np.asarray(idx.numpy())
    flat = x.reshape(2, 3, -1)
    np.testing.assert_array_equal(
        np.take_along_axis(flat, i.reshape(2, 3, -1), axis=2).ravel(),
        o.ravel())
    ref = F.max_pool2d(Tensor(x), 3, stride=2, padding=1)
    np.testing.assert_array_equal(o, np.asarray(ref.numpy()))

    x3 = (np.random.RandomState(1).randn(1, 2, 9, 9, 9)
          .astype(np.float32) * 4 - 4)
    o3, i3 = F.max_pool3d(Tensor(x3), 2, stride=2, padding=1,
                          return_mask=True)
    np.testing.assert_array_equal(
        np.take_along_axis(x3.reshape(1, 2, -1),
                           np.asarray(i3.numpy()).reshape(1, 2, -1),
                           axis=2).ravel(),
        np.asarray(o3.numpy()).ravel())


def test_device_op_table_on_chip(tmp_path):
    """On the real chip the xplane device plane carries XLA op spans:
    the per-op table must aggregate them (ref device_tracer.cc CUPTI
    correlation — here PJRT records, we parse)."""
    from paddle_tpu import profiler

    d = str(tmp_path / "trace")
    profiler.start_trace(d)
    x = jnp.ones((512, 512), jnp.bfloat16)
    for _ in range(3):
        x = (x @ x) / jnp.bfloat16(512.0)
    x.block_until_ready()
    profiler.stop_trace()
    table, rows = profiler.device_op_table(d, top=20)
    assert rows
    names = " ".join(r["name"] for r in rows)
    assert ("fusion" in names or "dot" in names or "convert" in names
            or "jit_" in names), names


# ---------------------------------------------------------------------------
# every kernel the default flags can select, compiled by Mosaic (not the
# interpreter) once at a shape the models use
# ---------------------------------------------------------------------------


def test_flash_attention_ernie_shape_with_dropout():
    """Flash fwd + both bwd kernels at the ERNIE-base step's shape
    (32, 12, 512, 64) bf16 with in-kernel dropout, through the DEFAULT
    dispatch (no FORCE override): head_dim 64 is half a lane tile."""
    from paddle_tpu.ops import fused_ops

    rng = np.random.default_rng(0)
    shape = (32, 12, 512, 64)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    seed = jnp.asarray(11, jnp.int32)

    def loss(a, b, c):
        o = fused_ops.flash_attention(a, b, c, seed, dropout_p=0.1)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    step = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _mosaic_calls(step, q, k, v) >= 3   # fwd, dq, dk/dv
    val, grads = jax.jit(step)(q, k, v)
    assert np.isfinite(float(val))
    for g in grads:
        assert g.shape == shape
        assert np.isfinite(np.asarray(g, np.float32)).all()
    # dropout really dropped: the undropped output differs, and the
    # same seed reproduces the same mask
    o_drop = fused_ops.flash_attention(q, k, v, seed, dropout_p=0.1)
    o_again = fused_ops.flash_attention(q, k, v, seed, dropout_p=0.1)
    o_plain = fused_ops.flash_attention(q, k, v)
    np.testing.assert_array_equal(np.asarray(o_drop, np.float32),
                                  np.asarray(o_again, np.float32))
    assert (np.asarray(o_drop, np.float32)
            != np.asarray(o_plain, np.float32)).any()


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 3e-2),
                                       (jnp.float32, 2e-2)])
def test_fused_lm_loss_ernie_shape(dtype, tol, monkeypatch):
    """The LM-head loss kernels at the ERNIE step's shape: N = 32*512
    rows, V = 18000 (NOT a multiple of the 1024 vocab chunk — the last
    chunk is 592 live columns), H = 768; fwd + dx + dw against the lax
    chunked twin on the same chip."""
    from paddle_tpu.ops import fused_loss as fl

    n, v, h = 16384, 18000, 768
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n, h)) * 0.5, dtype)
    w = jnp.asarray(rng.standard_normal((v, h)) * 0.05, dtype)
    lbl = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    lbl = lbl.at[::7].set(-100)

    def loss(a, b):
        return fl.fused_linear_cross_entropy(a, b, lbl)

    step = jax.value_and_grad(loss, argnums=(0, 1))
    before = fl._TRACE_COUNT
    assert _mosaic_calls(step, x, w) >= 3      # fwd, dx, dw
    assert fl._TRACE_COUNT > before
    got, (gx, gw) = jax.jit(step)(x, w)
    want, (rx, rw) = _lax_twin(monkeypatch, "PADDLE_TPU_LMLOSS_FORCE",
                               step, x, w)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    for g, r in ((gx, rx), (gw, rw)):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.isfinite(g).all()
        scale = np.abs(r).max()
        assert np.abs(g - r).max() <= tol * scale, \
            (np.abs(g - r).max(), scale)


# the gpt2-small tied head: hidden 768 -> vocab 50304, one row per slot
_HEAD_M, _HEAD_K, _HEAD_N = 8, 768, 50304


def test_dequant_matmul_gpt2_head_shape():
    from paddle_tpu.ops import quant_ops as qo

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((_HEAD_M, _HEAD_K)), jnp.float32)
    q = jnp.asarray(rng.integers(-127, 128, (_HEAD_N, _HEAD_K)), jnp.int8)
    s = jnp.asarray(rng.random(_HEAD_N) + 0.5, jnp.float32)
    before = qo._TRACE_COUNT
    assert _mosaic_calls(qo.dequant_matmul, x, q, s) >= 1
    assert qo._TRACE_COUNT > before
    got = np.asarray(jax.jit(qo.dequant_matmul)(x, q, s))
    want = np.asarray(x, np.float64) @ (
        np.asarray(q, np.float64) * (np.asarray(s, np.float64)
                                     / 127.0)[:, None]).T
    assert got.shape == (_HEAD_M, _HEAD_N)
    # the f32 operand goes through the MXU's default (bf16-pass)
    # precision: a band, not ulp parity
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_scaled_matmul_gpt2_head_shape(qdtype, monkeypatch):
    """scaled_matmul's kernels (scalars in (1, 1) SMEM blocks) against
    their lax twin on the chip; int8 accumulates in int32 on both
    paths, so only the f32 scale epilogue can differ."""
    from paddle_tpu.ops import lowp

    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((_HEAD_M, _HEAD_K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((_HEAD_K, _HEAD_N)) * 0.02,
                    jnp.float32)

    def fn(u, w):
        return lowp.scaled_matmul(u, w, qdtype=qdtype)

    before = lowp._TRACE_COUNT
    assert _mosaic_calls(fn, a, b) >= 1
    assert lowp._TRACE_COUNT > before
    got = np.asarray(jax.jit(fn)(a, b))
    want = np.asarray(_lax_twin(monkeypatch, "PADDLE_TPU_LOWP_FORCE",
                                fn, a, b))
    if qdtype == "int8":
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    else:
        assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


def test_w8a8_matmul_gpt2_head_shape(monkeypatch):
    from paddle_tpu.ops import lowp

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((_HEAD_M, _HEAD_K)), jnp.float32)
    qw = jnp.asarray(rng.integers(-127, 128, (_HEAD_K, _HEAD_N)), jnp.int8)

    def fn(u, w):
        return lowp.w8a8_matmul(u, w, 0.3, 4.0)

    assert _mosaic_calls(fn, x, qw) >= 1
    got = np.asarray(jax.jit(fn)(x, qw))
    want = np.asarray(_lax_twin(monkeypatch, "PADDLE_TPU_LOWP_FORCE",
                                fn, x, qw))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# the trainer and the serving engine, at test size
# ---------------------------------------------------------------------------


def test_ernie_width_engine_step_runs_both_kernels():
    """Engine.train_batch over ERNIE at full width (hidden 768, 12
    heads, vocab 18000, seq 512; depth cut to 2): the compiled step
    holds the flash and fused-LM-loss Mosaic kernels, compiles once and
    the loss falls."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, observe
    from paddle_tpu.engine import Engine
    from paddle_tpu.nlp.transformers import (
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )
    from paddle_tpu.ops import fused_loss as fl

    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=18000, hidden_size=768, num_layers=2,
                      num_heads=12, ffn_hidden_size=3072, max_seq_len=512,
                      dropout=0.1, attn_dropout=0.1, use_parallel=False)
    model = ErnieForPretraining(cfg)
    crit = ErniePretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    eng = Engine(model, opt, lambda out, y: crit(out[0], out[1], y))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 512)).astype(np.int32)
    labels = ids.copy()
    labels[rng.rand(8, 512) > 0.15] = -100
    observe.retrace.reset()
    before = fl._TRACE_COUNT
    losses = []
    with amp.auto_cast(enable=True, dtype="bfloat16"):
        for _ in range(6):
            losses.append(float(np.asarray(
                eng.train_batch(ids, labels)._value)))
        text = eng.compiled_text()
    assert fl._TRACE_COUNT > before
    # flash fwd + dq + dk/dv per layer, LM loss fwd + dx + dw
    assert text.count("tpu_custom_call") >= 6, text.count("tpu_custom_call")
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    steps = [e for e in observe.compile_events()
             if e["name"] == "train_step"]
    assert len(steps) == 1, steps


def test_meshed_engine_keeps_flash_kernel_on_chip():
    """A meshed Engine (a dp mesh over every visible chip — one is
    enough) keeps the Mosaic flash kernel: libtpu has no
    custom_partitioning and Mosaic refuses a mesh with an automatic
    axis left, so attention runs under a fully-manual shard_map
    (fused_ops._mesh_route).  Loss must match the unmeshed engine."""
    import paddle_tpu as paddle
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu import nn
    from paddle_tpu.engine import Engine

    class TinyAttn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(64, 64)

        def forward(self, x):       # x: [b, h, s, d]
            o = paddle.nn.functional.scaled_dot_product_attention(
                x, x, x, is_causal=True, qkv_layout="bhsd")
            return self.proj(o).mean()

    def build(mesh):
        paddle.seed(7)
        model = TinyAttn()
        opt = paddle.optimizer.SGD(learning_rate=0.0,
                                   parameters=model.parameters())
        kw = {} if mesh is None else dict(
            mesh=mesh, batch_spec=NamedSharding(mesh, P("dp")))
        return Engine(model, opt, lambda out, y: out, **kw)

    devs = jax.devices()
    b = 2 * len(devs)
    x = np.random.RandomState(4).randn(b, 4, 256, 64).astype(np.float32)
    y = np.zeros((b,), np.float32)
    ref = float(build(None).train_batch((x,), (y,)).item())
    eng = build(Mesh(np.array(devs), ("dp",)))
    got = float(eng.train_batch((x,), (y,)).item())
    # q = k = v is input data, so only the forward kernel survives DCE
    assert eng.compiled_text().count("tpu_custom_call") >= 1
    np.testing.assert_allclose(got, ref, rtol=1e-3)


def test_slot_engine_on_chip():
    """The serving engine's one compiled step on the chip: four
    requests (one longer than the prefill chunk, two sharing a prefix),
    in-vocabulary answers of the right length, compile counts frozen
    at one decode trace and one copy-on-write trace."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.nlp.transformers import GPTConfig, GPTForPretraining

    paddle.seed(5)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attn_dropout=0.0, use_parallel=False)
    eng = serving.SlotEngine(GPTForPretraining(cfg), max_slots=4,
                             block_size=8, prefill_chunk=8)
    eng.warmup()
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    eng.start()
    try:
        rng = np.random.RandomState(6)
        shared = rng.randint(1, 512, 12)
        prompts = [rng.randint(1, 512, 5), rng.randint(1, 512, 21),
                   np.concatenate([shared, rng.randint(1, 512, 3)]),
                   np.concatenate([shared, rng.randint(1, 512, 4)])]
        outs = [eng.submit(p, max_new_tokens=8, timeout=120.0)
                .result(timeout=120.0) for p in prompts]
    finally:
        eng.shutdown(drain=True)
    for p, out in zip(prompts, outs):
        assert out.shape == (p.size + 8,)
        np.testing.assert_array_equal(out[:p.size], p)
        assert ((out >= 0) & (out < cfg.vocab_size)).all()
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    assert eng.metrics.get("failed") == 0
    assert eng.metrics.get("prefix_hit_tokens") > 0
    # the chip updates the donated pools in place, every step
    assert eng.metrics.get("pool_inplace_steps") == \
        eng.metrics.get("steps") > 0


def test_resnet50_batch128_train_step_on_chip():
    """One compiled ResNet-50 train step at the bench's batch 128 under
    bf16 autocast: every conv plan shape the model has goes through the
    default routing on the chip."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.engine import Engine
    from paddle_tpu.ops import fused_conv as fc
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000, space_to_depth_stem=True)
    crit = nn.CrossEntropyLoss()
    opt = paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=model.parameters(),
        weight_decay=1e-4)
    eng = Engine(model, opt, lambda logits, y: crit(logits, y))
    rng = np.random.RandomState(0)
    imgs = rng.rand(128, 3, 224, 224).astype(np.float32)
    labels = rng.randint(0, 1000, (128,)).astype(np.int32)
    before = fc._TRACE_COUNT
    with amp.auto_cast(enable=True, dtype="bfloat16"):
        t0 = time.perf_counter()
        first = float(np.asarray(eng.train_batch(imgs, labels)._value))
        t1 = time.perf_counter()
        second = float(np.asarray(eng.train_batch(imgs, labels)._value))
        t2 = time.perf_counter()
    print(f"resnet50 b128: compile+step {t1 - t0:.1f}s, "
          f"step {1e3 * (t2 - t1):.0f} ms (smoke timing)")
    assert fc._TRACE_COUNT > before, \
        "compiled ResNet step never reached the pallas conv kernel"
    assert np.isfinite(first) and np.isfinite(second)


def test_fused_conv_every_resnet50_plan_shape_compiles():
    """Mosaic must take the conv kernel at EVERY shape ResNet-50's
    batch-128 train step sends it (forward, moments, fused-affine and
    the parity-decomposed backward convs) — traced abstractly to
    collect the distinct kernel signatures, then each compiled alone so
    one report names every shape the compiler refuses."""
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.engine import (
        buffer_values, functional_call, param_values,
    )
    from paddle_tpu.ops import fused_conv as fc
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    net = resnet50(num_classes=1000, space_to_depth_stem=True)
    net.train()
    params, buffers = dict(param_values(net)), dict(buffer_values(net))
    x = jax.ShapeDtypeStruct((128, 3, 224, 224), jnp.float32)

    seen = {}
    real = fc._pallas_conv

    def spy(xp, wk, plan, *, g=None, b=None, res=None, act="identity",
            moments=False, out_dtype=None):
        key = (xp.shape, str(xp.dtype), wk.shape, plan.ho, plan.wo,
               plan.ot, plan.kkh, plan.kkw, g is not None,
               res is not None, act, moments)
        seen.setdefault(key, plan)
        return real(xp, wk, plan, g=g, b=b, res=res, act=act,
                    moments=moments, out_dtype=out_dtype)

    def loss(p, img):
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            out = functional_call(net, {**buffers, **p}, img)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    fc._pallas_conv = spy
    try:
        jax.eval_shape(jax.grad(loss), params, x)
    finally:
        fc._pallas_conv = real
    assert len(seen) >= 20, len(seen)

    refused = []
    for key, plan in seen.items():
        (xs, xdt, ws, ho, wo, ot, kkh, kkw, fuse, has_res, act,
         moments) = key
        o = ws[-1]
        args = [jax.ShapeDtypeStruct(xs, xdt),
                jax.ShapeDtypeStruct(ws, xdt)]
        names = []
        if fuse:
            args += [jax.ShapeDtypeStruct((o,), jnp.float32)] * 2
            names += ["g", "b"]
        if has_res:
            args.append(jax.ShapeDtypeStruct((xs[0], ho, wo, o), xdt))
            names.append("res")

        def call(xp, wk, *rest, plan=plan, names=names, act=act,
                 moments=moments):
            return real(xp, wk, plan, act=act, moments=moments,
                        **dict(zip(names, rest)))

        try:
            jax.jit(call).lower(*args).compile()
        except Exception as e:  # noqa: BLE001 — collect every refusal
            refused.append(f"{key}: {str(e)[:300]}")
    assert not refused, (f"{len(refused)} of {len(seen)} conv "
                         "signatures refused:\n" + "\n".join(refused))


def test_second_process_cannot_take_the_chip():
    """One process per chip: this pytest process holds the TPU, so a
    child that asks for it must die with a message — not hang, and not
    quietly come up on another backend."""
    code = ("import jax; d = jax.devices()[0]; "
            "print('CHILD_PLATFORM', d.platform)")
    try:
        r = subprocess.run([sys.executable, "-c", code], timeout=240,
                           capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        pytest.fail("a second process asking for the held chip hung "
                    "for 240 s instead of failing")
    assert "CHILD_PLATFORM tpu" not in r.stdout, r.stdout
    assert r.returncode != 0, (r.stdout, r.stderr[-500:])
    assert r.stderr.strip(), "child died without a message"


def test_fused_conv_pallas_traces_inside_compiled_resnet():
    """The conv-fusion spy (review r6): a compiled ResNet train step on
    the chip must actually trace the Pallas fused-conv kernel — a
    silent fall-through to lax (probe failure, plan rejection on real
    shapes, flag plumbing) would still be numerically correct and
    invisible to every parity test, while quietly giving back the MFU
    the kernel exists to win."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import fused_conv as fc
    from paddle_tpu.vision.models import resnet18

    paddle.seed(31)
    net = resnet18(num_classes=8, space_to_depth_stem=True)
    net.train()
    x = paddle.to_tensor(np.random.RandomState(9)
                         .randn(8, 3, 64, 64).astype(np.float32))
    before = fc._TRACE_COUNT
    loss = paddle.mean(net(x) ** 2)
    loss.backward()
    assert fc._TRACE_COUNT > before, \
        "compiled ResNet step never reached the pallas conv kernel"
    assert np.isfinite(float(loss.numpy()))

    # the eval fused-affine path (folded BN) must route too
    net.eval()
    before = fc._TRACE_COUNT
    net(x)
    assert fc._TRACE_COUNT > before
