"""Fake-quantization ops.

Ref parity: paddle/fluid/operators/fake_quantize_op.cc kernels behind
the slim quantization passes (python/paddle/fluid/contrib/slim/
quantization/quantization_pass.py op set).  None of the code mirrors the
reference kernels — each op is a pure jnp composition.

TPU-native design: quant-dequant is SIMULATED in float arithmetic with a
straight-through estimator spelled as `x + stop_gradient(qdq(x) - x)`,
so one registered op serves QAT training, PTQ calibration, and frozen
inference under jit with no custom gradient plumbing (the reference
pairs each fake_quantize op with a pass-through grad op).  True int8
storage happens at freeze time in paddle_tpu.quantization, where weights
are kept as int8 arrays and dequantized on the fly — on TPU the win is
HBM bytes, not int8 ALUs, so dequant-to-bf16 before the MXU matmul is
the native lowering.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.op_registry import register_op


def _qmax(bit_length):
    return float(2 ** (bit_length - 1) - 1)


def quant_dequant(x, scale, qmax):
    """Symmetric uniform quantize-dequantize: round(x/scale*qmax) bucket
    values, clipped to [-qmax, qmax], mapped back to float."""
    s = jnp.maximum(jnp.asarray(scale, x.dtype), 1e-9)
    q = jnp.clip(jnp.round(x / s * qmax), -qmax, qmax)
    return q * s / qmax


def _ste(x, y):
    """Straight-through estimator: forward y, gradient of identity."""
    return x + lax.stop_gradient(y - x)


@register_op("fake_quantize_dequantize_abs_max", has_aux=True)
def fake_quantize_dequantize_abs_max(x, *, bit_length=8):
    """ref fake_quantize_op.cc FakeQuantizeDequantizeAbsMax: per-tensor
    dynamic scale = max|x|; returns (out, scale)."""
    qmax = _qmax(bit_length)
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32)
    y = _ste(x, quant_dequant(x, scale, qmax))
    return y, lax.stop_gradient(scale)


@register_op("fake_channel_wise_quantize_dequantize_abs_max", has_aux=True)
def fake_channel_wise_quantize_dequantize_abs_max(x, *, bit_length=8,
                                                  quant_axis=0):
    """ref fake_quantize_op.cc channel-wise variant: one scale per slice
    along quant_axis (conv OIHW -> axis 0; linear [in,out] -> axis 1)."""
    qmax = _qmax(bit_length)
    axes = tuple(a for a in range(x.ndim) if a != quant_axis)
    scale = jnp.max(jnp.abs(x), axis=axes).astype(jnp.float32)
    sshape = [1] * x.ndim
    sshape[quant_axis] = x.shape[quant_axis]
    y = _ste(x, quant_dequant(x, scale.reshape(sshape), qmax))
    return y, lax.stop_gradient(scale)


@register_op("fake_quantize_dequantize_moving_average_abs_max",
             has_aux=True)
def fake_quantize_dequantize_moving_average_abs_max(
        x, in_scale, *, bit_length=8, moving_rate=0.9, is_test=False):
    """ref fake_quantize_op.cc moving-average variant: activations keep
    an EMA of per-batch abs-max; inference freezes it.  Returns
    (out, new_scale) — the caller threads new_scale back into its
    buffer, exactly the running-stat pattern batch_norm uses."""
    qmax = _qmax(bit_length)
    in_scale = jnp.asarray(in_scale, jnp.float32).reshape(())
    if is_test:
        scale = in_scale
    else:
        cur = jnp.max(jnp.abs(x)).astype(jnp.float32)
        # first batch (scale==0) adopts the batch stat outright so the
        # EMA never anchors on the zero init
        ema = moving_rate * in_scale + (1.0 - moving_rate) * cur
        scale = jnp.where(in_scale > 0, ema, cur)
    # an uncalibrated scale (eval/export before any training batch) must
    # pass the activation through, not clamp it to ~0
    y = jnp.where(scale > 0, _ste(x, quant_dequant(x, scale, qmax)), x)
    return y, lax.stop_gradient(scale)


# ---------------------------------------------------------------------------
# frozen-int8 decode path: in-trace dequant + dequant-matmul epilogue
# ---------------------------------------------------------------------------
#
# The serving engine freezes weights to int8 at build time
# (quantization.quantize_state_int8) and dequantizes inside the one
# compiled decode trace.  Two primitives live here:
#
#   dequant_int8(q, scale)      the ONE dequant formula everywhere:
#                               q_f32 * (scale / 127.0).  Engine body,
#                               rollout golden digests, and the freeze
#                               helpers all share it so the canary gate
#                               stays bitwise.
#   dequant_matmul(x, q, scale) x @ dequant(q).T with the dequant as a
#                               matmul EPILOGUE: contract against the
#                               raw int8 rows (f32 accumulate) and scale
#                               the [*, N] output tile — exact for
#                               per-tensor / per-row scales because
#                               column scaling commutes with the
#                               contraction, and the int8 operand is
#                               what rides HBM.
#
# Execution paths, selected by platform only (as in fused_loss):
#   * Pallas TPU kernel when FLAGS_use_pallas and backend==tpu; a
#     lowering Mosaic refuses raises — no fallback on the chip.
#   * The same kernel in interpreter mode when
#     PADDLE_TPU_QUANT_FORCE=pallas off-TPU, so CPU tier-1 certifies
#     the exact kernel math.
#   * A pure-lax fallback everywhere else — identical formula.

# row/column tiles: int8 min tile on TPU is (32, 128), f32 is (8, 128);
# K is carried whole per tile (LM-head K = hidden size, a few hundred)
_DQ_BLOCK_M = 256
_DQ_BLOCK_N = 512

# incremented whenever the pallas dequant-matmul is traced (not the lax
# fallback) — tests assert the forced path really hits the kernel
_TRACE_COUNT = 0


def _mm(a, b, ca: int, cb: int):
    """Matmul contracting a's dim `ca` with b's dim `cb`, f32 accumulate
    (see fused_ops._mm — the MXU reads either operand orientation
    natively; an explicit .T would materialise a relayout)."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def _use_pallas_quant() -> bool:
    force = os.environ.get("PADDLE_TPU_QUANT_FORCE", "")
    if force == "pallas":
        return True
    if force == "lax":
        return False
    from ..framework.flags import flag

    return flag("FLAGS_use_pallas") and jax.default_backend() == "tpu"


def _interpret() -> bool:
    return (os.environ.get("PADDLE_TPU_QUANT_FORCE", "") == "pallas"
            and jax.default_backend() != "tpu")


def dequant_int8(q, scale):
    """Canonical int8 dequant: q_f32 * (scale / 127.0).

    Every consumer of a frozen weight set (decode-trace body, rollout
    golden digests, test references) must use this exact expression —
    epilogue dequant in `dequant_matmul` is algebraically equal but not
    bitwise, so the bitwise contracts pin which formula runs where."""
    return q.astype(jnp.float32) * (jnp.asarray(scale, jnp.float32)
                                    / 127.0)


def _dq_kernel(x_ref, q_ref, s_ref, o_ref):
    # x (bm, K) · q (bn, K) int8 -> o (bm, bn) f32, scale epilogue on
    # the output tile; s rides as (bn, 8) broadcast rows (scalar-per-row
    # VMEM idiom, see fused_loss._row8)
    acc = _mm(x_ref[...], q_ref[...].astype(jnp.float32), 1, 1)
    o_ref[...] = acc * (s_ref[:, 0][None, :] / 127.0)


def _dq_mm_pallas(x2, q, scale):
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    m, k = x2.shape
    n = q.shape[0]
    bm = min(_DQ_BLOCK_M, _round_up(m, 8))
    bn = min(_DQ_BLOCK_N, _round_up(n, 32))
    kp = _round_up(k, 128)
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    xp = jnp.zeros((mp, kp), x2.dtype).at[:m, :k].set(x2)
    qp = jnp.zeros((np_, kp), q.dtype).at[:n, :k].set(q)
    sp = jnp.zeros((np_, 8), jnp.float32).at[:n, :].set(
        jnp.broadcast_to(scale[:, None], (n, 8)))
    vmem = pltpu.VMEM
    bspec = lambda shape, imap: pl.BlockSpec(  # noqa: E731
        shape, imap, memory_space=vmem)
    out = pl.pallas_call(
        _dq_kernel,
        name="dequant_matmul",
        grid=(mp // bm, np_ // bn),
        in_specs=[bspec((bm, kp), lambda i, j: (i, 0)),
                  bspec((bn, kp), lambda i, j: (j, 0)),
                  bspec((bn, 8), lambda i, j: (j, 0))],
        out_specs=bspec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=_interpret(),
    )(xp, qp, sp)
    return out[:m, :n]


@register_op("dequant_matmul", no_grad=True)
def dequant_matmul(x, qweight, scale):
    """out = x @ dequant_int8(qweight, scale).T without materialising
    the dequantized weight: contract f32 activations against the raw
    int8 rows and apply `scale/127` as an output epilogue.

    x: (..., K) activations; qweight: (N, K) int8 (LM head = the tied
    embedding table); scale: scalar or (N,) per-row f32.  Returns
    (..., N) float32 logits.  Exact (in real arithmetic) vs operand
    dequant since the per-output-column scale commutes with the K
    contraction; bitwise it is a DIFFERENT formula, which is why the
    serving engine and the rollout golden digests both route the head
    through this op."""
    x = jnp.asarray(x)
    lead, k = x.shape[:-1], x.shape[-1]
    n = qweight.shape[0]
    x2 = x.reshape(-1, k)
    sc = jnp.asarray(scale, jnp.float32).reshape(-1)
    if sc.size == 1:
        sc = jnp.broadcast_to(sc, (n,))
    if _use_pallas_quant():
        out = _dq_mm_pallas(x2, qweight, sc)
    else:
        out = _mm(x2, qweight.astype(jnp.float32), 1, 1) \
            * (sc[None, :] / 127.0)
    return out.reshape(*lead, n)
