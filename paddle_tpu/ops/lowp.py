"""Low-precision scaled-matmul family: int8 / fp8-sim compute behind
``FLAGS_lowp_matmul``.

Ref parity: the fluid-era Paddle reached low-precision compute with
slim/QAT program passes that rewrote matmuls against calibrated scales.
Here the jax-native answer is ONE kernel family shared by the training
step and the serving decode trace:

  scaled_matmul(a, b, a_scale, b_scale)   custom_vjp — the standard
      recipe: low-precision forward (int8 with int32 accumulation, or
      bit-faithful e4m3 emulation with f32 accumulation), bf16
      backward against the saved full-precision operands.
  w8a8_matmul(x, qweight, scale, act_scale)   the serving epilogue:
      activations quantize in-trace against a frozen per-tensor scale
      and contract directly with an int8-frozen table (the
      quant_ops.dequant_matmul extension from weights-only to w8a8).

Scale semantics (shared with quantization/): a scale is the
REPRESENTABLE ABS-MAX of its tensor — ``q = clip(round(x/s * qmax))``
for int8 (qmax 127, matching quantize_weight_int8) and
``q = e4m3(x/s * 448)`` for fp8 — so the int8 epilogue factor
``s_a*s_b/127**2`` composes with the weights-only tables unchanged.

Scales come from three places, in priority order: explicit arguments
(serving's frozen scales), the active delayed-scaling region
(quantization/scaling.py ScaleState threaded through the train step as
donated carry — never a host sync or retrace), or dynamic current-step
abs-max (everywhere else: the hybrid block scan, the overlap-ring
per-shard partials, eager calls).

Three execution paths, selected by platform only (as in
quant_ops/fused_loss):
  * Pallas TPU kernels when FLAGS_use_pallas and the backend is TPU;
    a lowering Mosaic refuses raises — no fallback on the chip.
  * The same kernels in interpreter mode when
    PADDLE_TPU_LOWP_FORCE=pallas off-TPU, so CPU tier-1 certifies the
    exact kernel math (int8 parity with the lax path is bitwise:
    identical quantize, int32 accumulation, f32 epilogue).
  * A pure-lax fallback everywhere else.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import monitor

__all__ = [
    "mode", "scaled_matmul", "w8a8_matmul", "maybe_linear",
    "scale_region", "current", "operand_scales", "QMAX",
]

#: representable-abs-max -> code-point factor per quantized dtype
QMAX = {"int8": 127.0, "fp8": 448.0}

_Q_BLOCK_M = 256
_Q_BLOCK_N = 256
_EPS = 1e-9

# incremented whenever a pallas lowp matmul is traced (not the lax
# fallback) — tests assert the forced path really goes through the
# kernels rather than silently falling back
_TRACE_COUNT = 0

_warned_slots = False


def mode() -> str:
    """'off' | 'int8' | 'fp8' from FLAGS_lowp_matmul."""
    from ..framework.flags import flag

    m = str(flag("FLAGS_lowp_matmul")).strip().lower()
    if m in ("", "0", "false", "no", "none", "off"):
        return "off"
    if m not in QMAX:
        raise ValueError(
            f"FLAGS_lowp_matmul must be off|int8|fp8, got {m!r}")
    return m


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def _use_pallas_lowp() -> bool:
    force = os.environ.get("PADDLE_TPU_LOWP_FORCE", "")
    if force == "pallas":
        return True
    if force == "lax":
        return False
    from ..framework.flags import flag

    return flag("FLAGS_use_pallas") and jax.default_backend() == "tpu"


def _interpret() -> bool:
    return (os.environ.get("PADDLE_TPU_LOWP_FORCE", "") == "pallas"
            and jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# quantize helpers (per-tensor; scale = representable abs-max)
# ---------------------------------------------------------------------------


def amax_of(x):
    """The QAT observers' abs-max statistic (quantization/: the EMA
    observer and quantize_weight_int8 reduce the same way), clamped
    away from zero and gradient-stopped — the scale input."""
    return jnp.maximum(
        jnp.max(jnp.abs(lax.stop_gradient(x.astype(jnp.float32)))), _EPS)


def _quant_int8(x, s):
    q = jnp.round(x.astype(jnp.float32) * (127.0 / s))
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def _quant_f8(x, s):
    """Bit-faithful e4m3 emulation: scale to the fp8 dynamic range,
    saturate (the e4m3fn cast maps overflow to NaN, so clip first) and
    round-trip through the hardware dtype."""
    y = jnp.clip(x.astype(jnp.float32) * (448.0 / s), -448.0, 448.0)
    return y.astype(jnp.float8_e4m3fn)


# ---------------------------------------------------------------------------
# lax path (identical math to the kernels: int8 accumulates int32 so
# pallas-vs-lax int8 parity is bitwise; fp8 accumulates f32)
# ---------------------------------------------------------------------------


def _mm_dims(ca, cb):
    return (((ca,), (cb,)), ((), ()))


def _smm_lax(a, b, sa, sb, qdtype):
    if qdtype == "int8":
        acc = lax.dot_general(_quant_int8(a, sa), _quant_int8(b, sb),
                              _mm_dims(1, 0),
                              preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * (sa * sb / (127.0 * 127.0))
    qa = _quant_f8(a, sa).astype(jnp.float32)
    qb = _quant_f8(b, sb).astype(jnp.float32)
    acc = lax.dot_general(qa, qb, _mm_dims(1, 0),
                          preferred_element_type=jnp.float32)
    return acc * (sa * sb / (448.0 * 448.0))


def _w8a8_lax(a, qb, sb, sa):
    acc = lax.dot_general(_quant_int8(a, sa), qb, _mm_dims(1, 0),
                          preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (sa * sb / (127.0 * 127.0))


# ---------------------------------------------------------------------------
# pallas kernels: grid (M/bm, N/bn), full K per tile, scales in SMEM
# ---------------------------------------------------------------------------


def _qmm_kernel(sa_ref, sb_ref, a_ref, b_ref, o_ref, *, qdtype):
    sa = sa_ref[0, 0]
    sb = sb_ref[0, 0]
    if qdtype == "int8":
        qa = _quant_int8(a_ref[...], sa)
        qb = _quant_int8(b_ref[...], sb)
        acc = lax.dot_general(qa, qb, _mm_dims(1, 0),
                              preferred_element_type=jnp.int32)
        o_ref[...] = acc.astype(jnp.float32) * (sa * sb / (127.0 * 127.0))
    else:
        qa = _quant_f8(a_ref[...], sa).astype(jnp.float32)
        qb = _quant_f8(b_ref[...], sb).astype(jnp.float32)
        acc = lax.dot_general(qa, qb, _mm_dims(1, 0),
                              preferred_element_type=jnp.float32)
        o_ref[...] = acc * (sa * sb / (448.0 * 448.0))


def _w8a8_kernel(sa_ref, sb_ref, a_ref, qb_ref, o_ref):
    sa = sa_ref[0, 0]
    sb = sb_ref[0, 0]
    qa = _quant_int8(a_ref[...], sa)
    acc = lax.dot_general(qa, qb_ref[...], _mm_dims(1, 0),
                          preferred_element_type=jnp.int32)
    o_ref[...] = acc.astype(jnp.float32) * (sa * sb / (127.0 * 127.0))


def _smem11(s):
    return jnp.broadcast_to(jnp.asarray(s, jnp.float32), (1, 1))


def _pallas_mm(kernel, name, a, b, sa, sb):
    """Shared pad/grid/specs for the quantizing matmul kernels: a
    [m, k] float, b [k, n] float or int8, scalars in SMEM; zero padding
    quantizes to zero so the padded contraction is exact. `name` is the
    kernel's in a compiled program and a capture."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    m, k = a.shape
    n = b.shape[1]
    bm = min(_Q_BLOCK_M, _round_up(m, 8))
    bn = min(_Q_BLOCK_N, _round_up(n, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, 128)
    ap = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    bp = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    smem = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                        memory_space=pltpu.SMEM)
    vmem = pltpu.VMEM
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            smem, smem,
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0),
                         memory_space=vmem),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j),
                         memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=vmem),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=_interpret(),
    )(_smem11(sa), _smem11(sb), ap, bp)
    return out[:m, :n]


def _smm_pallas(a, b, sa, sb, qdtype):
    return _pallas_mm(functools.partial(_qmm_kernel, qdtype=qdtype),
                      "lowp_scaled_matmul", a, b, sa, sb)


def _w8a8_pallas(a, qb, sb, sa):
    return _pallas_mm(_w8a8_kernel, "w8a8_matmul", a, qb, sa, sb)


# ---------------------------------------------------------------------------
# custom_vjp: lowp forward, bf16 backward (standard recipe)
# ---------------------------------------------------------------------------


def _fwd_dispatch(a, b, sa, sb, qdtype):
    # trace-time: one quantized-matmul instance per compiled program
    monitor.stat_add(f"lowp.matmuls_{qdtype}")
    if _use_pallas_lowp():
        return _smm_pallas(a, b, sa, sb, qdtype)
    return _smm_lax(a, b, sa, sb, qdtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _smm(a, b, sa, sb, qdtype):
    return _fwd_dispatch(a, b, sa, sb, qdtype)


def _smm_fwd_rule(a, b, sa, sb, qdtype):
    return _fwd_dispatch(a, b, sa, sb, qdtype), (a, b, sa, sb)


def _smm_bwd_rule(qdtype, res, g):
    a, b, sa, sb = res
    # high-precision backward: bf16 operands into the MXU with f32
    # accumulation against the SAVED full-precision inputs — gradients
    # never see the quantization error (straight-through)
    g16 = g.astype(jnp.bfloat16)
    da = lax.dot_general(g16, b.astype(jnp.bfloat16), _mm_dims(1, 1),
                         preferred_element_type=jnp.float32)
    db = lax.dot_general(a.astype(jnp.bfloat16), g16, _mm_dims(0, 0),
                         preferred_element_type=jnp.float32)
    return (da.astype(a.dtype), db.astype(b.dtype),
            jnp.zeros_like(sa), jnp.zeros_like(sb))


_smm.defvjp(_smm_fwd_rule, _smm_bwd_rule)


def scaled_matmul(a, b, a_scale=None, b_scale=None, out_dtype=None,
                  qdtype=None):
    """``a @ b`` computed in low precision with f32/int32 accumulation.

    a: (..., K) float, b: (K, N) float. Scales are per-tensor
    representable-abs-max scalars; None computes the current-step
    abs-max (dynamic scaling — exact range, zero clipping). qdtype
    None follows FLAGS_lowp_matmul ('off' there still computes int8 —
    callers gate routing, this op always quantizes). The custom_vjp
    backward runs bf16 against the full-precision operands.
    """
    if qdtype is None:
        m = mode()
        qdtype = m if m != "off" else "int8"
    if qdtype not in QMAX:
        raise ValueError(f"qdtype must be int8|fp8, got {qdtype!r}")
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim < 1 or b.ndim != 2:
        raise ValueError(
            f"scaled_matmul expects a (..., K) and b (K, N); got "
            f"{a.shape} x {b.shape}")
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    sa = amax_of(a2) if a_scale is None \
        else jnp.maximum(jnp.asarray(a_scale, jnp.float32), _EPS)
    sb = amax_of(b) if b_scale is None \
        else jnp.maximum(jnp.asarray(b_scale, jnp.float32), _EPS)
    out = _smm(a2, b, sa, sb, qdtype)
    out = out.reshape(lead + (b.shape[1],))
    return out if out_dtype is None else out.astype(out_dtype)


def w8a8_matmul(x, qweight, scale, act_scale):
    """w8a8 decode epilogue: quantize activation rows to int8 against
    the frozen per-tensor `act_scale` and contract with an int8-frozen
    table (`qweight` [K, N] or its [N, K] quantize_state_int8 layout is
    the CALLER's concern — pass it contraction-ready). No grad: the
    serving trace never differentiates."""
    monitor.stat_add("lowp.matmuls_int8")
    x = jnp.asarray(x)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    sb = jnp.maximum(jnp.asarray(scale, jnp.float32), _EPS)
    sa = jnp.maximum(jnp.asarray(act_scale, jnp.float32), _EPS)
    if _use_pallas_lowp():
        out = _w8a8_pallas(x2, qweight, sb, sa)
    else:
        out = _w8a8_lax(x2, qweight, sb, sa)
    return lax.stop_gradient(out.reshape(lead + (qweight.shape[1],)))


# ---------------------------------------------------------------------------
# delayed-scaling region (the train-step ScaleState carry) + routing
# ---------------------------------------------------------------------------

_tls = threading.local()


class _ScaleRegion:
    """Trace-time recorder binding ScaleState slots to matmul call
    sites in (deterministic) trace order. All recorded values are
    tracers of the enclosing loss trace; `updated()` must be consumed
    before that trace returns (the engine folds it into the new
    buffers)."""

    def __init__(self, state):
        self.state = state
        self.capacity = int(state.scale.shape[0])
        self.n = 0
        self._amax = {}          # slot -> recorded abs-max scalar
        self._clipped = jnp.zeros((), jnp.float32)
        self._total = jnp.zeros((), jnp.float32)

    def slot(self):
        i = self.n
        self.n += 1
        if i >= self.capacity:
            global _warned_slots
            if not _warned_slots:
                _warned_slots = True
                import warnings

                warnings.warn(
                    f"lowp: more quantized matmul operands than the "
                    f"ScaleState capacity {self.capacity} "
                    "(FLAGS_lowp_slots); extras use dynamic scaling")
            monitor.stat_add("lowp.slot_overflow")
            return None
        return i

    def scale_for(self, i, x):
        """Delayed scale for slot i; the very first step has an empty
        history, so it falls back to the current-step abs-max."""
        return jnp.where(self.state.step > 0,
                         jnp.maximum(self.state.scale[i], _EPS),
                         amax_of(x))

    def record(self, i, x, s):
        xf = lax.stop_gradient(x.astype(jnp.float32))
        self._amax[i] = amax_of(x)
        self._clipped = self._clipped + jnp.sum(
            (jnp.abs(xf) > s).astype(jnp.float32))
        self._total = self._total + jnp.asarray(float(x.size),
                                                jnp.float32)

    def updated(self):
        """The next ScaleState: ring-write this step's amaxes and run
        the delayed-scale update schedule (in-graph, no host sync)."""
        from ..quantization.scaling import update_scale_state

        cap = self.capacity
        amax = jnp.zeros((cap,), jnp.float32)
        mask = jnp.zeros((cap,), jnp.bool_)
        for i, v in self._amax.items():
            amax = amax.at[i].set(v)
            mask = mask.at[i].set(True)
        return update_scale_state(self.state, amax, mask,
                                  self._clipped, self._total)


@contextlib.contextmanager
def scale_region(state):
    """Bind a ScaleState to the matmuls of the enclosed trace. None
    (or lowp off) is a no-op yielding None; routing then uses dynamic
    scales."""
    if state is None or mode() == "off":
        yield None
        return
    prev = getattr(_tls, "region", None)
    _tls.region = _ScaleRegion(state)
    try:
        yield _tls.region
    finally:
        _tls.region = prev


def current():
    """The active delayed-scaling region, or None."""
    return getattr(_tls, "region", None)


@contextlib.contextmanager
def suppress_region():
    """Hide the active region from the enclosed code: sub-traces
    (jax.checkpoint segments, scan bodies, shard_map bodies) must not
    record their tracers into the outer trace's region — their matmuls
    quantize with dynamic scales instead."""
    prev = getattr(_tls, "region", None)
    _tls.region = None
    try:
        yield
    finally:
        _tls.region = prev


def operand_scales(a, b):
    """(a_scale, b_scale) for one matmul: delayed-scaling slots when a
    region is active, dynamic abs-max otherwise. Also records this
    step's amaxes + clip counts into the region."""
    ctx = current()
    if ctx is None:
        return amax_of(a), amax_of(b)
    ia, ib = ctx.slot(), ctx.slot()
    sa = amax_of(a) if ia is None else ctx.scale_for(ia, a)
    sb = amax_of(b) if ib is None else ctx.scale_for(ib, b)
    if ia is not None:
        ctx.record(ia, a, sa)
    if ib is not None:
        ctx.record(ib, b, sb)
    return sa, sb


def maybe_linear(x, weight):
    """Lowp route for F.linear (bias NOT applied): returns the output
    Tensor, or None to keep the matmul_v2 path — flag off, tape-based
    autograd in flight, or non-float/low-rank operands. The bitwise
    contract: 'off' returns None before touching anything."""
    if mode() == "off":
        return None
    from ..core.tensor import Tensor

    if not isinstance(x, Tensor) or not isinstance(weight, Tensor):
        return None
    if getattr(x, "_tape", None) is not None or \
            getattr(weight, "_tape", None) is not None:
        return None
    xv, wv = x._value, weight._value
    if xv.ndim < 2 or wv.ndim != 2:
        return None
    if not (jnp.issubdtype(xv.dtype, jnp.floating)
            and jnp.issubdtype(wv.dtype, jnp.floating)):
        return None
    m = mode()
    sa, sb = operand_scales(xv, wv)
    out = scaled_matmul(xv, wv, sa, sb, qdtype=m,
                        out_dtype=jnp.result_type(xv.dtype, wv.dtype))
    return Tensor(out)
