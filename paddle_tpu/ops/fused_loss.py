"""Fused LM-head loss: chunked-vocab linear + cross-entropy that never
materializes the `[N, V]` logits.

Ref parity: the reference computes the tied-decoder projection
(matmul_v2 against the embedding table) and then
softmax_with_cross_entropy as two ops, paying `[N, V]` of HBM in forward
and again in backward.  Here both collapse into one streaming op
(flash-attention / Liger-Kernel lineage — the same online-logsumexp
trick fused_ops.py uses over keys, applied over vocab chunks):

  forward   streams `[cv, H]` chunks of the weight through VMEM, keeps a
            per-row online (max, sumexp, picked-logit) triple in f32, and
            emits only per-row `nll = lse - s[label]` and `lse`.
  backward  re-streams the same chunks, rebuilds each score tile from
            (x, w, lse), forms `dlogits = softmax - onehot` in-register
            and contracts it immediately into dx / dw f32 accumulators —
            the logits gradient also never touches HBM.

Numerics match `cross_entropy(matmul(x, w.T))` exactly at fp32 (same
lse formulation) and to bf16 tolerance under AMP: operands stay bf16
into the MXU with f32 accumulation (`_mm`), loss/lse are f32.

Three execution paths, selected by platform only:
  * Pallas TPU kernels when `FLAGS_use_pallas` and the backend is TPU;
    a kernel Mosaic refuses raises out of the step with Mosaic's
    message — there is no fallback on the chip.
  * The same kernels in interpreter mode when
    PADDLE_TPU_LMLOSS_FORCE=pallas off-TPU, so CPU tier-1 certifies the
    exact kernel math + backward.
  * A pure-lax `lax.scan` chunked fallback everywhere else — same
    no-materialization memory profile (XLA sees only `[N, cv]` tiles).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.op_registry import register_op

_NEG_INF = -1e30

# Row block / vocab chunk: VMEM at (256, 1024, H=768) — x tile 384KB
# bf16, w chunk 1.5MB bf16, score tile 1MB f32, dw accumulator 3MB f32 —
# comfortably under the 16MB/core budget while keeping the MXU matmuls
# large enough that grid overhead doesn't dominate (same sizing logic as
# fused_ops._BLOCK_Q/_BLOCK_K).
_BLOCK_N = 256
_CHUNK_V = 1024

# incremented whenever a pallas lm-loss is traced (not the lax
# fallback) — tests assert the forced path really goes through the
# kernels rather than silently falling back
_TRACE_COUNT = 0


def _mm(a, b, ca: int, cb: int):
    """Matmul contracting a's dim `ca` with b's dim `cb`, f32 accumulate
    (see fused_ops._mm: the MXU reads either operand orientation
    natively; an explicit .T would materialise a relayout)."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def _use_pallas_lm() -> bool:
    force = os.environ.get("PADDLE_TPU_LMLOSS_FORCE", "")
    if force == "pallas":
        return True
    if force == "lax":
        return False
    from ..framework.flags import flag

    return flag("FLAGS_use_pallas") and jax.default_backend() == "tpu"


def _interpret() -> bool:
    return (os.environ.get("PADDLE_TPU_LMLOSS_FORCE", "") == "pallas"
            and jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------
#
# Layout notes (idioms from fused_ops.py):
#   * per-row scalars (labels, lse, loss, upstream g) travel as (N, 8)
#     broadcasts — Mosaic pads lanes to 128 in VMEM but HBM only moves 8.
#   * block offsets arrive as (n, 8, 128) int32 data inputs instead of
#     pl.program_id, which fails to re-trace under nested AD here.
#   * the sequential grid dim accumulates into VMEM f32 scratch with
#     @pl.when init on the first slot and write-out on the last.


def _off_inputs(n, step):
    """(n, 8, 128) int32 block-offset input: [i*step] broadcast."""
    return jnp.broadcast_to(
        (jnp.arange(n, dtype=jnp.int32) * step)[:, None, None],
        (n, 8, 128))


def _row8(v, n_pad):
    """Pad a per-row (N,) vector to (n_pad, 8) f32/i32 broadcast."""
    v = jnp.pad(v, (0, n_pad - v.shape[0]),
                constant_values=jnp.zeros((), v.dtype))
    return jnp.broadcast_to(v[:, None], (n_pad, 8))


def _fwd_kernel(voff_ref, x_ref, w_ref, lbl_ref, loss_ref, lse_ref,
                m_sc, l_sc, p_sc, *, vocab, last_voff):
    # x_ref: (bn, H), w_ref: (cv, H), lbl_ref: (bn, 8) int32,
    # loss/lse_ref: (bn, 8) f32; scratch m/l/p: (bn, 8) f32 carrying the
    # online (running max, sumexp, picked logit) across vocab chunks.
    bn = x_ref.shape[0]
    cv = w_ref.shape[0]
    v_off = voff_ref[0, 0, 0]

    @pl.when(v_off == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        p_sc[...] = jnp.zeros_like(p_sc)

    x = x_ref[...]
    w = w_ref[...]
    s = _mm(x, w, 1, 1)  # (bn, cv) f32 scores for this vocab chunk
    col = v_off + lax.broadcasted_iota(jnp.int32, (bn, cv), 1)
    valid = col < vocab
    s = jnp.where(valid, s, _NEG_INF)
    lbl = lbl_ref[:, :1]
    m_i = m_sc[:, :1]
    l_i = l_sc[:, :1]
    m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
    l_new = l_i * jnp.exp(m_i - m_new) + \
        jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True)
    hit = valid & (col == lbl)
    p_new = p_sc[:, :1] + jnp.sum(jnp.where(hit, s, 0.0), axis=-1,
                                  keepdims=True)
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)
    p_sc[...] = jnp.broadcast_to(p_new, p_sc.shape)

    @pl.when(v_off == last_voff)
    def _done():
        # every row sees >= 1 valid column, so l >= exp(0) after the
        # running max: no zero guard needed (unlike flash's masked rows)
        lse = m_sc[:, :1] + jnp.log(l_sc[:, :1])
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        loss_ref[...] = jnp.broadcast_to(lse - p_sc[:, :1],
                                         loss_ref.shape)


def _dlogits(x, w, v_off, vocab, lbl, lse, g):
    """(softmax - onehot) * g for one score tile, rebuilt from lse —
    shared by the dx and dw kernels so both see identical tiles."""
    bn = x.shape[0]
    cv = w.shape[0]
    s = _mm(x, w, 1, 1)
    col = v_off + lax.broadcasted_iota(jnp.int32, (bn, cv), 1)
    valid = col < vocab
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    hit = valid & (col == lbl)
    return (p - hit.astype(jnp.float32)) * g


def _bwd_dx_kernel(voff_ref, x_ref, w_ref, lbl_ref, lse_ref, g_ref,
                   dx_ref, acc_sc, *, vocab, last_voff):
    v_off = voff_ref[0, 0, 0]

    @pl.when(v_off == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    x = x_ref[...]
    w = w_ref[...]
    d = _dlogits(x, w, v_off, vocab, lbl_ref[:, :1], lse_ref[:, :1],
                 g_ref[:, :1])
    # dx += d @ w: contract the chunk dim; d drops to the operand dtype
    # so the MXU stays at bf16 throughput (accumulator is f32 scratch)
    acc_sc[...] += _mm(d.astype(x.dtype), w, 1, 0)

    @pl.when(v_off == last_voff)
    def _done():
        dx_ref[...] = acc_sc[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(voff_ref, roff_ref, x_ref, w_ref, lbl_ref, lse_ref,
                   g_ref, dw_ref, acc_sc, *, vocab, last_roff):
    v_off = voff_ref[0, 0, 0]
    r_off = roff_ref[0, 0, 0]

    @pl.when(r_off == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    x = x_ref[...]
    w = w_ref[...]
    d = _dlogits(x, w, v_off, vocab, lbl_ref[:, :1], lse_ref[:, :1],
                 g_ref[:, :1])
    # dw += d.T @ x: contract the row dim
    acc_sc[...] += _mm(d.astype(x.dtype), x, 0, 0)

    @pl.when(r_off == last_roff)
    def _done():
        dw_ref[...] = acc_sc[...].astype(dw_ref.dtype)


def _block_n(n: int) -> int:
    return min(_BLOCK_N, _round_up(n, 8))


def _pad_operands(x, w, labels, cv):
    n, h = x.shape
    v = w.shape[0]
    bn = _block_n(n)
    nr = _cdiv(n, bn)
    n_pad = nr * bn
    cv = min(_round_up(cv, 128), _round_up(v, 128))
    nv = _cdiv(v, cv)
    v_pad = nv * cv
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    wp = jnp.pad(w, ((0, v_pad - v), (0, 0)))
    # padded rows get label -1: it never matches a column, so their
    # picked logit is 0 and their (finite) nll is discarded by the
    # caller's slice; their g is 0-padded in backward.
    lblp = _row8(jnp.pad(labels.astype(jnp.int32), (0, n_pad - n),
                         constant_values=-1), n_pad)
    return xp, wp, lblp, bn, nr, n_pad, cv, nv


def _fwd_pallas(x, w, labels, cv):
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    n, h = x.shape
    v = w.shape[0]
    xp, wp, lblp, bn, nr, n_pad, cv, nv = _pad_operands(x, w, labels, cv)
    vmem = pltpu.VMEM
    bspec = lambda shape, imap: pl.BlockSpec(  # noqa: E731
        shape, imap, memory_space=vmem)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=v, last_voff=(nv - 1) * cv),
        name="lm_loss_fwd",
        grid=(nr, nv),
        in_specs=[
            bspec((1, 8, 128), lambda i, j: (j, 0, 0)),
            bspec((bn, h), lambda i, j: (i, 0)),
            bspec((cv, h), lambda i, j: (j, 0)),
            bspec((bn, 8), lambda i, j: (i, 0)),
        ],
        out_specs=[
            bspec((bn, 8), lambda i, j: (i, 0)),
            bspec((bn, 8), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 8), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 8), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 8), jnp.float32),
                        pltpu.VMEM((bn, 8), jnp.float32),
                        pltpu.VMEM((bn, 8), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=_interpret(),
    )(_off_inputs(nv, cv), xp, wp, lblp)
    return loss[:n, 0], lse[:n, 0]


def _bwd_pallas(x, w, labels, lse, g, cv):
    n, h = x.shape
    v = w.shape[0]
    xp, wp, lblp, bn, nr, n_pad, cv, nv = _pad_operands(x, w, labels, cv)
    lsep = _row8(lse, n_pad)
    gp = _row8(g, n_pad)
    vmem = pltpu.VMEM
    bspec = lambda shape, imap: pl.BlockSpec(  # noqa: E731
        shape, imap, memory_space=vmem)

    # dx: grid (row block, vocab chunk) — chunks sequential into scratch
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, vocab=v,
                          last_voff=(nv - 1) * cv),
        name="lm_loss_dx",
        grid=(nr, nv),
        in_specs=[
            bspec((1, 8, 128), lambda i, j: (j, 0, 0)),
            bspec((bn, h), lambda i, j: (i, 0)),
            bspec((cv, h), lambda i, j: (j, 0)),
            bspec((bn, 8), lambda i, j: (i, 0)),
            bspec((bn, 8), lambda i, j: (i, 0)),
            bspec((bn, 8), lambda i, j: (i, 0)),
        ],
        out_specs=bspec((bn, h), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, h), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, h), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=_interpret(),
    )(_off_inputs(nv, cv), xp, wp, lblp, lsep, gp)

    # dw: grid (vocab chunk, row block) — rows sequential into scratch
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, vocab=v,
                          last_roff=(nr - 1) * bn),
        name="lm_loss_dw",
        grid=(nv, nr),
        in_specs=[
            bspec((1, 8, 128), lambda a, b: (a, 0, 0)),
            bspec((1, 8, 128), lambda a, b: (b, 0, 0)),
            bspec((bn, h), lambda a, b: (b, 0)),
            bspec((cv, h), lambda a, b: (a, 0)),
            bspec((bn, 8), lambda a, b: (b, 0)),
            bspec((bn, 8), lambda a, b: (b, 0)),
            bspec((bn, 8), lambda a, b: (b, 0)),
        ],
        out_specs=bspec((cv, h), lambda a, b: (a, 0)),
        out_shape=jax.ShapeDtypeStruct((nv * cv, h), w.dtype),
        scratch_shapes=[pltpu.VMEM((cv, h), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=_interpret(),
    )(_off_inputs(nv, cv), _off_inputs(nr, bn), xp, wp, lblp, lsep, gp)
    return dx[:n], dw[:v]


# ---------------------------------------------------------------------------
# lax.scan fallback (identical math; runs anywhere; XLA only ever sees
# [N, cv] score tiles so the no-materialization profile is preserved)
# ---------------------------------------------------------------------------


def _chunked_w(w, cv):
    v, h = w.shape
    nv = _cdiv(v, cv)
    wp = jnp.pad(w, ((0, nv * cv - v), (0, 0)))
    return wp.reshape(nv, cv, h), nv


def _fwd_lax(x, w, labels, cv):
    n, _ = x.shape
    v = w.shape[0]
    wc, nv = _chunked_w(w, cv)
    lbl = labels.astype(jnp.int32)

    def step(carry, inp):
        m_i, l_i, p_i = carry
        off, wk = inp
        s = _mm(x, wk, 1, 1)  # (n, cv) f32
        col = off + jnp.arange(cv, dtype=jnp.int32)
        s = jnp.where(col[None, :] < v, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        l_new = l_i * jnp.exp(m_i - m_new) + \
            jnp.sum(jnp.exp(s - m_new[:, None]), axis=-1)
        hit = (col[None, :] < v) & (col[None, :] == lbl[:, None])
        p_new = p_i + jnp.sum(jnp.where(hit, s, 0.0), axis=-1)
        return (m_new, l_new, p_new), None

    offs = jnp.arange(nv, dtype=jnp.int32) * cv
    init = (jnp.full((n,), _NEG_INF, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, l, picked), _ = lax.scan(step, init, (offs, wc))
    lse = m + jnp.log(l)
    return lse - picked, lse


def _bwd_lax(x, w, labels, lse, g, cv):
    n, h = x.shape
    v = w.shape[0]
    wc, nv = _chunked_w(w, cv)
    lbl = labels.astype(jnp.int32)

    def step(dx_acc, inp):
        off, wk = inp
        s = _mm(x, wk, 1, 1)
        col = off + jnp.arange(cv, dtype=jnp.int32)
        valid = col[None, :] < v
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        hit = valid & (col[None, :] == lbl[:, None])
        d = ((p - hit.astype(jnp.float32)) * g[:, None]).astype(x.dtype)
        dx_acc = dx_acc + _mm(d, wk, 1, 0)
        dwk = _mm(d, x, 0, 0)
        return dx_acc, dwk

    offs = jnp.arange(nv, dtype=jnp.int32) * cv
    dx, dwc = lax.scan(step, jnp.zeros((n, h), jnp.float32), (offs, wc))
    dw = dwc.reshape(nv * cv, h)[:v]
    return dx.astype(x.dtype), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# custom_vjp + public op
# ---------------------------------------------------------------------------


def _fwd_lax_lowp(x, w, labels, cv, qdtype):
    """_fwd_lax with the per-chunk score matmuls quantized (the lowp
    route for the fused LM-head loss). Scales are dynamic per-tensor
    abs-max — this runs inside the _lce custom_vjp forward rule, a
    sub-trace where the train step's delayed-scaling region must not
    record. x quantizes once; each weight chunk quantizes in-scan.
    The backward recomputes scores at full precision against the lowp
    lse (standard lowp-fwd/high-precision-bwd recipe; the mismatch is
    covered by the bench.py --lowp rtol gate)."""
    from . import lowp as _lowp

    monitor_name = f"lowp.matmuls_{qdtype}"
    from ..framework import monitor as _monitor

    _monitor.stat_add(monitor_name)
    n, _ = x.shape
    v = w.shape[0]
    wc, nv = _chunked_w(w, cv)
    lbl = labels.astype(jnp.int32)
    sx = _lowp.amax_of(x)
    if qdtype == "int8":
        qx = _lowp._quant_int8(x, sx)
    else:
        qx = _lowp._quant_f8(x, sx).astype(jnp.float32)

    def scores(wk):
        sw = _lowp.amax_of(wk)
        if qdtype == "int8":
            qw = _lowp._quant_int8(wk, sw)
            acc = lax.dot_general(qx, qw, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
            return acc.astype(jnp.float32) * (sx * sw / (127.0 * 127.0))
        qw = _lowp._quant_f8(wk, sw).astype(jnp.float32)
        acc = lax.dot_general(qx, qw, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        return acc * (sx * sw / (448.0 * 448.0))

    def step(carry, inp):
        m_i, l_i, p_i = carry
        off, wk = inp
        s = scores(wk)  # (n, cv) f32
        col = off + jnp.arange(cv, dtype=jnp.int32)
        s = jnp.where(col[None, :] < v, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        l_new = l_i * jnp.exp(m_i - m_new) + \
            jnp.sum(jnp.exp(s - m_new[:, None]), axis=-1)
        hit = (col[None, :] < v) & (col[None, :] == lbl[:, None])
        p_new = p_i + jnp.sum(jnp.where(hit, s, 0.0), axis=-1)
        return (m_new, l_new, p_new), None

    offs = jnp.arange(nv, dtype=jnp.int32) * cv
    init = (jnp.full((n,), _NEG_INF, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, l, picked), _ = lax.scan(step, init, (offs, wc))
    lse = m + jnp.log(l)
    return lse - picked, lse


def _lowp_mode():
    from . import lowp as _lowp

    return _lowp.mode()


def _fwd_dispatch(x, w, labels, cv):
    m = _lowp_mode()
    if m != "off":
        # lowp forces the lax scan (the pallas LM-loss kernels stay
        # full-precision; the quantized scores use the same online-lse
        # math)
        return _fwd_lax_lowp(x, w, labels, cv, m)
    if _use_pallas_lm():
        return _fwd_pallas(x, w, labels, cv)
    return _fwd_lax(x, w, labels, cv)


def _bwd_dispatch(x, w, labels, lse, g, cv):
    if _use_pallas_lm():
        return _bwd_pallas(x, w, labels, lse, g, cv)
    return _bwd_lax(x, w, labels, lse, g, cv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _lce(x, w, labels, cv):
    """Per-row raw nll = lse - s[label], f32 (N,). ignore_index masking
    happens OUTSIDE (a jnp.where whose vjp zeroes g on ignored rows), so
    the kernel never needs to know about it."""
    nll, _ = _fwd_dispatch(x, w, labels, cv)
    return nll


def _lce_fwd_rule(x, w, labels, cv):
    nll, lse = _fwd_dispatch(x, w, labels, cv)
    return nll, (x, w, labels, lse)


def _lce_bwd_rule(cv, res, g):
    x, w, labels, lse = res
    dx, dw = _bwd_dispatch(x, w, labels, lse,
                           g.astype(jnp.float32), cv)
    return dx, dw, jnp.zeros_like(labels)


_lce.defvjp(_lce_fwd_rule, _lce_bwd_rule)


@register_op("fused_linear_cross_entropy")
def fused_linear_cross_entropy(x, weight, label, *, ignore_index=-100,
                               reduction="mean", chunk_v=0):
    """cross_entropy(x @ weight.T, label) without the `[N, V]` logits.

    x: (..., H) hidden states, weight: (V, H) tied decoder table,
    label: (...,) int.  Output is f32 (the reference cross_entropy
    upcasts before log_softmax); `mean` divides by the non-ignored row
    count clamped to 1, matching nn_ops.cross_entropy.
    """
    lead = x.shape[:-1]
    h = x.shape[-1]
    v = weight.shape[0]
    x2 = x.reshape(-1, h)
    lbl = jnp.asarray(label).reshape(-1)
    w = weight
    if w.dtype != x2.dtype:
        # AMP may cast only the float inputs it recognises; align on the
        # activation dtype (astype is differentiable — its vjp casts dw
        # back to the parameter dtype)
        w = w.astype(x2.dtype)
    cv = int(chunk_v) if chunk_v else min(_CHUNK_V, _round_up(v, 128))
    nll = _lce(x2, w, lbl, cv)
    valid = lbl.astype(jnp.int32) != ignore_index
    loss = jnp.where(valid, nll, 0.0)
    if reduction == "none":
        return loss.reshape(lead)
    if reduction == "sum":
        return jnp.sum(loss)
    return jnp.sum(loss) / jnp.maximum(
        jnp.sum(valid.astype(jnp.float32)), 1.0)


# ---------------------------------------------------------------------------
# deferred LM head: the routing handle ErniePretrainingHeads returns in
# place of materialized logits when the fused path is active
# ---------------------------------------------------------------------------


class DeferredLMHead:
    """(hidden, tied weight) pair standing in for `hidden @ weight.T`.

    ErniePretrainingHeads returns this instead of `[B, S, V]` logits when
    the plainness predicate holds; ErniePretrainingCriterion consumes it
    via F.fused_linear_cross_entropy.  Registered as a pytree node so the
    engine's output-tree wrapping (`jax.tree.map(Tensor, out)`) descends
    into the two arrays instead of boxing the handle itself.  Callers
    that need real logits (inference, external heads) call
    `materialize()` — the unfused tied matmul."""

    def __init__(self, hidden, weight):
        self.hidden = hidden
        self.weight = weight

    def materialize(self):
        from ..core.dispatch import apply

        return apply("matmul_v2", self.hidden, self.weight, trans_y=True)


jax.tree_util.register_pytree_node(
    DeferredLMHead,
    lambda d: ((d.hidden, d.weight), None),
    lambda _, c: DeferredLMHead(*c))
