"""Pallas TPU convolution kernels with fused BN/ReLU(+residual) epilogues.

Ref parity: paddle/fluid/framework/ir/conv_bn_fuse_pass.cc +
conv_elementwise_add_act_fuse_pass.cc + operators/conv_cudnn_op.cu — the
reference folds BN into the conv and picks a cudnn fused algo; here the
same fusion is a Mosaic kernel whose epilogue applies the per-channel
affine + activation (+ residual add) on the f32 accumulator before it
ever leaves VMEM, and (in training) emits the per-channel sum/sum-sq
moments from the same accumulator so the BN statistics pass never
re-reads the conv output from HBM.

Kernel shape: ONE stride-1 VALID NHWC kernel covers every ResNet conv.
  * stride 2 lowers to stride 1 by space-to-depth parity decomposition:
    z[ho] = sum_{a,q} x_plane[a][ho+q] * w[2q+a], i.e. the same weight
    folding as vision.models.resnet.fold_conv7_stem, applied at trace
    time.  This is also what kills the C<=64 stem MXU underfill: the
    vanilla 7x7/s2 stem lowers to a 4x4/s1 conv over 12 channels.
  * 1x1 convs flatten (H, W) into a single (Ho*Wo, C) x (C, O) matmul
    (reusing the flash kernels' f32-accumulate dot_general idiom).
  * 3x3 convs unroll their taps as shifted row-matmuls from the padded
    image held in VMEM (im2col-in-VMEM without materialising patches).

The custom VJP rewrites the input-dilated strided-conv gradient as
parity-decomposed stride-1 transposed convs routed through the SAME
kernel (the second named conv loss from BENCH r5); dw transposes the
lax reference conv (jax.linear_transpose — exact, no extra forward).

Gating mirrors fused_ops: FLAGS_use_pallas_conv + on-TPU backend, with
PADDLE_TPU_CONV_FORCE=pallas|lax overriding (pallas off-TPU runs the
kernels in interpreter mode so CPU tier-1 certifies the exact kernel
math + backward).  On a TPU a conv the plan accepts either lowers or
the step raises with Mosaic's message — there is no fallback.
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
from .nn_ops import _bn_act_core, _conv_padding, _pair

# Per-block VMEM budget for the whole padded input plane + weight tile +
# output tile (v5e has 16 MB higher is risk of spills).  Every ResNet-50
# conv at batch-slice granularity fits: worst case layer1 dz plane
# 58*58*256*4B ~ 3.4 MB.
_VMEM_BUDGET = 10 * 2**20
_MAX_TAPS = 4  # per spatial dim, post stride-lowering (k<=8 at s=2)

# incremented whenever a pallas conv is traced (not the lax path) — the
# tpu-tier spy test asserts the compiled ResNet step goes through the
# kernel
_TRACE_COUNT = 0


def _use_pallas_conv() -> bool:
    force = os.environ.get("PADDLE_TPU_CONV_FORCE", "")
    if force == "pallas":
        return True
    if force == "lax":
        return False
    from ..framework.flags import flag

    return flag("FLAGS_use_pallas_conv") and jax.default_backend() == "tpu"


def _interpret() -> bool:
    return (os.environ.get("PADDLE_TPU_CONV_FORCE", "") == "pallas"
            and jax.default_backend() != "tpu")


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def _mm(a, b, ca: int, cb: int):
    """f32-accumulating matmul (see fused_ops._mm: dot_general reads
    either orientation natively on the MXU; .T would relayout)."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# plan: eligibility + static geometry of the stride-1 lowering
# ---------------------------------------------------------------------------


class _Plan:
    __slots__ = ("s", "pads", "ho", "wo", "ot", "kkh", "kkw", "flat")

    def __init__(self, s, pads, ho, wo, ot, kkh, kkw):
        self.s, self.pads = s, pads
        self.ho, self.wo, self.ot = ho, wo, ot
        self.kkh, self.kkw = kkh, kkw
        # 1x1 (post-lowering) convs run as one flattened (Ho*Wo, C) x
        # (C, Ot) matmul — per-row dots would underfill the MXU's M dim
        self.flat = kkh == 1 and kkw == 1


def _plan(xs, ws, strides, pads, itemsize):
    """Static plan for the NHWC stride-1 kernel, or None when the conv
    cannot take the pallas path (caller keeps lax).  Assumes the caller
    already verified NCHW / groups=1 / dilation=1."""
    if strides[0] != strides[1] or strides[0] not in (1, 2):
        return None
    s = strides[0]
    n, c, h, w = xs
    o, ci, kh, kw = ws
    if ci != c or n < 1:
        return None
    kkh, kkw = -(-kh // s), -(-kw // s)
    if kkh > _MAX_TAPS or kkw > _MAX_TAPS:
        return None
    ot = o if o <= 128 else 128
    if o % ot:
        return None
    ho = (h + pads[0][0] + pads[0][1] - kh) // s + 1
    wo = (w + pads[1][0] + pads[1][1] - kw) // s + 1
    if ho <= 0 or wo <= 0:
        return None
    ce = c * min(s, kh) * min(s, kw)
    xbytes = (ho + kkh - 1) * (wo + kkw - 1) * ce * itemsize
    wbytes = kkh * kkw * ce * ot * itemsize
    obytes = ho * wo * ot * 4
    if xbytes + wbytes + 2 * obytes > _VMEM_BUDGET:
        return None
    return _Plan(s, (tuple(pads[0]), tuple(pads[1])), ho, wo, ot, kkh, kkw)


def _lower(x, w, plan):
    """Trace-time lowering to an equivalent stride-1 VALID conv: returns
    (xp [N,Hp,Wp,Ce] pre-padded NHWC, wk [Kkh*Kkw, Ce, O]).

    stride 2: parity planes xp_a[i] = xpad[2i+a] become channels and the
    weight regroups as w'[o,(a,b,c),q,r] = w[o,c,2q+a,2r+b] (zero where
    2q+a >= k) — identical folding to fold_conv7_stem, done on-device."""
    n, c, h, wd = x.shape
    o, _, kh, kw = w.shape
    (plh, phh), (plw, phw) = plan.pads
    zero = jnp.zeros((), x.dtype)
    if plan.s == 1:
        xp = lax.pad(x, zero, ((0, 0, 0), (0, 0, 0), (plh, phh, 0),
                               (plw, phw, 0)))
        wk = w
    else:
        # one extra zero row/col parity-pads odd extents so both planes
        # have equal length (the zeros land on taps past the support)
        eh = (h + plh + phh) % 2
        ew = (wd + plw + phw) % 2
        xpad = lax.pad(x, zero, ((0, 0, 0), (0, 0, 0), (plh, phh + eh, 0),
                                 (plw, phw + ew, 0)))
        al = range(min(2, kh))
        bl = range(min(2, kw))
        xp = jnp.concatenate([xpad[:, :, a::2, b::2]
                              for a in al for b in bl], axis=1)
        wpad = lax.pad(w, jnp.zeros((), w.dtype),
                       ((0, 0, 0), (0, 0, 0), (0, 2 * plan.kkh - kh, 0),
                        (0, 2 * plan.kkw - kw, 0)))
        wk = jnp.concatenate([wpad[:, :, a::2, b::2]
                              for a in al for b in bl], axis=1)
    # trim to exactly the rows/cols the VALID conv reads (even-k lowering
    # can leave one unused trailing plane row)
    hp, wp = plan.ho + plan.kkh - 1, plan.wo + plan.kkw - 1
    assert xp.shape[2] >= hp and xp.shape[3] >= wp, (xp.shape, hp, wp)
    xp = xp[:, :, :hp, :wp].transpose(0, 2, 3, 1)
    ce = wk.shape[1]
    wk = wk.transpose(2, 3, 1, 0).reshape(plan.kkh * plan.kkw, ce, o)
    return xp, wk


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _conv_kernel(x_ref, w_ref, *refs, kk, wo, act, fuse, has_res, moments):
    """grid (N, O/Ot); block = one image's padded plane x one O tile.
    fori over output rows, taps statically unrolled (kk <= 4 per dim
    post-lowering); per-row (Wo, Ce) x (Ce, Ot) dot with f32 accumulate.
    Epilogues on the accumulator: per-channel affine+act(+residual)
    (eval-fused form) or sum/sum-sq moments (training BN stats)."""
    kkh, kkw = kk
    i0 = 0
    if fuse:
        g_ref, b_ref = refs[0], refs[1]
        i0 = 2
    if has_res:
        r_ref = refs[i0]
        i0 += 1
    o_ref = refs[i0]
    if moments:
        s1_ref, s2_ref = refs[i0 + 1], refs[i0 + 2]
    ho = o_ref.shape[1]
    ot = o_ref.shape[-1]

    def row(i, carry):
        m1, m2 = carry
        acc = jnp.zeros((wo, ot), jnp.float32)
        for dh in range(kkh):
            for dw in range(kkw):
                acc += _mm(x_ref[0, i + dh, pl.ds(dw, wo), :],
                           w_ref[dh * kkw + dw], 1, 0)
        if moments:
            m1 = m1 + jnp.sum(acc, axis=0, keepdims=True)
            m2 = m2 + jnp.sum(acc * acc, axis=0, keepdims=True)
        z = acc
        if fuse:
            z = z * g_ref[...] + b_ref[...]
        if has_res:
            z = z + r_ref[0, i].astype(jnp.float32)
        if act == "relu":
            z = jnp.maximum(z, 0.0)
        o_ref[0, i] = z.astype(o_ref.dtype)
        return m1, m2

    z0 = jnp.zeros((1, ot), jnp.float32)
    m1, m2 = lax.fori_loop(0, ho, row, (z0, z0))
    if moments:
        # 8-sublane broadcast (not 128): HBM stores only 8 lanes' worth
        # per channel tile — same trick as the flash lse output
        s1_ref[...] = jnp.broadcast_to(m1, (8, ot))[None]
        s2_ref[...] = jnp.broadcast_to(m2, (8, ot))[None]


def _pallas_conv(xp, wk, plan, *, g=None, b=None, res=None,
                 act="identity", moments=False, out_dtype=None):
    """pallas_call wrapper (NHWC). Returns [y] / [y, msum, msq] with
    moments as (N, 8, O) f32 partials (summed over N by the caller)."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    n, hp, wp, ce = xp.shape
    kk, _, o = wk.shape
    ot = plan.ot
    if plan.flat:
        hk, wo_k = 1, plan.ho * plan.wo
        xp = xp.reshape(n, 1, wo_k, ce)
        if res is not None:
            res = res.reshape(n, 1, wo_k, o)
    else:
        hk, wo_k = plan.ho, plan.wo
    hp, wp = xp.shape[1], xp.shape[2]
    out_dtype = out_dtype or xp.dtype

    def bspec(shape, imap):
        return pl.BlockSpec(shape, imap, memory_space=pltpu.VMEM)

    in_specs = [bspec((1, hp, wp, ce), lambda i, j: (i, 0, 0, 0)),
                bspec((kk, ce, ot), lambda i, j: (0, 0, j))]
    ops = [xp, wk]
    if g is not None:
        in_specs += [bspec((1, ot), lambda i, j: (0, j)),
                     bspec((1, ot), lambda i, j: (0, j))]
        ops += [g.reshape(1, o).astype(jnp.float32),
                b.reshape(1, o).astype(jnp.float32)]
    if res is not None:
        in_specs.append(bspec((1, hk, wo_k, ot), lambda i, j: (i, 0, 0, j)))
        ops.append(res)
    out_specs = [bspec((1, hk, wo_k, ot), lambda i, j: (i, 0, 0, j))]
    out_shape = [jax.ShapeDtypeStruct((n, hk, wo_k, o), out_dtype)]
    if moments:
        out_specs += [bspec((1, 8, ot), lambda i, j: (i, 0, j))] * 2
        out_shape += [jax.ShapeDtypeStruct((n, 8, o), jnp.float32)] * 2
    outs = pl.pallas_call(
        functools.partial(_conv_kernel, kk=(plan.kkh, plan.kkw), wo=wo_k,
                          act=act, fuse=g is not None,
                          has_res=res is not None, moments=moments),
        name="fused_conv",
        grid=(n, o // ot), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=_interpret())(*ops)
    y = outs[0].reshape(n, plan.ho, plan.wo, o)
    return [y] + list(outs[1:])


# ---------------------------------------------------------------------------
# pallas-or-lax forward dispatch
# ---------------------------------------------------------------------------


def _conv_ref(x, w, strides, pads):
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(x, w, window_strides=tuple(strides),
                                    padding=tuple(pads),
                                    dimension_numbers=dn)


def _fwd(x, w, s, pads, *, g=None, b=None, res=None, act="identity",
         moments=False):
    """Fused conv forward, NCHW in/out.  Identical epilogue formulation
    on both paths (f32 affine/act on the conv accumulator, cast once at
    the end) so pallas vs lax parity is a pure tiling question."""
    assert not (moments and (g is not None or res is not None))
    plan = _plan(x.shape, w.shape, (s, s), pads, x.dtype.itemsize)
    if plan is not None and _use_pallas_conv():
        xp, wk = _lower(x, w, plan)
        rs = res.transpose(0, 2, 3, 1) if res is not None else None
        outs = _pallas_conv(xp, wk, plan, g=g, b=b, res=rs, act=act,
                            moments=moments, out_dtype=x.dtype)
        y = outs[0].transpose(0, 3, 1, 2)
        if moments:
            return y, outs[1][:, 0, :].sum(0), outs[2][:, 0, :].sum(0)
        return y
    z = _conv_ref(x, w, (s, s), pads)
    if moments:
        z32 = z.astype(jnp.float32)
        return (z, jnp.sum(z32, axis=(0, 2, 3)),
                jnp.sum(z32 * z32, axis=(0, 2, 3)))
    if g is None and res is None and act == "identity":
        return z
    z32 = z.astype(jnp.float32)
    if g is not None:
        z32 = z32 * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)
    if res is not None:
        z32 = z32 + res.astype(jnp.float32)
    if act == "relu":
        z32 = jnp.maximum(z32, 0.0)
    return z32.astype(x.dtype)


# ---------------------------------------------------------------------------
# custom VJP: transposed-conv dx by parity decomposition
# ---------------------------------------------------------------------------


def _taps_1d(k, s, a, pad_lo):
    """1-D taps of the transposed conv feeding dx rows u = a (mod s):
    kernel positions kh with kh = (a+pad_lo) mod s, whose shifts
    m = (kh-a-pad_lo)/s are consecutive integers — ordered by descending
    m the sum dxp[i] = sum dz[i-m]·w[kh] is a plain stride-1 correlation
    with low padding m_max.  Returns (taps, m_max) or None (no taps ->
    that parity plane receives no gradient)."""
    ks = [kh for kh in range(k) if (kh - a - pad_lo) % s == 0]
    if not ks:
        return None
    return list(reversed(ks)), (ks[-1] - a - pad_lo) // s


def _input_grad(dz, w, cfg, x_shape):
    """dx as stride-1 transposed convs routed back through _fwd (so the
    backward conv runs on the SAME pallas kernel).  This is the rewrite
    of the input-dilated strided gradient: instead of dilating dz with
    s-1 zeros (3/4 wasted MXU work at s=2), each input-parity plane gets
    its own dense small-kernel conv and the planes interleave back."""
    s, plh, phh, plw, phw = cfg
    n, c, h, wd = x_shape
    kh, kw = w.shape[2], w.shape[3]
    ho, wo = dz.shape[2], dz.shape[3]

    def plane(a, b, ha, wa):
        th, tw = _taps_1d(kh, s, a, plh), _taps_1d(kw, s, b, plw)
        if th is None or tw is None:
            return None
        rows, mh = th
        cols, mw = tw
        wab = w[:, :, rows][:, :, :, cols].transpose(1, 0, 2, 3)
        pads = ((mh, ha - ho - mh + len(rows) - 1),
                (mw, wa - wo - mw + len(cols) - 1))
        return _fwd(dz, wab, 1, pads)

    if s == 1:
        out = plane(0, 0, h, wd)
        return out if out is not None else jnp.zeros(x_shape, dz.dtype)
    dx = jnp.zeros(x_shape, dz.dtype)
    for a in range(s):
        ha = (h - a + s - 1) // s
        for b in range(s):
            wa = (wd - b + s - 1) // s
            if ha <= 0 or wa <= 0:
                continue
            p = plane(a, b, ha, wa)
            if p is not None:
                dx = dx.at[:, :, a::s, b::s].set(p)
    return dx


def _conv_grads(x, w, dz, cfg):
    s = cfg[0]
    pads = ((cfg[1], cfg[2]), (cfg[3], cfg[4]))
    dz = dz.astype(x.dtype)
    dx = _input_grad(dz, w.astype(x.dtype), cfg, x.shape)
    # dw: transpose the (linear-in-w) reference conv — exact, and unlike
    # jax.vjp it does not execute a throwaway forward
    dw, = jax.linear_transpose(
        lambda ww: _conv_ref(x, ww, (s, s), pads),
        jax.ShapeDtypeStruct(w.shape, x.dtype))(dz)
    return dx.astype(x.dtype), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _conv_core(cfg, moments, x, w):
    """Plain conv (optionally + moments) with the transposed-conv
    backward.  cfg = (s, plh, phh, plw, phw) — static and hashable."""
    return _fwd(x, w, cfg[0], ((cfg[1], cfg[2]), (cfg[3], cfg[4])),
                moments=moments)


def _conv_core_fwd(cfg, moments, x, w):
    return _conv_core(cfg, moments, x, w), (x, w)


def _conv_core_bwd(cfg, moments, saved, ct):
    x, w = saved
    # moment cotangents are structurally zero: every caller stops
    # gradients on the stats (the epilogue VJP owns the stats' dx term)
    dz = ct[0] if moments else ct
    return _conv_grads(x, w, dz, cfg)


_conv_core.defvjp(_conv_core_fwd, _conv_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _conv_affine(cfg, act, x, w, g, b, res):
    """Eval-fused y = act(conv(x,w)·g + b [+ res]) — the fully-folded BN
    epilogue (g = scale·rsqrt(var+eps), b = bias − mean·g).  res with
    ndim != 4 is the no-residual placeholder."""
    return _fwd(x, w, cfg[0], ((cfg[1], cfg[2]), (cfg[3], cfg[4])),
                g=g, b=b, res=res if res.ndim == 4 else None, act=act)


def _conv_affine_fwd(cfg, act, x, w, g, b, res):
    return _conv_affine(cfg, act, x, w, g, b, res), (x, w, g, b, res)


def _conv_affine_bwd(cfg, act, saved, dy):
    x, w, g, b, res = saved
    has_res = res.ndim == 4
    # flash-style recompute: one extra conv instead of saving z — the
    # fused path's backward never re-reads a stored pre-activation
    z32 = _fwd(x, w, cfg[0],
               ((cfg[1], cfg[2]), (cfg[3], cfg[4]))).astype(jnp.float32)
    u = z32 * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)
    if has_res:
        u = u + res.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    du = jnp.where(u > 0.0, dy32, 0.0) if act == "relu" else dy32
    dg = jnp.sum(du * z32, axis=(0, 2, 3))
    db = jnp.sum(du, axis=(0, 2, 3))
    dx, dw = _conv_grads(x, w, du * g.reshape(1, -1, 1, 1), cfg)
    dres = du.astype(res.dtype) if has_res else jnp.zeros_like(res)
    return dx, dw, dg.astype(g.dtype), db.astype(b.dtype), dres


_conv_affine.defvjp(_conv_affine_fwd, _conv_affine_bwd)


# ---------------------------------------------------------------------------
# op surface
# ---------------------------------------------------------------------------


def _explicit_pads(pad, xs, ks, strides):
    if isinstance(pad, str):
        if pad == "VALID":
            return ((0, 0), (0, 0))
        out = []
        for size, k, s in ((xs[2], ks[0], strides[0]),
                           (xs[3], ks[1], strides[1])):
            total = max(0, (-(-size // s) - 1) * s + k - size)
            out.append((total // 2, total - total // 2))
        return tuple(out)
    return (tuple(pad[0]), tuple(pad[1]))


def _supported(x, w, strides, dilations, groups, data_format):
    return (data_format == "NCHW" and groups == 1
            and dilations == (1, 1) and strides[0] == strides[1]
            and strides[0] in (1, 2) and x.ndim == 4
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.dtype == w.dtype)


def conv2d_maybe_pallas(x, w, strides, pad, dilations, groups,
                        data_format):
    """Hook for nn_ops.conv2d: route a plain conv through the pallas
    kernel + custom VJP when the gates and plan allow; None keeps the
    caller on lax.conv_general_dilated (XLA AD)."""
    if not _use_pallas_conv():
        return None
    if not _supported(x, w, strides, dilations, groups, data_format):
        return None
    pads = _explicit_pads(pad, x.shape, (w.shape[2], w.shape[3]), strides)
    if _plan(x.shape, w.shape, strides, pads, x.dtype.itemsize) is None:
        return None
    cfg = (strides[0], pads[0][0], pads[0][1], pads[1][0], pads[1][1])
    return _conv_core(cfg, False, x, w)


def _amp_cast(op_name, *arrs):
    """The composed pair autocasts conv2d's x/w to the low dtype (AMP
    white list) while the BN params stay f32 (batch_norm is black
    listed); this op sits in neither list so it replicates that split
    itself: x/w/residual cast, scale/bias/mean/variance untouched."""
    from ..core import config

    level, amp_dtype, white, black = config.amp_state()
    if not level or (black and op_name in black):
        return arrs
    low = jnp.bfloat16 if amp_dtype == "bfloat16" else jnp.float16
    return tuple(a.astype(low) if a is not None
                 and jnp.issubdtype(a.dtype, jnp.floating) else a
                 for a in arrs)


@register_op("fused_conv2d_bn_act", has_aux=True)
def fused_conv2d_bn_act(x, weight, scale, bias, mean, variance,
                        residual=None, *, stride=1, padding=0, dilation=1,
                        groups=1, momentum=0.9, epsilon=1e-5, act="relu",
                        is_test=False, data_format="NCHW",
                        use_global_stats=False):
    """y = act(batch_norm(conv2d(x, weight)) [+ residual]); aux =
    updated running stats.

    Eval / global-stats: the BN folds to one per-channel affine applied
    in the conv epilogue (one kernel, no second HBM pass).  Training:
    the kernel emits (z, sum, sum_sq) in one pass — the stats reduction
    never re-reads z — then the existing _bn_act_core VJP normalizes and
    owns the full training dx (incl. the stats' dependence on z).
    Unsupported layouts compose conv2d + fused_bn_act unchanged."""
    strides = _pair(stride)
    dilations = _pair(dilation)
    x, weight, residual = _amp_cast("fused_conv2d_bn_act", x, weight,
                                    residual)
    if _supported(x, weight.astype(x.dtype), strides, dilations, groups,
                  data_format):
        weight = weight.astype(x.dtype)
        kh, kw = weight.shape[2], weight.shape[3]
        pad = _conv_padding(padding, 2, strides, dilations, (kh, kw))
        pads = _explicit_pads(pad, x.shape, (kh, kw), strides)
        cfg = (strides[0], pads[0][0], pads[0][1], pads[1][0], pads[1][1])
        if is_test or use_global_stats:
            inv = lax.rsqrt(variance.astype(jnp.float32) + epsilon)
            g = scale.astype(jnp.float32) * inv
            bb = bias.astype(jnp.float32) - mean.astype(jnp.float32) * g
            dummy = residual if residual is not None \
                else jnp.zeros((0,), x.dtype)
            y = _conv_affine(cfg, act, x, weight, g, bb, dummy)
            return y, (mean, variance)
        z, msum, msq = _conv_core(cfg, True, x, weight)
        cnt = z.shape[0] * z.shape[2] * z.shape[3]
        use_mean = lax.stop_gradient(msum / cnt)
        use_var = lax.stop_gradient(
            jnp.maximum(msq / cnt - use_mean * use_mean, 0.0))
        inv = lax.rsqrt(use_var + epsilon)
        y = _bn_act_core(act, 1, z, scale, bias, use_mean, inv, residual)
        new_mean = momentum * mean + (1 - momentum) * use_mean
        new_var = momentum * variance + (1 - momentum) * use_var
        return y, (lax.stop_gradient(new_mean),
                   lax.stop_gradient(new_var))
    from . import nn_ops

    z = nn_ops.conv2d(x, weight, stride=stride, padding=padding,
                      dilation=dilation, groups=groups,
                      data_format=data_format)
    return nn_ops.fused_bn_act(z, scale, bias, mean, variance, residual,
                               momentum=momentum, epsilon=epsilon, act=act,
                               is_test=is_test, data_format=data_format,
                               use_global_stats=use_global_stats)
