"""Latency-hiding collective-matmul: ring-decomposed mp collectives.

The GSPMD path emits the tensor-parallel collectives as monolithic
all-gather / all-reduce ops around the sharded matmuls; on a ring
interconnect the collective time is exposed whenever the compiler's
async overlap pass can't split it. This module decomposes each
mp-sharded matmul + collective pair into `mp` ring steps — one
`lax.ppermute` hop interleaved with one per-shard partial matmul — so
every hop's transfer hides behind the next partial product (the
fluid-era "parallelism by program rewriting" lesson, SURVEY.md; same
ring schedule as the pallas guide's ring collectives, expressed at the
`lax` level so it runs on CPU meshes and composes with autodiff).

Three primitives cover the Megatron block:

- ``matmul_allreduce``      row-parallel, dense activations
                            (x·W followed by all-reduce over mp)
- ``allgather_matmul``      column-parallel, sequence-parallel input
                            (all-gather of the seq axis before x·W)
- ``matmul_reducescatter``  row-parallel, sequence-parallel output
                            (x·W followed by reduce-scatter of seq)

All three run SPMD-manual inside `jax.shard_map` and are exact up to
partial-sum reassociation: the ring accumulates the mp partial products
in ring order rather than the single fused reduction's order, so parity vs the
GSPMD path is bitwise for the gather phase and ~1 ulp for the reduce
phases (tests use rtol 1e-6 on fp32).

Routing: engines enter `region(mesh, sequence_parallel=...)` around the
model call when `FLAGS_mp_overlap` is on (PADDLE_TPU_MP_OVERLAP_FORCE
overrides) and the mesh qualifies (`supported`); Column/RowParallelLinear
consult `current()` and fall back to the GSPMD collectives whenever a
guard fails — shapes that don't divide the ring, tape-based autograd,
eager execution, or an enclosing manual region.
"""

from __future__ import annotations

import contextlib
import os
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor

# canonical mesh axis names (== distributed.topology.DP_AXIS/MP_AXIS;
# spelled out so `paddle_tpu.ops` stays importable before the
# distributed package finishes loading during package init)
DP_AXIS = "dp"
MP_AXIS = "mp"

__all__ = [
    "enabled", "supported", "region", "current",
    "matmul_allreduce", "allgather_matmul", "matmul_reducescatter",
    "maybe_column_parallel", "maybe_row_parallel",
    "model_sequence_parallel",
]


def model_sequence_parallel(layer):
    """True when any sublayer runs megatron sequence parallelism (the
    decoder blocks carry a `sequence_parallel` attr)."""
    try:
        subs = layer.sublayers(include_self=True)
    except (AttributeError, TypeError):
        subs = [layer]
    return any(bool(getattr(l, "sequence_parallel", False))
               for l in subs)


def _force():
    """PADDLE_TPU_MP_OVERLAP_FORCE=on|off wins over the flag; else None."""
    v = os.environ.get("PADDLE_TPU_MP_OVERLAP_FORCE", "").strip().lower()
    if v in ("1", "on", "true", "yes"):
        return True
    if v in ("0", "off", "false", "no"):
        return False
    return None


def enabled():
    forced = _force()
    if forced is not None:
        return forced
    from ..framework.flags import flag
    return bool(flag("FLAGS_mp_overlap"))


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def supported(mesh):
    """Ring decomposition applies on pure dp x mp meshes with mp > 1.

    Any other nontrivial axis (pp, sharding, sep) means the step is
    already inside — or about to enter — another manual region, and
    the ring shard_map does not nest there, so the GSPMD path stays.
    """
    if mesh is None:
        return False
    sizes = _axis_sizes(mesh)
    if sizes.get(MP_AXIS, 1) <= 1:
        return False
    return all(size == 1 for name, size in sizes.items()
               if name not in (DP_AXIS, MP_AXIS))


# -- trace region ------------------------------------------------------------

_tls = threading.local()


class _Region:
    __slots__ = ("mesh", "sequence_parallel")

    def __init__(self, mesh, sequence_parallel):
        self.mesh = mesh
        self.sequence_parallel = bool(sequence_parallel)


@contextlib.contextmanager
def region(mesh, sequence_parallel=False):
    """Mark a trace region whose mp matmuls may use the ring kernels.

    No-op (plain GSPMD trace) unless overlap is enabled AND the mesh
    qualifies; entering costs nothing per step — it only runs at trace
    time inside jit.
    """
    if not (enabled() and supported(mesh)):
        yield
        return
    prev = getattr(_tls, "region", None)
    _tls.region = _Region(mesh, sequence_parallel)
    try:
        yield
    finally:
        _tls.region = prev


def current():
    """The active overlap region, or None."""
    return getattr(_tls, "region", None)


def _inside_manual_region():
    """True when tracing already runs under a shard_map's manual axes —
    the ring shard_map must not nest there."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


# -- ring primitives ---------------------------------------------------------
#
# Shapes below are GLOBAL; n = mp degree. All primitives return None when
# a divisibility guard fails so the caller keeps the GSPMD path.


def _ring(n):
    # forward ring: device i sends to i+1 (mod n)
    return [(i, (i + 1) % n) for i in range(n)]


def _dp_part(mesh, x):
    """Shard the leading batch axis over dp when it divides; else
    replicate over dp (exact, just redundant)."""
    dp = _axis_sizes(mesh).get(DP_AXIS, 1)
    if dp > 1 and x.ndim >= 3 and x.shape[0] % dp == 0:
        return DP_AXIS
    return None


def _spec(ndim, dp, seq=None, last=None):
    """PartitionSpec of exactly `ndim` entries: optional dp on dim 0,
    `seq` on dim -2, `last` on dim -1."""
    parts = [None] * ndim
    if dp is not None and ndim >= 3:
        parts[0] = dp
    if seq is not None:
        parts[-2] = seq
    if last is not None:
        parts[-1] = last
    return P(*parts)


def _smap(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names={DP_AXIS, MP_AXIS}, check_vma=False)


def _pmm(a, b):
    """One ring-hop partial matmul. Under FLAGS_lowp_matmul the
    per-shard partials quantize through the scaled-matmul family
    (dynamic per-hop abs-max scales — this runs inside a shard_map
    body, where the train step's delayed-scaling region must not leak)
    and accumulate across hops at the operands' precision."""
    from . import lowp as _lowp

    m = _lowp.mode()
    if m == "off":
        return a @ b
    return _lowp.scaled_matmul(a, b, qdtype=m,
                               out_dtype=jnp.result_type(a, b))


def matmul_allreduce(x, w, mesh):
    """Row-parallel matmul with the all-reduce decomposed into a
    reduce-scatter ring + all-gather ring, both hidden behind per-chunk
    partial matmuls.

    x [..., s, h] (last dim mp-sharded), w [h, M] (dim 0 mp-sharded)
    -> [..., s, M] replicated over mp. Requires h % n == 0, M % n == 0.
    """
    n = _axis_sizes(mesh)[MP_AXIS]
    if x.ndim < 2 or x.shape[-1] != w.shape[0]:
        return None
    if x.shape[-1] % n or w.shape[1] % n:
        return None
    dp = _dp_part(mesh, x)
    fwd = _ring(n)

    def local(xl, wl):
        # xl [..., s, h/n], wl [h/n, M]
        idx = lax.axis_index(MP_AXIS)
        csz = wl.shape[1] // n

        def wchunk(c):
            return lax.dynamic_slice_in_dim(wl, c * csz, csz, axis=1)

        # reduce-scatter phase: after n-1 hops device idx holds output
        # chunk idx fully summed over all mp shards of the contraction
        acc = _pmm(xl, wchunk((idx - 1) % n))
        for t in range(1, n):
            acc = lax.ppermute(acc, MP_AXIS, fwd) \
                + _pmm(xl, wchunk((idx - t - 1) % n))
        # all-gather phase: circulate the finished chunks
        parts = [acc]
        cur = acc
        for _ in range(n - 1):
            cur = lax.ppermute(cur, MP_AXIS, fwd)
            parts.append(cur)
        stacked = jnp.stack(parts)           # [n, ..., s, csz]
        # parts[k] on device idx is chunk (idx - k) mod n; reorder to 0..n-1
        order = (idx - jnp.arange(n)) % n
        y = jnp.take(stacked, jnp.argsort(order), axis=0)
        y = jnp.moveaxis(y, 0, -2)           # [..., s, n, csz]
        return y.reshape(y.shape[:-2] + (n * csz,))

    out = _smap(mesh, local,
                (_spec(x.ndim, dp, last=MP_AXIS), P(MP_AXIS, None)),
                _spec(x.ndim, dp))
    return out(x, w)


def allgather_matmul(x, w, mesh):
    """Column-parallel matmul over a sequence-parallel input with the
    seq all-gather decomposed into ring hops hidden behind per-chunk
    matmuls.

    x [..., s, h] (dim -2 mp-sharded), w [h, M] (dim 1 mp-sharded)
    -> [..., s, M] with last dim mp-sharded. Requires s % n == 0,
    M % n == 0.
    """
    n = _axis_sizes(mesh)[MP_AXIS]
    if x.ndim < 2 or x.shape[-1] != w.shape[0]:
        return None
    if x.shape[-2] % n or w.shape[1] % n:
        return None
    dp = _dp_part(mesh, x)
    fwd = _ring(n)

    def local(xl, wl):
        # xl [..., s/n, h], wl [h, M/n]
        idx = lax.axis_index(MP_AXIS)
        sl = xl.shape[-2]
        cur = xl
        y = None
        for t in range(n):
            part = _pmm(cur, wl)             # [..., s/n, M/n]
            if y is None:
                y = jnp.zeros(part.shape[:-2] + (n * sl, part.shape[-1]),
                              part.dtype)
            c = (idx - t) % n                # which seq chunk `cur` is
            y = lax.dynamic_update_slice_in_dim(y, part, c * sl, axis=-2)
            if t < n - 1:
                cur = lax.ppermute(cur, MP_AXIS, fwd)
        return y

    out = _smap(mesh, local,
                (_spec(x.ndim, dp, seq=MP_AXIS), P(None, MP_AXIS)),
                _spec(x.ndim, dp, last=MP_AXIS))
    return out(x, w)


def matmul_reducescatter(x, w, mesh):
    """Row-parallel matmul whose output reduce-scatters the seq axis,
    decomposed into ring hops hidden behind per-chunk partial matmuls.

    x [..., s, h] (last dim mp-sharded), w [h, M] (dim 0 mp-sharded)
    -> [..., s, M] with dim -2 mp-sharded. Requires h % n == 0,
    s % n == 0.
    """
    n = _axis_sizes(mesh)[MP_AXIS]
    if x.ndim < 2 or x.shape[-1] != w.shape[0]:
        return None
    if x.shape[-1] % n or x.shape[-2] % n:
        return None
    dp = _dp_part(mesh, x)
    fwd = _ring(n)

    def local(xl, wl):
        # xl [..., s, h/n], wl [h/n, M]
        idx = lax.axis_index(MP_AXIS)
        sl = xl.shape[-2] // n

        def pchunk(c):
            return _pmm(lax.dynamic_slice_in_dim(xl, c * sl, sl, axis=-2),
                        wl)

        # after n-1 hops device idx holds seq chunk idx fully summed
        acc = pchunk((idx - 1) % n)
        for t in range(1, n):
            acc = lax.ppermute(acc, MP_AXIS, fwd) \
                + pchunk((idx - t - 1) % n)
        return acc

    out = _smap(mesh, local,
                (_spec(x.ndim, dp, last=MP_AXIS), P(MP_AXIS, None)),
                _spec(x.ndim, dp, seq=MP_AXIS))
    return out(x, w)


# -- Tensor-level routing (consulted by mp_layers) ---------------------------


def _routable(*tensors):
    """All guards a route must pass before leaving the GSPMD path."""
    ctx = current()
    if ctx is None:
        return None
    if _inside_manual_region():
        return None
    for t in tensors:
        if not isinstance(t, Tensor):
            return None
        if not isinstance(t._value, jax.core.Tracer):
            return None
        if getattr(t, "_tape", None) is not None:
            return None
    return ctx


def maybe_column_parallel(x, weight):
    """Ring path for ColumnParallelLinear (gather_output=False under
    sequence parallelism — the only column case with a forward
    collective to hide). Returns the output Tensor (bias NOT applied)
    or None to keep the GSPMD path."""
    ctx = _routable(x, weight)
    if ctx is None or not ctx.sequence_parallel:
        return None
    if x._value.ndim < 2:
        return None
    out = allgather_matmul(x._value, weight._value, ctx.mesh)
    return None if out is None else Tensor(out)


def maybe_row_parallel(x, weight):
    """Ring path for RowParallelLinear: reduce-scatter variant under
    sequence parallelism, decomposed all-reduce otherwise. Returns the
    output Tensor (bias NOT applied) or None to keep the GSPMD path."""
    ctx = _routable(x, weight)
    if ctx is None:
        return None
    if x._value.ndim < 2:
        return None
    if ctx.sequence_parallel:
        out = matmul_reducescatter(x._value, weight._value, ctx.mesh)
    else:
        out = matmul_allreduce(x._value, weight._value, ctx.mesh)
    return None if out is None else Tensor(out)
