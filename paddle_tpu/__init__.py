"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle (~v2.1, the fluid+dygraph era), rebuilt from scratch on
JAX/XLA/Pallas.

Usage mirrors paddle: `import paddle_tpu as paddle`.

Architecture (see SURVEY.md §7 for the full mapping):
- eager Tensor API over jax.Array + tape autograd (dygraph parity)
- compiled execution via the functional engine / paddle_tpu.jit (static &
  distributed parity; one XLA computation per train step)
- parallelism via jax.sharding Mesh + GSPMD specs + shard_map pipelines
  (Fleet parity: dp / tensor / pipeline / sharding hybrid)
"""

from __future__ import annotations

# -- persistent compile cache ----------------------------------------------
# One place for every entry point (trainer, server, launcher children,
# bench scripts, chip_smoke.py).  JAX_COMPILATION_CACHE_DIR, when set,
# is read by jax itself and nothing here overrides it; otherwise the
# cache sits at one fixed path inside the checkout — the path is part
# of the cache key, so it never comes from tempfile, a pid or the time.
import os as _os

import jax as _jax

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

# -- core ------------------------------------------------------------------
from .core.tensor import Parameter, Tensor  # noqa: F401
from .core.config import (  # noqa: F401
    enable_grad, get_default_dtype, no_grad, set_default_dtype,
    set_grad_enabled,
)
from .core.autograd import grad  # noqa: F401
from .core.dtype import dtype_handle as _dtype_handle

# dtype singletons: paddle.float32, ...
bool = _dtype_handle("bool")  # noqa: A001
uint8 = _dtype_handle("uint8")
int8 = _dtype_handle("int8")
int16 = _dtype_handle("int16")
int32 = _dtype_handle("int32")
int64 = _dtype_handle("int64")
float16 = _dtype_handle("float16")
bfloat16 = _dtype_handle("bfloat16")
float32 = _dtype_handle("float32")
float64 = _dtype_handle("float64")
complex64 = _dtype_handle("complex64")
complex128 = _dtype_handle("complex128")

# -- ops must register before the tensor API is used -----------------------
from . import ops  # noqa: F401,E402

# -- functional tensor API (also attaches Tensor methods) ------------------
from .tensor.creation import (  # noqa: F401,E402
    arange, assign, clone, complex, diag, diagflat, empty, empty_like, eye,
    full, full_like, linspace, logspace, meshgrid, ones, ones_like,
    to_tensor, tril, triu, zeros, zeros_like,
)
from .tensor.math import (  # noqa: F401,E402
    abs, acos, acosh, add, addmm, all, allclose, amax, amin, any, asin,
    asinh, atan, atan2, atanh, bmm, ceil, clip, conj, cos, cosh,
    count_nonzero, cross, cumprod, cumsum, diagonal, digamma, divide, dot,
    equal_all, erf, erfinv, exp, expm1, floor, floor_divide, floor_mod,
    fmax, fmin, frac, heaviside, imag, increment, inner, isclose, isfinite,
    isinf, isnan, kron, lerp, lgamma, log, log1p, log2, log10, logaddexp,
    logcumsumexp, logsumexp, matmul, max, maximum, mean, min, minimum, mm,
    mod, multiply, nanmean, nansum, neg, nextafter, outer, pow, prod, real,
    reciprocal, remainder, round, rsqrt, scale, sign, sin, sinh, sqrt,
    square, stanh, subtract, sum, tan, tanh, trace, trunc,
)
from .tensor.manipulation import (  # noqa: F401,E402
    as_complex, as_real, broadcast_tensors, broadcast_to, cast, chunk,
    concat, crop, diag_embed, expand, expand_as, flatten, flip, gather,
    gather_nd, index_sample, index_select, masked_fill, masked_select,
    moveaxis, nonzero, put_along_axis, repeat_interleave, reshape, roll,
    rot90, scatter, scatter_nd, scatter_nd_add, slice, split, squeeze,
    stack, strided_slice, swapaxes, t, take_along_axis, tensordot, tile,
    transpose, unique, unsqueeze, unstack, where,
)
from .tensor.logic import (  # noqa: F401,E402
    equal, greater_equal, greater_than, is_empty, is_tensor, less_equal,
    less_than, logical_and, logical_not, logical_or, logical_xor, not_equal,
)
from .tensor.search import (  # noqa: F401,E402
    argmax, argmin, argsort, bucketize, index_put, kthvalue, mode,
    searchsorted, sort, topk,
)
from .tensor.random import (  # noqa: F401,E402
    bernoulli, multinomial, normal, poisson, rand, randint, randint_like,
    randn, randperm, standard_normal, uniform,
)
from .tensor.stat import (  # noqa: F401,E402
    bincount, histogram, median, numel, quantile, std, var,
)
from .tensor.einsum import einsum  # noqa: F401,E402
from .tensor import linalg  # noqa: F401,E402
from . import tensor  # noqa: F401,E402

# -- framework -------------------------------------------------------------
from .framework import get_rng_state, seed, set_rng_state  # noqa: F401,E402
from . import framework  # noqa: F401,E402

# -- device management -----------------------------------------------------
from .device import (  # noqa: F401,E402
    get_device, is_compiled_with_cuda, is_compiled_with_npu,
    is_compiled_with_rocm, is_compiled_with_xpu, set_device,
)
from . import device  # noqa: F401,E402

# -- subsystem namespaces (imported lazily to keep import light) -----------
from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import rec  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import observe  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from .framework.flags import get_flags, set_flags  # noqa: F401,E402
from .framework import monitor  # noqa: F401,E402
from .framework import errors  # noqa: F401,E402
from .framework.io import load, save  # noqa: F401,E402
from .hapi.model import Model  # noqa: F401,E402
from .nn.layer.layers import Layer  # noqa: F401,E402
from .dataparallel import DataParallel  # noqa: F401,E402

__version__ = "0.1.0"


def disable_static(place=None):
    """Leave static-graph (op capture) mode; eager execution resumes."""
    from .static.program import _disable_static

    _disable_static()


def enable_static():
    """Enter static-graph mode: paddle ops called on `static.data`
    Variables record into the default main Program instead of executing
    (ref fluid/framework.py enable_static). Run with static.Executor."""
    from .static.program import _enable_static

    _enable_static()


def in_dynamic_mode():
    from .static.program import in_static_mode

    return not in_static_mode()


def backward(tensors, grad_tensors=None, retain_graph=False):
    from .core.autograd import backward as _b

    return _b(tensors, grad_tensors, retain_graph)


# importing the clip/device submodules above rebound the package
# attributes to the modules; the paddle API names are the functions
from .tensor.math import clip as clip  # noqa: F401,E402

from . import reader  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from .reader import batch  # noqa: F401,E402
from . import dataset  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import compat  # noqa: F401,E402
from . import callbacks  # noqa: F401,E402
from . import hub  # noqa: F401,E402
