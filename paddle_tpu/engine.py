"""Functional engine: the compiled execution path.

TPU-native replacement for the reference's static-graph Executor +
ParallelExecutor (paddle/fluid/framework/executor.cc, parallel_executor.cc)
and the Fleet meta-optimizer program rewrites: instead of interpreting a
ProgramDesc op-by-op, the eager model code is traced *functionally* (the
same nn.Layer forward runs with parameter values swapped for tracers) and
compiled by XLA into one program per train/eval step. Parallelism is
expressed with jax.sharding (GSPMD) specs attached to parameters
(`Parameter.param_spec`) and optimizer-state sharding rules (ZeRO).

Autograd note: inside the functional trace the eager tape is bypassed
(jax.grad differentiates the traced computation directly); `detach()` /
frozen parameters cut gradients via lax.stop_gradient / constant capture,
matching dygraph semantics.
"""

from __future__ import annotations

import contextlib
import time
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .core.tensor import Parameter, Tensor
from .framework import random as _random


# ---------------------------------------------------------------------------
# functional_call: run a Layer's forward with externally-supplied params
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _swap_state(layer, values: dict):
    """Temporarily replace parameter/buffer backing arrays with `values`.
    Yields the state-dict so callers can read (possibly traced) post-call
    buffer values before restoration."""
    sd = layer.state_dict()
    saved = {}
    for name, arr in values.items():
        t = sd.get(name)
        if t is None:
            continue
        saved[name] = t._value
        t._value = arr
    try:
        yield sd
    finally:
        for name, old in saved.items():
            sd[name]._value = old


def state_values(layer):
    """OrderedDict name -> backing array for all params + persistable
    buffers."""
    return OrderedDict((k, v._value) for k, v in layer.state_dict().items())


def param_values(layer):
    return OrderedDict(
        (k, v._value) for k, v in layer.state_dict().items()
        if isinstance(v, Parameter) and not v.stop_gradient)


def buffer_values(layer):
    params = set()
    for k, v in layer.state_dict().items():
        if isinstance(v, Parameter) and not v.stop_gradient:
            params.add(k)
    return OrderedDict(
        (k, v._value) for k, v in layer.state_dict().items()
        if k not in params)


def param_specs(layer):
    """GSPMD PartitionSpecs per trainable param name (None = replicated)."""
    return OrderedDict(
        (k, getattr(v, "param_spec", None))
        for k, v in layer.state_dict().items()
        if isinstance(v, Parameter) and not v.stop_gradient)


def _unwrap(out):
    return jax.tree.map(
        lambda t: t._value if isinstance(t, Tensor) else t, out,
        is_leaf=lambda t: isinstance(t, Tensor))


def functional_call(layer, values, *args, capture_buffers=False, **kwargs):
    """Run `layer(*args)` with parameters/buffers taken from `values`
    (dict name->array). Differentiable wrt `values` under jax traces."""
    from .core.config import no_tape

    wrapped = [Tensor(a) if not isinstance(a, Tensor) else a for a in args]
    with no_tape(), _swap_state(layer, values) as sd:
        out = layer(*wrapped, **kwargs)
        if capture_buffers:
            post = OrderedDict(
                (k, sd[k]._value) for k in values if k in sd)
            return _unwrap(out), post
    return _unwrap(out)


def functional_apply(layer, values, fn, mesh=None):
    """Run an arbitrary `fn(layer)` with parameters/buffers taken from
    `values` (dict name->array), tape off — the inference analogue of
    functional_call for callers that need more than one plain forward
    (e.g. the serving decode step: cached GPT forward + lm-head logits
    inside one jitted function). Returns fn's result with Tensors
    unwrapped to arrays.

    When `mesh` is given the call runs inside `ops.overlap.region(mesh)`
    so RowParallelLinear matmuls route through the ring collective-matmul
    kernels when `FLAGS_mp_overlap` is on and the mesh qualifies — the
    same silent-guard contract as training (unsupported mesh or
    non-divisible shapes fall back to plain GSPMD)."""
    from .core.config import no_tape
    from .ops import overlap

    region = (overlap.region(mesh) if mesh is not None
              else contextlib.nullcontext())
    with region, no_tape(), _swap_state(layer, values):
        out = fn(layer)
    return _unwrap(out)


# ---------------------------------------------------------------------------
# train step builder
# ---------------------------------------------------------------------------


class TrainState:
    """Bundles params / opt state / buffers for the compiled path."""

    def __init__(self, params, opt_state, buffers, step=0):
        self.params = params
        self.opt_state = opt_state
        self.buffers = buffers
        self.step = step


def init_train_state(layer, optimizer, *, opt_state_mesh_host=None):
    """Build the compiled-path state.  `opt_state_mesh_host`: a mesh —
    park each parameter's freshly-built optimizer state in pinned host
    memory immediately, so the whole-tree state (2x params for Adam)
    never coexists on device.  For billion-parameter offload configs
    that transient footprint is itself the OOM; the per-param peak here
    is one parameter's state."""
    params = dict(param_values(layer))
    buffers = dict(buffer_values(layer))
    host_sh = None
    if opt_state_mesh_host is not None:
        kind = _host_memory_kind(opt_state_mesh_host)
        if kind is not None:
            host_sh = NamedSharding(opt_state_mesh_host, P(),
                                    memory_kind=kind)
    opt_state = {}
    for k, v in params.items():
        st = optimizer._init_state(v)
        if host_sh is not None:
            st = jax.device_put(st, host_sh)
            jax.block_until_ready(st)  # free the device copy promptly
        opt_state[k] = st
    return TrainState(params, opt_state, buffers)


def write_back(layer, state: TrainState):
    """Copy compiled-state arrays back into the eager Layer."""
    sd = layer.state_dict()
    for k, v in state.params.items():
        if k in sd:
            sd[k]._value = v
    for k, v in state.buffers.items():
        if k in sd:
            sd[k]._value = v


def host_offload_shardings(mesh, dev_sh_tree):
    """(device, host) sharding trees for at-rest optimizer-state offload
    (ref sharding/offload_helper.py), or None when the backend has no
    host memory space. Shared by Engine and HybridParallelEngine."""
    kind = _host_memory_kind(mesh)
    if kind is None:
        return None
    host = jax.tree.map(
        lambda sh: NamedSharding(mesh, sh.spec, memory_kind=kind),
        dev_sh_tree, is_leaf=lambda x: isinstance(x, NamedSharding))
    return dev_sh_tree, host


def _host_memory_kind(mesh):
    """'pinned_host' when the backend exposes it (TPU and CPU do), else
    None — offload degrades to device memory with a warning."""
    dev = next(iter(mesh.devices.flat))
    if "pinned_host" in {m.kind for m in dev.addressable_memories()}:
        return "pinned_host"
    import warnings

    warnings.warn("optimizer-state offload requested but the backend has "
                  "no pinned_host memory space; keeping state on device")
    return None


def build_shardings(layer, optimizer, mesh, *, dp_axis="dp",
                    sharding_axis=None, zero_stage=0):
    """Construct NamedShardings for params / opt state from param_specs.

    ZeRO (`sharding` in fleet terms, ref fleet/meta_optimizers/sharding_
    optimizer.py + dygraph_sharding_optimizer.py:27):
      stage>=1  shard optimizer moments along `sharding_axis` on the
                first divisible dim (GSPMD partitions the update)
      stage>=3  additionally shard the PARAMETERS the same way — XLA
                all-gathers them where the forward needs full values and
                frees the gathered copies after use (the stage-3
                working-set behaviour)
    """
    specs = param_specs(layer)

    def _zero_spec(arr):
        """First-divisible-dim sharding spec, or None."""
        if sharding_axis is None or arr.ndim < 1:
            return None
        axis_size = mesh.shape[sharding_axis]
        if arr.shape[0] % axis_size == 0 and arr.shape[0] >= axis_size:
            return P(sharding_axis, *([None] * (arr.ndim - 1)))
        return None

    def param_sharding(name, arr):
        spec = specs.get(name)
        if spec is not None:
            return NamedSharding(mesh, spec)
        if zero_stage >= 3:
            zspec = _zero_spec(arr)
            if zspec is not None:
                return NamedSharding(mesh, zspec)
        return NamedSharding(mesh, P())

    warned = set()  # once per param name across state leaves AND grads

    def opt_leaf_sharding(name, arr):
        spec = specs.get(name)
        if spec is not None and any(s is not None for s in spec):
            return NamedSharding(mesh, spec) if len(spec) == arr.ndim \
                else NamedSharding(mesh, P())
        if zero_stage >= 1 and sharding_axis is not None and arr.ndim >= 1:
            zspec = _zero_spec(arr)
            if zspec is not None:
                return NamedSharding(mesh, zspec)
            axis_size = mesh.shape[sharding_axis]
            if arr.size >= axis_size and name not in warned:
                warned.add(name)
                import warnings

                warnings.warn(
                    f"ZeRO: state/gradient for '{name}' (shape "
                    f"{arr.shape}) is not divisible by sharding degree "
                    f"{axis_size} on dim 0; replicating this parameter",
                    stacklevel=3)
        return NamedSharding(mesh, P())

    return param_sharding, opt_leaf_sharding


# reserved buffer slots for in-graph dynamic loss scaling
LOSS_SCALE_KEY = "__loss_scale__"
GOOD_STEPS_KEY = "__loss_scale_good_steps__"
BAD_STEPS_KEY = "__loss_scale_bad_steps__"
# reserved buffer slot for the in-graph anomaly guard: consecutive
# non-finite-step counter (int32, lives with the other step state so it
# is donated/checkpointed like everything else)
ANOMALY_BAD_STEPS_KEY = "__anomaly_bad_steps__"
# reserved buffer slot for FLAGS_record_grad_norm: global gradient norm
# (pre-clip) computed inside the compiled step, read lazily by the
# flight recorder — no extra device pass, no per-step host sync
GRAD_NORM_KEY = "__grad_norm__"
# reserved buffer slot for FLAGS_lowp_matmul delayed scaling: the
# quantization.scaling.ScaleState pytree rides the buffer carry so the
# per-tensor amax history/scales update in-graph — donated with the rest
# of the step state, never a host sync or retrace
LOWP_SCALE_KEY = "__lowp_scale__"
_RESERVED_BUFFER_KEYS = (LOSS_SCALE_KEY, GOOD_STEPS_KEY, BAD_STEPS_KEY,
                         ANOMALY_BAD_STEPS_KEY, GRAD_NORM_KEY,
                         LOWP_SCALE_KEY)

# paddle GradScaler defaults (ref python/paddle/amp/grad_scaler.py)
DEFAULT_SCALE_CONFIG = dict(
    init_loss_scaling=2.0 ** 15, incr_ratio=2.0, decr_ratio=0.5,
    incr_every_n_steps=1000, decr_every_n_nan_or_inf=2)


def make_train_step(layer, loss_fn, optimizer, *, grad_clip=None,
                    donate=True, mesh=None, batch_spec=None, zero_stage=0,
                    sharding_axis=None, loss_scale=None, comm_dtype=None,
                    anomaly_guard=False, record_grad_norm=None, lowp=None):
    """Build a jitted step:
    (params, buffers, opt_state, batch, lr, key) ->
        (loss, params, buffers, opt_state)

    batch: dict with 'inputs' (tuple of arrays) and optional 'labels'
    (tuple). loss_fn(outputs, *labels) -> scalar Tensor.

    anomaly_guard: replaces FLAGS_check_nan_inf's per-op eager scan for
    compiled training (ref nan_inf_utils_detail.cu). One fused in-graph
    finiteness bit over loss + unscaled grads per step; a bad step skips
    the parameter/optimizer/buffer update entirely (jnp.where select, no
    host sync, no recompilation) and increments the
    ANOMALY_BAD_STEPS_KEY buffer, which the Engine reads at step
    boundaries to trigger checkpoint rollback.

    comm_dtype ('bfloat16'/'float16'): the fp16_allreduce strategy (ref
    fleet/meta_optimizers/fp16_allreduce_optimizer.py). Under GSPMD the
    gradient all-reduce is fused into the backward matmuls, so reduced-
    precision communication means computing those grads in the reduced
    dtype: the step runs under O2 autocast of `comm_dtype` while params
    and optimizer state stay fp32 (master weights).
    """
    if record_grad_norm is None:
        from .framework.flags import flag as _flag

        record_grad_norm = _flag("FLAGS_record_grad_norm")
    grad_clip = grad_clip if grad_clip is not None else \
        getattr(optimizer, "_grad_clip", None)
    # per-param decay/lr-mult metadata baked in as compile-time constants
    # (mirrors eager Optimizer._preprocess; ADVICE r1 fix)
    _sd = layer.state_dict()
    # ASP n:m masks re-applied in-graph after every update, so pruned
    # weights stay zero on the compiled path too (ref asp_optimizer.py)
    from .incubate.asp import apply_masks_tree as _asp_apply, \
        masks_for as _asp_masks_for

    asp_masks = _asp_masks_for(layer)

    from .ops import overlap as _overlap
    from .ops import lowp as _lowp

    _seq_parallel = _overlap.model_sequence_parallel(layer)
    if lowp is None:
        lowp = _lowp.mode() != "off"

    def loss_of(params, buffers, batch, key, lowp_state=None):
        if comm_dtype is not None:
            from .amp import auto_cast

            amp_ctx = auto_cast(enable=True, level="O2", dtype=comm_dtype)
        else:
            amp_ctx = contextlib.nullcontext()
        # mp collective-matmul overlap (trace-time no-op unless
        # FLAGS_mp_overlap is on and the mesh is pure dp x mp)
        # lowp delayed scaling: bind the ScaleState carry to this
        # trace's quantized matmuls (trace-order slots); the updated
        # state leaves through the aux return, never a Python cell
        with _random.rng_scope(key), amp_ctx, _overlap.region(
                mesh, sequence_parallel=_seq_parallel), \
                _lowp.scale_region(lowp_state) as lowp_rec:
            inputs = batch["inputs"]
            if not isinstance(inputs, (list, tuple)):
                inputs = (inputs,)
            values = {**buffers, **params}
            out, post = functional_call(layer, values, *inputs,
                                        capture_buffers=True)
            labels = batch.get("labels", ())
            loss = loss_fn(jax.tree.map(Tensor, out)
                           if not isinstance(out, Tensor) else out,
                           *(Tensor(l) for l in labels))
            loss_v = loss._value if isinstance(loss, Tensor) else loss
            new_buffers = {k: post[k] for k in buffers}
            if lowp_rec is not None:
                new_buffers[LOWP_SCALE_KEY] = lowp_rec.updated()
            return loss_v.astype(jnp.float32), new_buffers

    # single build of the sharding rules, shared by the ZeRO-2 gradient
    # constraint and the jit in/out shardings below
    param_sh = opt_sh = None
    if mesh is not None:
        param_sh, opt_sh = build_shardings(
            layer, optimizer, mesh, zero_stage=zero_stage,
            sharding_axis=sharding_axis)

    # ZeRO-2: constrain gradients to the moment sharding so GSPMD lowers
    # the dp grad sum into reduce-scatter feeding sharded updates
    # (ref fleet/meta_optimizers/sharding_optimizer.py grad sharding)
    grad_constraint = None
    if zero_stage >= 2 and mesh is not None and sharding_axis is not None:
        def grad_constraint(grads):
            return {k: jax.lax.with_sharding_constraint(
                g, opt_sh(k, g)) for k, g in grads.items()}

    # In-graph dynamic loss scaling (fp16-compat mode; ref
    # operators/amp/check_finite_and_unscale_op.cc +
    # update_loss_scaling_op.cc, python/paddle/amp/grad_scaler.py
    # defaults). State lives in reserved buffer slots; the scale decays
    # after `decr_every_n_nan_or_inf` CONSECUTIVE non-finite steps and
    # grows after `incr_every_n_steps` consecutive finite ones.
    # `loss_scale` may be: None | float (static) | "dynamic" | dict of
    # GradScaler knobs.
    scale_cfg = dict(DEFAULT_SCALE_CONFIG)
    if isinstance(loss_scale, dict):
        scale_cfg.update(loss_scale)
        dynamic_scale = True
    else:
        dynamic_scale = loss_scale == "dynamic"
    static_scale = float(loss_scale) if (
        loss_scale is not None and not dynamic_scale
        and not isinstance(loss_scale, dict)) else None

    def _step_impl(params, buffers, opt_state, batch, lr, key):
        # trace-time: this body runs exactly once per compilation, so
        # one recorded event == one compile of the step program
        from . import observe as _observe

        _observe.record_compile(
            "train_step", signature=_observe.signature_of(batch))
        if dynamic_scale:
            scale = buffers[LOSS_SCALE_KEY]
            good = buffers[GOOD_STEPS_KEY]
            bad = buffers[BAD_STEPS_KEY]
        elif static_scale is not None:
            scale = jnp.asarray(static_scale, jnp.float32)
        anomaly_prev = buffers.get(ANOMALY_BAD_STEPS_KEY)
        lowp_prev = buffers.get(LOWP_SCALE_KEY)
        model_buffers = {k: v for k, v in buffers.items()
                         if k not in _RESERVED_BUFFER_KEYS}

        def scaled_loss(params, model_buffers, batch, key):
            loss, nb = loss_of(params, model_buffers, batch, key,
                               lowp_state=lowp_prev)
            if loss_scale is not None:
                return loss * scale, (loss, nb)
            return loss, (loss, nb)

        from .framework import monitor as _monitor

        traced = ("dropout_masks_traced", "dropout_mask_elements_traced")
        before = [_monitor.stat_get(name) for name in traced]
        (_, (loss, new_buffers)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params, model_buffers, batch, key)
        # the dropout masks this program draws, counted where they were
        # traced: one a site, whatever reads it forward and backward
        masks, elements = (_monitor.stat_get(name) - was
                           for name, was in zip(traced, before))
        _observe.annotate("train_step", dropout_masks=masks,
                          dropout_mask_elements=elements)
        if loss_scale is not None:
            grads = jax.tree.map(lambda g: g / scale, grads)
            # finiteness is judged on the raw unscaled grads BEFORE
            # decay/clip — clippers like ClipGradByValue would map inf to
            # finite values and hide the overflow (ref
            # check_finite_and_unscale_op: the check precedes clipping)
            finite = jnp.asarray(True)
            for g in jax.tree.leaves(grads):
                finite = finite & jnp.isfinite(g).all()
        if anomaly_guard:
            from .amp import all_finite as _all_finite

            # like the loss-scale check, judged on RAW grads before
            # decay/clip (a value clipper would map inf -> finite and
            # hide the anomaly), plus the loss itself (a NaN loss with
            # zero grads — e.g. a poisoned masked branch — must count)
            grads_finite = finite if loss_scale is not None \
                else _all_finite(grads)
            guard_ok = grads_finite & jnp.isfinite(loss)
        if record_grad_norm:
            # global l2 norm of the RAW grads (post-unscale, pre-
            # decay/clip) — the number a clipper would have seen
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
        if grad_constraint is not None:
            grads = grad_constraint(grads)
        metas = optimizer.param_metas_for(params, _sd)
        # eager _preprocess order: coupled decay first, then clip
        grads = optimizer.decay_gradients_tree(params, grads, metas)
        if grad_clip is not None:
            grads = grad_clip._clip_fn(grads)
        new_params, new_opt = optimizer.apply_gradients_tree(
            params, grads, opt_state, lr, metas=metas)
        if asp_masks:
            new_params = _asp_apply(layer, new_params,
                                    engine_name="Engine")
        if loss_scale is not None:
            # both static and dynamic scaling skip non-finite steps
            # (paddle GradScaler found_inf semantics)
            pick = lambda new, old: jax.tree.map(  # noqa: E731
                lambda n, o: jnp.where(finite, n, o), new, old)
            new_params = pick(new_params, params)
            new_opt = pick(new_opt, opt_state)
            new_buffers = dict(new_buffers)
        if anomaly_guard:
            # skip the whole update on a bad step — params, moments AND
            # captured buffer updates (BN running stats etc.) — and count
            # consecutive bad steps in-graph; everything is a where()
            # select on the one fused bit, so the compiled step stays a
            # single program with no host round-trip
            gpick = lambda new, old: jax.tree.map(  # noqa: E731
                lambda n, o: jnp.where(guard_ok, n, o), new, old)
            new_params = gpick(new_params, params)
            new_opt = gpick(new_opt, opt_state)
            # the old-side tree must mirror new_buffers' keys — the lowp
            # ScaleState rides along, and a bad step keeps the previous
            # scales (its amaxes may be the very poison being skipped)
            old_buffers = dict(model_buffers)
            if lowp_prev is not None and LOWP_SCALE_KEY in new_buffers:
                old_buffers[LOWP_SCALE_KEY] = lowp_prev
            new_buffers = dict(gpick(new_buffers, old_buffers))
            new_buffers[ANOMALY_BAD_STEPS_KEY] = jnp.where(
                guard_ok, 0, anomaly_prev + 1).astype(jnp.int32)
        if record_grad_norm:
            # written AFTER the guard's where()-select over the model
            # buffers so the recorded norm is the step's actual raw
            # norm even when the update itself was skipped
            new_buffers = dict(new_buffers)
            new_buffers[GRAD_NORM_KEY] = gnorm.astype(jnp.float32)
        if dynamic_scale:
            good_next = jnp.where(finite, good + 1, 0)
            bad_next = jnp.where(finite, 0, bad + 1)
            grow = finite & (good_next >= scale_cfg["incr_every_n_steps"])
            shrink = (~finite) & (
                bad_next >= scale_cfg["decr_every_n_nan_or_inf"])
            new_scale = jnp.where(
                grow, scale * scale_cfg["incr_ratio"],
                jnp.where(shrink, scale * scale_cfg["decr_ratio"], scale))
            new_buffers[LOSS_SCALE_KEY] = new_scale
            new_buffers[GOOD_STEPS_KEY] = jnp.where(grow, 0, good_next)
            new_buffers[BAD_STEPS_KEY] = jnp.where(shrink, 0, bad_next)
        return loss, new_params, new_buffers, new_opt

    # the function's name is the program's: `jit_train_step` on the
    # capture's `XLA Modules` line, `PjitFunction(train_step)` on the host's
    def train_step(params, buffers, opt_state, batch, lr, key):
        if mesh is None:
            return _step_impl(params, buffers, opt_state, batch, lr, key)
        # meshed step: GSPMD-partitioned program — attention runs under
        # a shard_map over the batch/head axes so the Mosaic kernel
        # runs per-shard (fused_ops.gspmd_tracing)
        from .ops.fused_ops import gspmd_tracing

        with gspmd_tracing(mesh):
            return _step_impl(params, buffers, opt_state, batch, lr, key)

    in_shardings = None
    out_shardings = None
    if mesh is not None:
        params0 = param_values(layer)
        p_sh = {k: param_sh(k, v) for k, v in params0.items()}
        buf_sh = {k: NamedSharding(mesh, P())
                  for k in buffer_values(layer)}
        if loss_scale == "dynamic" or isinstance(loss_scale, dict):
            buf_sh[LOSS_SCALE_KEY] = NamedSharding(mesh, P())
            buf_sh[GOOD_STEPS_KEY] = NamedSharding(mesh, P())
            buf_sh[BAD_STEPS_KEY] = NamedSharding(mesh, P())
        if anomaly_guard:
            buf_sh[ANOMALY_BAD_STEPS_KEY] = NamedSharding(mesh, P())
        if record_grad_norm:
            buf_sh[GRAD_NORM_KEY] = NamedSharding(mesh, P())
        if lowp:
            # sharding prefix over the ScaleState pytree: replicated
            buf_sh[LOWP_SCALE_KEY] = NamedSharding(mesh, P())
        opt0 = {k: optimizer._init_state(v) for k, v in params0.items()}
        o_sh = {k: jax.tree.map(lambda a, kk=k: opt_sh(kk, a), st)
                for k, st in opt0.items()}
        repl = NamedSharding(mesh, P())
        b_sh = batch_spec if batch_spec is not None else repl
        in_shardings = (p_sh, buf_sh, o_sh, b_sh, repl, repl)
        out_shardings = (repl, p_sh, buf_sh, o_sh)
    donate_argnums = (0, 1, 2) if donate else ()
    if mesh is not None:
        jitted = jax.jit(train_step, donate_argnums=donate_argnums,
                         in_shardings=in_shardings,
                         out_shardings=out_shardings)
    else:
        jitted = jax.jit(train_step, donate_argnums=donate_argnums)
    # the un-jitted step is re-usable inside larger traced loops (bench
    # scans N steps in one program to amortise dispatch latency)
    jitted._raw_step_fn = train_step
    # exposed so Engine can pre-place live state into these shardings
    # (offload moves opt state to host memory; jit requires the arg's
    # memory kind to already match)
    jitted._state_shardings = (
        (in_shardings[0], in_shardings[1], in_shardings[2])
        if in_shardings is not None else None)
    return jitted


def make_eval_step(layer, mesh=None):
    def eval_fn(values, *inputs):
        was_training = layer.training
        layer.eval()
        try:
            return functional_call(layer, values, *inputs)
        finally:
            if was_training:
                layer.train()

    return jax.jit(eval_fn)


def compile_step(step_fn, protos):
    """Lower and compile the SAME program an engine's `train_batch`
    runs (a persistent-cache hit), kept out of the compile-event
    registry and any no_retrace guard."""
    if step_fn is None or protos is None:
        raise RuntimeError("run train_batch() once first")
    from . import observe as _observe

    with _observe.retrace.suppress():
        return step_fn.lower(*protos).compile()


class Engine:
    """Drives compiled training of an eager Layer: the Paddle user keeps
    the dygraph API (model, optimizer, loss), this turns each step into one
    XLA program. Used by hapi.Model.prepare, bench, and the distributed
    trainers."""

    def __init__(self, layer, optimizer, loss_fn, grad_clip=None, mesh=None,
                 batch_spec=None, zero_stage=0, sharding_axis=None,
                 loss_scale=None, offload=False, comm_dtype=None,
                 anomaly_guard=False):
        self.layer = layer
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.batch_spec = batch_spec
        self.zero_stage = zero_stage
        self.sharding_axis = sharding_axis
        self.loss_scale = loss_scale
        self.offload = offload
        self.comm_dtype = comm_dtype
        self.anomaly_guard = anomaly_guard
        self.state = init_train_state(
            layer, optimizer,
            opt_state_mesh_host=mesh if offload else None)
        if loss_scale == "dynamic" or isinstance(loss_scale, dict):
            # in-graph dynamic loss scaling state (fp16-compat mode)
            cfg = dict(DEFAULT_SCALE_CONFIG)
            if isinstance(loss_scale, dict):
                cfg.update(loss_scale)
            self.state.buffers[LOSS_SCALE_KEY] = jnp.asarray(
                float(cfg["init_loss_scaling"]), jnp.float32)
            self.state.buffers[GOOD_STEPS_KEY] = jnp.asarray(0, jnp.int32)
            self.state.buffers[BAD_STEPS_KEY] = jnp.asarray(0, jnp.int32)
        if anomaly_guard:
            self.state.buffers[ANOMALY_BAD_STEPS_KEY] = \
                jnp.asarray(0, jnp.int32)
        # FLAGS_record_grad_norm is latched at construction: the buffer
        # tree (and so the compiled step's signature) must not change
        # mid-run, or every later step would retrace
        from .framework.flags import flag as _flag

        self._record_grad_norm = _flag("FLAGS_record_grad_norm")
        if self._record_grad_norm:
            self.state.buffers[GRAD_NORM_KEY] = jnp.asarray(0.0,
                                                            jnp.float32)
        # FLAGS_lowp_matmul latched the same way: the ScaleState buffer
        # joins the donated carry at construction or never
        from .ops import lowp as _lowp_mod

        self._lowp = _lowp_mod.mode() != "off"
        if self._lowp:
            from .quantization.scaling import init_scale_state

            self.state.buffers[LOWP_SCALE_KEY] = init_scale_state()
        self._step_fn = None
        self._offload_sh = None
        self._grad_clip = grad_clip
        self._step_protos = None
        self._mem_analysis = None
        self._batch_sig = None
        self._ckpt_manager = None
        self._last_batch = None
        # the losses of dispatched steps the device had not finished at
        # the last dispatch, oldest first
        self._in_flight = deque()

    def _build(self):
        self._step_fn = make_train_step(
            self.layer, self.loss_fn, self.optimizer,
            grad_clip=self._grad_clip, mesh=self.mesh,
            batch_spec=self.batch_spec, zero_stage=self.zero_stage,
            sharding_axis=self.sharding_axis, loss_scale=self.loss_scale,
            comm_dtype=self.comm_dtype, anomaly_guard=self.anomaly_guard,
            record_grad_norm=self._record_grad_norm, lowp=self._lowp)
        self._offload_sh = None
        if self.offload and self._step_fn._state_shardings is not None:
            # optimizer-state offload (ref sharding/offload_helper.py):
            # state RESTS in pinned host memory between steps and moves
            # to device around each call. (In-graph streaming transfers
            # need TPU host-offload support; the at-rest form works on
            # every backend and still frees device memory between steps.)
            # The freshly-initialised state stays on device — parking it
            # now would just round-trip it back in the first step.
            _, _, o_sh = self._step_fn._state_shardings
            self._offload_sh = host_offload_shardings(self.mesh, o_sh)

    @staticmethod
    def _arrs(ts):
        # jax.Array passes through untouched: DataLoader device
        # prefetch must not be undone by a jnp.asarray round-trip
        return tuple(
            t._value if isinstance(t, Tensor)
            else t if isinstance(t, jax.Array)
            else jnp.asarray(t)
            for t in ts)

    def _steps_in_flight(self):
        """Steps dispatched and not yet finished, without a sync: a
        step's loss is ready when the step is done, and steps finish in
        the order they were dispatched. 0 = the next dispatch goes to a
        device with nothing to do."""
        flying = self._in_flight
        while flying and flying[0].is_ready():
            flying.popleft()
        return len(flying)

    def train_batch(self, inputs, labels=()):
        from . import observe as _observe
        from .framework import monitor as _monitor

        t_step0 = time.perf_counter()
        if self._step_fn is None:
            self._build()
        with _observe.phase("host-prep"):
            if not isinstance(inputs, (list, tuple)):
                inputs = (inputs,)
            if not isinstance(labels, (list, tuple)):
                labels = (labels,)
            # stashed (host-side references) so attribute_step can
            # replay the live step shape under an xplane capture
            self._last_batch = (inputs, labels)
            batch = {"inputs": self._arrs(inputs),
                     "labels": self._arrs(labels)}
            from .framework import faults as _faults

            # fault-injection point: a scheduled 'nan' action poisons
            # the HOST batch (in-graph effect on loss/grads, no
            # recompilation) — the deterministic way to exercise the
            # anomaly guard
            batch = _faults.fault_point("train.batch", batch)
            key = _random.default_generator.next_key()
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        opt_state = self.state.opt_state
        if self._offload_sh is not None:
            dev_sh, host_sh = self._offload_sh
            with _observe.phase("h2d"):
                opt_state = jax.device_put(opt_state, dev_sh)
        # cheap per-step signature: plain tuple comprehension over the
        # two known leaf tuples instead of a jax.tree.map traversal
        # (tree.map rebuilds registry nodes + a dict every step; this is
        # pure python on ~4 leaves)
        batch_sig = (
            tuple((a.shape, a.dtype.name) for a in batch["inputs"]),
            tuple((a.shape, a.dtype.name) for a in batch["labels"]),
        )
        compiling = (self._step_protos is None
                     or batch_sig != self._batch_sig)
        if compiling:
            # a new batch shape means a new compiled program: refresh
            # the protos so memory_analysis() reports the live program
            self._batch_sig = batch_sig
            self._mem_analysis = None
            self._step_protos = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (self.state.params, self.state.buffers, opt_state,
                 batch, lr, key))
        in_flight = self._steps_in_flight()
        _monitor.stat_add("train_steps")
        if in_flight == 0:
            _monitor.stat_add("train_dispatches_device_idle")
        t_fn0 = time.perf_counter()
        with _observe.phase("compile" if compiling else "device-step",
                            step=self.state.step + 1, in_flight=in_flight):
            loss, self.state.params, self.state.buffers, new_opt = \
                self._step_fn(self.state.params, self.state.buffers,
                              opt_state, batch, lr, key)
        self._in_flight.append(loss)
        if compiling:
            # the step body's trace-time record_compile logged the
            # event; backfill how long trace+compile+first-dispatch took
            _observe.annotate("train_step",
                              wall_s=time.perf_counter() - t_fn0)
        if self._offload_sh is not None:
            with _observe.phase("h2d"):
                new_opt = jax.device_put(new_opt, self._offload_sh[1])
        self.state.opt_state = new_opt
        self.state.step += 1
        if self.anomaly_guard:
            # the counter readback is the guard's only host sync and it
            # blocks dispatch, so amortise it: the in-graph guard skips
            # every bad update immediately regardless, the host only
            # decides ROLLBACK — which FLAGS_anomaly_check_interval may
            # delay by up to interval-1 (bad, already-skipped) steps
            from .framework import flags as _flags

            interval = _flags.flag("FLAGS_anomaly_check_interval")
            if interval <= 1 or self.state.step % interval == 0:
                with _observe.phase("anomaly-readback"):
                    self._check_anomaly()
        self._flight_record(loss, compiling,
                            time.perf_counter() - t_step0, in_flight)
        from . import profiler as _profiler

        if _profiler.is_op_profiling_enabled():
            _profiler.record_device_memory("train_batch")
        return Tensor(loss)

    def _flight_record(self, loss, compiling, step_s, in_flight):
        """One flight-recorder entry per step. Loss / grad-norm /
        anomaly counter stay as device arrays (no host sync here); the
        recorder materializes them only when a black box is dumped."""
        from . import observe as _observe
        from .framework import flags as _flags

        fields = {"loss": loss, "step_ms": step_s * 1e3,
                  "in_flight": in_flight, "compiled": compiling}
        if self._record_grad_norm:
            fields["grad_norm"] = self.state.buffers[GRAD_NORM_KEY]
        if self.anomaly_guard:
            fields["anomaly_bad_steps"] = \
                self.state.buffers[ANOMALY_BAD_STEPS_KEY]
        if _flags.flag("FLAGS_flight_record_memory"):
            from . import device as _device

            try:
                fields["bytes_in_use"] = \
                    _device.memory_stats()["bytes_in_use"]
            except Exception:
                pass
        _observe.flight.record_step(self.state.step, **fields)

    def attribute_step(self, logdir=None, steps=1, top=10):
        """Where does the device time of a training step go?  Captures
        an xplane trace of `steps` replays of the LAST train_batch shape
        and classifies device time into matmul / attention / collective
        / elementwise / other buckets (observe.attribute) — the
        measurement ROADMAP item 4's overlap work starts from.

        NOTE: state is donated through the compiled step, so the traced
        steps are REAL steps — training advances by `steps`.  Returns
        the attribution report dict (buckets, fractions, total_us,
        top_ops); the raw capture stays under `logdir` for xprof."""
        if self._last_batch is None:
            raise RuntimeError("run train_batch() once first")
        import tempfile

        from . import observe as _observe, profiler as _profiler

        if logdir is None:
            logdir = tempfile.mkdtemp(prefix="paddle-attrib-")
        inputs, labels = self._last_batch
        _profiler.start_trace(logdir)
        try:
            for _ in range(steps):
                self.train_batch(inputs, labels)
            # drain async dispatch so every step's device work lands
            # inside the capture window
            jax.block_until_ready(self.state.params)
        finally:
            _profiler.stop_trace()
        return _observe.attribute(logdir, top=top)

    def overlap_report(self, logdir=None, steps=1):
        """Capture a trace of `steps` real steps (same mechanics as
        attribute_step) and pair the collective bucket against
        concurrently-resident matmul/attention time: returns
        observe.overlap_report's dict, whose headline
        `exposed_collective_frac` is the share of device time spent in
        collectives with NO compute in flight — the number the
        FLAGS_mp_overlap ring schedule exists to push down."""
        if self._last_batch is None:
            raise RuntimeError("run train_batch() once first")
        import tempfile

        from . import observe as _observe, profiler as _profiler

        if logdir is None:
            logdir = tempfile.mkdtemp(prefix="paddle-overlap-")
        inputs, labels = self._last_batch
        _profiler.start_trace(logdir)
        try:
            for _ in range(steps):
                self.train_batch(inputs, labels)
            jax.block_until_ready(self.state.params)
        finally:
            _profiler.stop_trace()
        return _observe.overlap_report(logdir)

    def memory_analysis(self) -> dict:
        """MEASURED per-step device memory of the compiled train step
        (XLA's buffer assignment — ref profiler.proto:38 MemEvent /
        monitor.h:77 GPU mem high-watermark, which infer what XLA here
        reports exactly).  Keys in bytes: arguments (resident state:
        params/opt/batch), temps (activations + workspace), outputs,
        alias (donated arg<->output reuse), generated_code, peak
        (XLA's peak liveness when reported, else arg+temp+out-alias);
        host_* mirror them for host-memory-kind buffers (offload)."""
        if self._mem_analysis is None:
            from . import observe as _observe

            ma = compile_step(self._step_fn,
                              self._step_protos).memory_analysis()
            peak = getattr(ma, "peak_memory_in_bytes", 0) or (
                ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            self._mem_analysis = {
                "arguments": ma.argument_size_in_bytes,
                "temps": ma.temp_size_in_bytes,
                "outputs": ma.output_size_in_bytes,
                "alias": ma.alias_size_in_bytes,
                "generated_code": ma.generated_code_size_in_bytes,
                "peak": peak,
                "host_arguments": ma.host_argument_size_in_bytes,
                "host_temps": ma.host_temp_size_in_bytes,
                "host_outputs": ma.host_output_size_in_bytes,
            }
            from .framework import monitor

            monitor.stat_max("device_mem_step_peak_bytes",
                             self._mem_analysis["peak"])
            # backfill the compile registry so a retrace audit shows
            # peak memory next to each program's signature
            _observe.annotate("train_step", peak_bytes=peak)
        return dict(self._mem_analysis)

    def compiled_text(self) -> str:
        """Optimized HLO of the compiled train step — the program the
        device runs; a Mosaic kernel shows in it as `tpu_custom_call`."""
        return compile_step(self._step_fn, self._step_protos).as_text()

    def attach_checkpoint_manager(self, manager):
        """Give the anomaly guard a rollback target: when
        FLAGS_anomaly_max_bad_steps consecutive steps go non-finite, the
        engine restores the newest readable checkpoint from this
        CheckpointManager (train_epoch_range attaches its own manager
        automatically)."""
        self._ckpt_manager = manager

    def _check_anomaly(self):
        """Step-boundary policy for the in-graph guard: ONE scalar read
        of the consecutive-bad-step buffer (the only host sync the guard
        adds — never per-op), then rollback once the budget is spent."""
        from .framework import flags as _flags, monitor as _monitor

        bad = int(self.state.buffers[ANOMALY_BAD_STEPS_KEY])
        if bad == 0:
            return
        _monitor.stat_add("anomaly_bad_steps")
        max_bad = _flags.flag("FLAGS_anomaly_max_bad_steps")
        if not max_bad or bad < max_bad:
            return  # skipped in-graph; give the run a chance to recover
        if self._ckpt_manager is None:
            from .framework.errors import PreconditionNotMetError

            raise PreconditionNotMetError(
                f"anomaly guard: {bad} consecutive non-finite steps and "
                "no checkpoint manager attached for rollback — call "
                "engine.attach_checkpoint_manager(...) or train via "
                "checkpoint.train_epoch_range")
        import warnings

        from . import observe as _observe
        from .distributed import checkpoint as _ckpt

        # rollback destroys the live (anomalous) state — preserve the
        # black box first so the post-mortem still has the bad steps
        _observe.flight.note("anomaly_rollback", bad_steps=bad,
                             engine_step=self.state.step)
        _observe.flight.dump("anomaly-rollback")
        self._ckpt_manager.wait_until_finished()
        step, _ = self._ckpt_manager.restore_with(
            lambda p: _ckpt.load_train_state(p, self))
        # the restored snapshot predates the anomaly: clear the counter
        # so the guard re-arms from zero
        self.state.buffers = dict(self.state.buffers)
        self.state.buffers[ANOMALY_BAD_STEPS_KEY] = \
            jnp.asarray(0, jnp.int32)
        _monitor.stat_add("anomaly_rollbacks")
        warnings.warn(
            f"anomaly guard: {bad} consecutive non-finite steps; rolled "
            f"back to checkpoint ckpt-{step} (engine step "
            f"{self.state.step})")

    def sync_to_layer(self):
        write_back(self.layer, self.state)
