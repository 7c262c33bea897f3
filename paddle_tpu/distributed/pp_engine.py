"""Pipeline engine: compiles PipelineLayer training into one XLA program.

Ref parity: PipelineTrainer/SectionWorker
(paddle/fluid/framework/pipeline_trainer.cc:30-52,
section_worker.cc:104-180) — their F-then-B / 1F1B interpreting loop
becomes a `lax.scan` over micro-batches inside `jit`.

Three schedules:
- "spmd" (stage-uniform bodies): scan + ppermute collective-permute
  pipeline over the 'pp' mesh axis (see meta_parallel.pipeline_parallel.
  pipeline_spmd); jax AD yields the reverse pipeline. Used by the flagship
  transformer path.
- "hetero" (general PipelineLayer, pp > 1): the SAME scan+ppermute ring
  schedule over genuinely different per-stage programs — per-stage
  parameter pytrees packed into [S, Pmax] rows sharded over 'pp'
  (pack_stage_rows: per-device memory = the largest stage, true
  placement), stage bodies under lax.switch, distinct
  input/activation/output ring shapes (pipeline_spmd_hetero).  Shared
  (tied) layers stay replicated and jax AD sums their grads across use
  sites — the reference's shared-weight allreduce.
- "accum" (fallback): micro-batch gradient-accumulation scan over the
  full layer under GSPMD — NO cross-stage placement or overlap.  Used
  only when the hetero contract cannot be met (non-array stage
  boundary, mismatched inter-stage shapes) and WARNS loudly.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..framework import random as _random
from ..engine import functional_call, param_values, buffer_values


class _HeteroUnsupported(Exception):
    pass


class PipelineEngine:
    def __init__(self, pipeline_layer, optimizer, hcg, *,
                 micro_batch_size=1, accumulate_steps=1, loss_fn=None):
        self.layer = pipeline_layer
        self.optimizer = optimizer
        self.hcg = hcg
        self.micro_batch_size = micro_batch_size
        self.accumulate_steps = accumulate_steps
        self.loss_fn = loss_fn or getattr(pipeline_layer, "_loss_fn", None)
        self.params = dict(param_values(pipeline_layer))
        self.buffers = dict(buffer_values(pipeline_layer))
        # allocated lazily: the hetero schedule keeps its own packed
        # optimizer state and never reads this per-param one
        self.opt_state = None
        self._step_fn = None
        self.schedule = None

    def _build(self):
        pp = self.hcg.get_pipe_parallel_world_size() \
            if self.hcg is not None else 1
        if pp > 1:
            try:
                self._build_hetero()
                self.schedule = "hetero"
                return
            except _HeteroUnsupported as e:
                warnings.warn(
                    "PipelineEngine: heterogeneous ring schedule "
                    f"unavailable ({e}); FALLING BACK to gradient "
                    "accumulation — micro-batches will NOT overlap "
                    "across stages (no pipelining)")
        self.schedule = "accum"
        self._build_accum()

    # -- hetero: ring schedule over per-stage programs ---------------------

    def _build_hetero(self):
        from .fleet.meta_parallel.pipeline_parallel import (
            pack_stage_rows, pipeline_spmd_hetero,
        )
        from .fleet.meta_parallel.pp_layers import (
            PipelineLayer, _SharedRef,
        )
        from ..incubate.asp import masks_for

        layer = self.layer
        if not isinstance(layer, PipelineLayer):
            raise _HeteroUnsupported("layer is not a PipelineLayer")
        S = layer._num_stages
        pp = self.hcg.get_pipe_parallel_world_size()
        if S != pp:
            raise _HeteroUnsupported(
                f"num_stages {S} != pp degree {pp}")
        if self.loss_fn is None:
            raise _HeteroUnsupported("no loss_fn")
        if masks_for(layer):
            raise _HeteroUnsupported("ASP masks not supported here")
        # packing stage params into one [S, Pmax] row is only sound for
        # purely ELEMENTWISE update rules — trust-ratio optimizers
        # (Lamb/LARS) compute per-PARAM norms, and per-leaf norm clip
        # would clip the concatenation as one tensor
        if type(self.optimizer).__name__ in ("Lamb", "LarsMomentum"):
            raise _HeteroUnsupported(
                f"{type(self.optimizer).__name__} computes per-parameter "
                "trust ratios; packed stage rows would merge them")
        gc = getattr(self.optimizer, "_grad_clip", None)
        if gc is not None and type(gc).__name__ == "ClipGradByNorm":
            raise _HeteroUnsupported(
                "per-leaf ClipGradByNorm cannot act on packed stage rows")
        mesh = self.hcg.get_mesh()
        M = self.accumulate_steps
        opt = self.optimizer
        loss_fn = self.loss_fn
        subs = list(layer.run_function)
        shared_ids = {id(sl) for sl in layer._shared.values()}
        base_index = {id(sl): i for i, sl in enumerate(subs)
                      if id(sl) in shared_ids}

        # group trainable params: per-stage trees (placed) vs shared
        # (tied across stages -> replicated, grads summed by AD)
        stage_trees = [dict() for _ in range(S)]
        shared0 = {}
        for i, sub in enumerate(subs):
            if isinstance(sub, _SharedRef):
                continue
            prefix = f"run_function.{i}."
            dst = shared0 if id(sub) in shared_ids \
                else stage_trees[layer.stage_of_layer(i)]
            for name in sub.state_dict():
                full = prefix + name
                if full in self.params:
                    dst[full] = self.params[full]

        buffers = dict(self.buffers)

        def call_sub(i, sub, lookup, sp, bufs, x):
            if isinstance(sub, _SharedRef):
                base = sub._base[0]
                bi = base_index[id(base)]
                vals = self._sub_values(base, f"run_function.{bi}.",
                                        sp, sp, bufs)
                if sub._forward_func is not None:
                    from ..core.config import no_tape
                    from ..engine import _swap_state, _unwrap

                    with no_tape(), _swap_state(base, vals):
                        return _unwrap(sub._forward_func(base, Tensor(x)))
                return functional_call(base, vals, x)
            prefix = f"run_function.{i}."
            vals = self._sub_values(sub, prefix, lookup, sp, bufs)
            return functional_call(sub, vals, x)

        bounds = layer.segment_parts

        def make_stage_fn(s):
            lo, hi = bounds[s], bounds[s + 1]
            last = s == S - 1

            def fn(local, shared, x, *extra):
                sp, bufs = shared
                t = x
                for i in range(lo, hi):
                    t = call_sub(i, subs[i], local, sp, bufs, t)
                if last:
                    loss = loss_fn(
                        Tensor(t) if not isinstance(t, Tensor) else t,
                        Tensor(extra[0]))
                    lv = loss._value if isinstance(loss, Tensor) else loss
                    return jnp.asarray(lv, jnp.float32)
                return t._value if isinstance(t, Tensor) else t

            return fn

        stage_fns = [make_stage_fn(s) for s in range(S)]

        # probe boundary shapes: every inter-stage activation must be ONE
        # array of one shape (the ring's layout)
        x_proto, y_proto = self._mb_protos
        shared_arg = (shared0, buffers)
        act = None
        try:
            for s in range(S):
                args = [stage_trees[s], shared_arg,
                        x_proto if s == 0 else act]
                if s == S - 1:
                    args.append(y_proto)
                out = jax.eval_shape(stage_fns[s], *args)
                if s < S - 1:
                    if not isinstance(out, jax.ShapeDtypeStruct):
                        raise _HeteroUnsupported(
                            f"stage {s} boundary is not a single array")
                    if act is not None and (out.shape, out.dtype) != (
                            act.shape, act.dtype):
                        raise _HeteroUnsupported(
                            f"inter-stage shapes differ: {act} vs {out}")
                    act = out
                elif not (isinstance(out, jax.ShapeDtypeStruct)
                          and out.shape == ()):
                    raise _HeteroUnsupported(
                        "loss_fn must reduce to a scalar per micro-batch "
                        f"(got {out})")
        except _HeteroUnsupported:
            raise
        except Exception as e:  # noqa: BLE001 - probing failed
            raise _HeteroUnsupported(f"stage probing failed: {e}")
        out_proto = jax.ShapeDtypeStruct((), jnp.float32)

        rows0, unpack, pack = pack_stage_rows(stage_trees)
        self._stage_trees = stage_trees
        self._pack = pack
        self._unpack = unpack
        self._run = run = pipeline_spmd_hetero(
            stage_fns, mesh, num_stages=S, num_micro=M, unpack=unpack,
            act_proto=act, out_proto=out_proto, has_extra=True)

        # weight-decay masks over the packed rows (decay_gradients_tree
        # semantics: L2 adds coeff*p, L1 adds coeff*sign(p))
        metas_all = opt.param_metas_for(self.params,
                                        layer.state_dict()) or {}
        for tree in stage_trees:
            for k in tree:
                m = metas_all.get(k) or {}
                if (m.get("lr_mult", 1.0) != 1.0
                        or "decoupled_coeff" in m
                        or "hyper_overrides" in m):
                    raise _HeteroUnsupported(
                        f"per-param optimizer overrides on {k} cannot "
                        "ride a packed stage row")
        coeff_trees, l1_trees = [], []
        any_decay = False
        for tree in stage_trees:
            ct, lt = {}, {}
            for k, v in tree.items():
                m = metas_all.get(k) or {}
                c = float(m.get("coeff") or 0.0)
                any_decay = any_decay or c != 0.0
                ct[k] = jnp.full(v.shape, c, jnp.float32)
                lt[k] = jnp.full(v.shape, 1.0 if m.get("l1") else 0.0,
                                 jnp.float32)
            coeff_trees.append(ct)
            l1_trees.append(lt)
        wd_rows = pack(coeff_trees) if any_decay else None
        l1_rows = pack(l1_trees) if any_decay else None
        shared_metas = {k: metas_all.get(k) for k in shared0}

        from jax.sharding import NamedSharding, PartitionSpec as P

        row_sh = NamedSharding(mesh, P("pp"))
        repl = NamedSharding(mesh, P())
        self._rows = jax.device_put(rows0, row_sh)
        self._shared = {k: jax.device_put(v, repl)
                        for k, v in shared0.items()}
        self._hopt = {
            "rows": opt._init_state(rows0),
            **{k: opt._init_state(v) for k, v in shared0.items()},
        }

        def step_fn(rows, shared, opt_state, bufs, x, y, lr, key):
            from .. import observe as _observe
            from ..ops.fused_ops import gspmd_tracing

            _observe.record_compile(
                "pp.train_step", signature=_observe.signature_of(x, y))
            with gspmd_tracing(mesh):
                def loss_of(rows, shared):
                    losses = run(rows, (shared, bufs), x, extra=y,
                                 key=key)
                    return jnp.mean(losses)

                loss, (g_rows, g_shared) = jax.value_and_grad(
                    loss_of, argnums=(0, 1))(rows, shared)
                if wd_rows is not None:
                    g_rows = g_rows + wd_rows * jnp.where(
                        l1_rows > 0, jnp.sign(rows), rows)
                g_shared = opt.decay_gradients_tree(
                    shared, g_shared, shared_metas)
                gc = getattr(opt, "_grad_clip", None)
                if gc is not None:
                    g_rows, g_shared = gc._clip_fn((g_rows, g_shared))
                params_tree = {"__pp_rows__": rows, **shared}
                grads_tree = {"__pp_rows__": g_rows, **g_shared}
                metas_tree = {"__pp_rows__": None, **shared_metas}
                new_p, new_o = opt.apply_gradients_tree(
                    params_tree, grads_tree, opt_state, lr,
                    metas=metas_tree)
                new_rows = new_p.pop("__pp_rows__")
                return loss, new_rows, new_p, new_o

        # opt state keys follow the params_tree keys inside step_fn;
        # row-shaped leaves shard over 'pp', scalars/others replicate
        self._hopt = {"__pp_rows__": self._hopt.pop("rows"),
                      **self._hopt}

        def _opt_leaf_sh(leaf, rowlike):
            return row_sh if (rowlike
                              and getattr(leaf, "shape", None)
                              == rows0.shape) else repl

        opt_sh = {
            k: jax.tree.map(
                lambda a, rl=(k == "__pp_rows__"): _opt_leaf_sh(a, rl), v)
            for k, v in self._hopt.items()
        }
        shared_sh = {k: repl for k in shared0}
        self._step_fn = jax.jit(
            step_fn,
            in_shardings=(row_sh, shared_sh, opt_sh,
                          None, None, None, None, None),
            out_shardings=(None, row_sh, shared_sh, opt_sh),
            donate_argnums=(0, 1, 2))

    def _sub_values(self, sub, prefix, lookup, sp, bufs):
        vals = {}
        for name in sub.state_dict():
            full = prefix + name
            if full in lookup:
                vals[name] = lookup[full]
            elif full in sp:
                vals[name] = sp[full]
            elif full in bufs:
                vals[name] = bufs[full]
        return vals

    # -- accum: gradient-accumulation fallback -----------------------------

    def _build_accum(self):
        layer = self.layer
        loss_fn = self.loss_fn
        opt = self.optimizer
        M = self.accumulate_steps
        if self.opt_state is None:
            self.opt_state = {k: opt._init_state(v)
                              for k, v in self.params.items()}
        from ..incubate.asp import masks_for

        _asp_masks = masks_for(layer)

        def micro_loss(params, buffers, x_mb, y_mb, key):
            with _random.rng_scope(key):
                values = {**buffers, **params}
                out = functional_call(layer, values, Tensor(x_mb))
                loss = loss_fn(Tensor(out) if not isinstance(out, Tensor)
                               else out, Tensor(y_mb))
                return (loss._value if isinstance(loss, Tensor)
                        else loss).astype(jnp.float32)

        grad_fn = jax.value_and_grad(micro_loss)

        metas = opt.param_metas_for(self.params, layer.state_dict())

        def step_fn(params, opt_state, buffers, x, y, lr, key):
            from .. import observe as _observe

            _observe.record_compile(
                "pp.train_step", signature=_observe.signature_of(x, y))
            # x, y: [M, micro_batch, ...]
            def accum(carry, mb):
                gsum, lsum, i = carry
                xm, ym = mb
                k = jax.random.fold_in(key, i)
                loss, g = grad_fn(params, buffers, xm, ym, k)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + loss, i + 1), None

            zero = jax.tree.map(jnp.zeros_like, params)
            (gsum, lsum, _), _ = jax.lax.scan(
                accum, (zero, jnp.zeros((), jnp.float32), 0), (x, y))
            grads = jax.tree.map(lambda g: g / M, gsum)
            grads = opt.decay_gradients_tree(params, grads, metas)
            gc = getattr(opt, "_grad_clip", None)
            if gc is not None:
                grads = gc._clip_fn(grads)
            new_params, new_opt = opt.apply_gradients_tree(
                params, grads, opt_state, lr, metas=metas)
            if _asp_masks:
                from ..incubate.asp import apply_masks_tree

                new_params = apply_masks_tree(
                    layer, new_params, engine_name="PipelineEngine")
            return lsum / M, new_params, new_opt

        self._step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    def _microbatch(self, arr):
        arr = arr._value if isinstance(arr, Tensor) else jnp.asarray(arr)
        M = self.accumulate_steps
        b = arr.shape[0]
        assert b % M == 0, (
            f"global batch {b} not divisible by accumulate_steps {M}")
        return arr.reshape((M, b // M) + arr.shape[1:])

    def train_batch(self, inputs, labels):
        from .. import observe as _observe

        with _observe.phase("host-prep"):
            x = self._microbatch(inputs)
            y = self._microbatch(labels)
            compiling = self._step_fn is None
            if compiling:
                self._mb_protos = (
                    jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                    jax.ShapeDtypeStruct(y.shape[1:], y.dtype))
                self._build()
            key = _random.default_generator.next_key()
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        with _observe.phase("compile" if compiling else "device-step"):
            if self.schedule == "hetero":
                loss, self._rows, self._shared, self._hopt = \
                    self._step_fn(
                        self._rows, self._shared, self._hopt,
                        self.buffers, x, y, lr, key)
            else:
                loss, self.params, self.opt_state = self._step_fn(
                    self.params, self.opt_state, self.buffers,
                    x, y, lr, key)
        return Tensor(loss)

    def sync_to_layer(self):
        sd = self.layer.state_dict()
        if self.schedule == "hetero":
            for s, tree in enumerate(self._stage_trees):
                vals = self._unpack(s, self._rows[s])
                for k, v in vals.items():
                    if k in sd:
                        sd[k]._value = v
            for k, v in self._shared.items():
                if k in sd:
                    sd[k]._value = v
            return
        for k, v in self.params.items():
            if k in sd:
                sd[k]._value = v
