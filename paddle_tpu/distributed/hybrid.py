"""Hybrid-parallel (dp x mp x pp x sharding) compiled training for
uniform-decoder transformers — the flagship path for the ladder's ERNIE
sharding and GPT-3 hybrid configs.

Ref parity: the composition the reference reaches with
HybridCommunicateGroup + PipelineLayer + 1F1B SectionWorker + megatron TP
layers + DygraphShardingOptimizer (python/paddle/distributed/fleet/
meta_parallel/*, paddle/fluid/framework/section_worker.cc). Here the whole
thing is ONE jitted XLA program:

- dp: global batch sharded over 'dp' (GSPMD inserts grad all-reduce)
- mp: megatron TP via Parameter.param_spec on qkv/mlp weights (GSPMD
  inserts the per-block all-reduces), vocab-sharded embedding + loss
- pp: transformer blocks stacked [L, ...] -> reshaped [S, L/S, ...],
  leading axis sharded over 'pp'; a scan+ppermute collective-permute
  pipeline (meta_parallel.pipeline_parallel.pipeline_spmd) runs the
  micro-batch schedule; jax AD produces the reverse pipeline
- sharding (ZeRO): optimizer moments sharded over the 'sharding' axis via
  out_shardings on the optimizer state tree
"""

from __future__ import annotations

import re
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..engine import _swap_state, _unwrap, compile_step, param_specs
from ..framework import random as _random
from .topology import DP_AXIS, MP_AXIS, PP_AXIS, SHARDING_AXIS
from .fleet.meta_parallel.pipeline_parallel import pipeline_spmd


def split_uniform_params(layer, block_prefix_re):
    """Split state into (stacked block params, other params).

    block_prefix_re: regex with one group for the layer index, e.g.
    r"gpt\\.layers\\.(\\d+)\\.(.*)"  -> stacked under key group(2).
    Returns (stacked: dict name -> [L, ...] array, rest: dict, num_layers).
    """
    pat = re.compile(block_prefix_re)
    per_layer = {}
    rest = {}
    for name, t in layer.state_dict().items():
        m = pat.match(name)
        if m:
            idx, sub = int(m.group(1)), m.group(2)
            per_layer.setdefault(sub, {})[idx] = t._value
        else:
            rest[name] = t._value
    num_layers = 0
    stacked = {}
    for sub, by_idx in per_layer.items():
        num_layers = max(num_layers, max(by_idx) + 1)
        stacked[sub] = jnp.stack([by_idx[i] for i in sorted(by_idx)])
    return stacked, rest, num_layers


def _block_spec_map(template_block):
    """param name (relative to one block) -> PartitionSpec or None."""
    return param_specs(template_block)


class HybridParallelEngine:
    """Compiled hybrid training for GPT/ERNIE-style models.

    The model must expose: `embeddings_forward(values, ids, key)`,
    uniform `layers` (indexable), and `head_forward(values, h, labels,
    key)` -> scalar loss. Adapters below provide these for the nlp models.
    """

    def __init__(self, model, criterion, optimizer, hcg, *,
                 block_regex, template_block, embed_fn, head_fn,
                 accumulate_steps=1, zero_stage=0, offload=False):
        self.model = model
        self.criterion = criterion
        self.optimizer = optimizer
        self.hcg = hcg
        self.mesh = hcg.get_mesh()
        self.accumulate_steps = accumulate_steps
        self.zero_stage = zero_stage
        self.offload = offload
        self.block_regex = block_regex
        self.template_block = template_block
        self.embed_fn = embed_fn
        self.head_fn = head_fn

        stacked, rest, L = split_uniform_params(model, block_regex)
        self.num_layers = L
        S = hcg.get_pipe_parallel_world_size()
        assert L % S == 0, f"num_layers {L} % pp {S} != 0"
        self.pp = S
        self.layers_per_stage = L // S
        # [L, ...] -> [S, L/S, ...]
        self.block_params = {
            k: v.reshape((S, L // S) + v.shape[1:])
            for k, v in stacked.items()}
        # trainable vs frozen split of the rest
        specs = param_specs(model)
        self.rest_params = {
            k: v for k, v in rest.items() if k in specs}
        self.rest_buffers = {
            k: v for k, v in rest.items() if k not in specs}
        self._zero_warned = set()
        self.opt_state = {
            "blocks": {k: self.optimizer._init_state(v)
                       for k, v in self.block_params.items()},
            "rest": {k: self.optimizer._init_state(v)
                     for k, v in self.rest_params.items()},
        }
        self._step_fn = None
        self._offload_sh = None
        self._step_protos = None
        self._mem_analysis = None
        self._last_batch = None
        self._shardings = sh = self._build_shardings(specs)
        # place the state where it will live NOW: left as built — whole,
        # on the first device — it would sit there beside the sharded
        # copies the first step makes (gpt2-medium: 5.7 GB extra on
        # chip 0 while the other chips hold their 2.2 GB share)
        self.block_params = jax.device_put(self.block_params, sh["blocks"])
        self.rest_params = jax.device_put(self.rest_params, sh["rest"])
        self.rest_buffers = jax.device_put(self.rest_buffers,
                                           sh["buffers"])
        self.opt_state = jax.device_put(self.opt_state, sh["opt"])

    # -- sharding specs ------------------------------------------------------
    def _block_leaf_spec(self, name, arr):
        bspecs = _block_spec_map(self.template_block)
        inner = bspecs.get(name)
        if inner is None:
            inner = P(*([None] * (arr.ndim - 2)))
        return P(PP_AXIS, None, *tuple(inner))

    def _opt_leaf_spec(self, pspec, arr, name=""):
        # moments follow the param sharding; scalars replicate
        if arr.ndim == 0:
            return P()
        if self.zero_stage >= 1 and self.mesh.shape.get(SHARDING_AXIS,
                                                        1) > 1:
            # shard the first non-pp dim over 'sharding' when divisible
            spec = list(pspec) if pspec is not None else \
                [None] * arr.ndim
            spec += [None] * (arr.ndim - len(spec))
            placed = False
            for i, s in enumerate(spec):
                if s is None and arr.shape[i] % \
                        self.mesh.shape[SHARDING_AXIS] == 0 and \
                        arr.shape[i] > 1:
                    spec[i] = SHARDING_AXIS
                    placed = True
                    break
            if not placed and all(s is None for s in spec) \
                    and arr.size >= self.mesh.shape[SHARDING_AXIS] \
                    and name not in self._zero_warned:
                # only a truly replicated state warrants the warning —
                # pp/mp-sharded leaves just have no free dim left; once
                # per param, across state leaves and grad retraces
                self._zero_warned.add(name)
                import warnings

                warnings.warn(
                    f"ZeRO: state/gradient for '{name}' (shape "
                    f"{arr.shape}) has no dim divisible by sharding "
                    f"degree {self.mesh.shape[SHARDING_AXIS]}; "
                    "replicating", stacklevel=3)
            return P(*spec)
        if pspec is not None:
            spec = list(pspec) + [None] * (arr.ndim - len(pspec))
            return P(*spec)
        return P(*([None] * arr.ndim))

    def _build_shardings(self, specs):
        mesh = self.mesh

        def ns(spec):
            return NamedSharding(mesh, spec)

        def param_spec_of(k, v, base):
            # ZeRO-3: shard the parameters themselves on a free divisible
            # dim (XLA all-gathers where full values are consumed)
            if self.zero_stage >= 3:
                return self._opt_leaf_spec(
                    tuple(base) if base is not None else None, v, name=k)
            return base if base is not None else P()

        block_sh = {
            k: ns(param_spec_of(k, v, self._block_leaf_spec(k, v)))
            for k, v in self.block_params.items()}
        rest_sh = {}
        for k, v in self.rest_params.items():
            rest_sh[k] = ns(param_spec_of(k, v, specs.get(k)))
        buf_sh = {k: ns(P()) for k in self.rest_buffers}
        opt_block_sh = {
            k: jax.tree.map(
                lambda a, kk=k: ns(self._opt_leaf_spec(
                    tuple(self._block_leaf_spec(kk,
                          self.block_params[kk])), a, name=kk)), st)
            for k, st in self.opt_state["blocks"].items()}
        opt_rest_sh = {
            k: jax.tree.map(
                lambda a, kk=k: ns(self._opt_leaf_spec(
                    specs.get(kk), a, name=kk)), st)
            for k, st in self.opt_state["rest"].items()}
        data_sh = ns(P(DP_AXIS))  # tokens [B, s]: batch dim over dp
        return dict(blocks=block_sh, rest=rest_sh, buffers=buf_sh,
                    opt=dict(blocks=opt_block_sh, rest=opt_rest_sh),
                    data=data_sh, repl=ns(P()))

    # -- the compiled step ---------------------------------------------------
    def _build(self):
        M = self.accumulate_steps
        S = self.pp
        Lps = self.layers_per_stage
        template = self.template_block
        embed_fn, head_fn = self.embed_fn, self.head_fn
        mesh = self.mesh
        opt = self.optimizer
        from ..incubate.asp import masks_for as _masks_for, \
            stacked_masks_for as _stacked_masks_for

        # stacked block params re-mask via [S, L/S, ...] stacked masks;
        # everything else (embeddings/head) by state-dict name
        _asp_block_masks, _asp_covered = _stacked_masks_for(
            self.model, self.block_regex, self.num_layers, S)
        _asp_rest_masks = {k: v for k, v in _masks_for(self.model).items()
                           if k not in _asp_covered}

        from ..core.config import no_tape
        from ..ops import overlap as _overlap
        from .fleet.utils.recompute import remat_wrapper

        # FLAGS_remat_policy: 'auto' keeps the scan's save-residuals
        # shape; full/dots_saveable rematerialize each block in backward
        remat = remat_wrapper(default="none")

        def run_block(h, kk, layer_params):
            with _random.rng_scope(kk):
                with no_tape(), _swap_state(template, layer_params):
                    out = template(Tensor(h))
            return out._value if isinstance(out, Tensor) else out

        def stage_fn(stage_params, x):
            # stage_params leaves: [Lps, ...]; scan the blocks
            def body(h, inp):
                layer_params, idx = inp
                # fold-in OUTSIDE the remat wrapper: the trace-level RNG
                # stream is consumed exactly once per block regardless
                # of policy (backward replays get the key as an arg)
                kk = jax.random.fold_in(_random.next_key(), idx)
                return remat(run_block)(h, kk, layer_params), None

            h, _ = jax.lax.scan(body, x,
                                (stage_params, jnp.arange(Lps)))
            return h

        # pp==1 needs no pipeline: the single stage runs on the merged
        # micro axis (exact — one stage, no bubbles), which also keeps
        # the step a plain GSPMD trace the overlap ring shard_map can
        # nest in
        pipeline = pipeline_spmd(stage_fn, mesh, num_stages=S,
                                 num_micro=M) if S > 1 else None

        # per-param decay/lr-mult constants (mirrors eager _preprocess);
        # block params take their meta from the template block's Parameter
        block_metas = opt.param_metas_for(self.block_params,
                                          template.state_dict())
        rest_metas = opt.param_metas_for(self.rest_params,
                                         self.model.state_dict())

        # mp collective-matmul overlap: active only when FLAGS_mp_overlap
        # (or the FORCE env) is on AND the mesh is pure dp x mp — the
        # region is a trace-time no-op otherwise
        seq_parallel = bool(getattr(template, "sequence_parallel", False))

        def loss_of(block_params, rest_params, buffers, batch, key):
            tokens, labels = batch
            with _random.rng_scope(key), _overlap.region(
                    mesh, sequence_parallel=seq_parallel):
                values = {**buffers, **rest_params}
                x = embed_fn(self.model, values, tokens)  # [B, s, h]
                b, s, h = x.shape
                if pipeline is not None:
                    x = x.reshape((M, b // M, s, h))
                    x = pipeline(block_params, x)
                    x = x.reshape((b, s, h))
                else:
                    x = stage_fn(jax.tree.map(lambda v: v[0],
                                              block_params), x)
                loss = head_fn(self.model, values, x, labels)
                return loss.astype(jnp.float32)

        # ZeRO-2: gradients constrained to the moment shardings — GSPMD
        # lowers the grad reductions into reduce-scatter over 'sharding'
        grad_constraint = None
        if self.zero_stage >= 2 and mesh.shape.get(SHARDING_AXIS, 1) > 1:
            specs_all = param_specs(self.model)

            def grad_constraint(gb, gr):
                gb = {k: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, self._opt_leaf_spec(
                        tuple(self._block_leaf_spec(k, g)), g, name=k)))
                    for k, g in gb.items()}
                gr = {k: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, self._opt_leaf_spec(
                        specs_all.get(k), g, name=k)))
                    for k, g in gr.items()}
                return gb, gr

        def step_fn(block_params, rest_params, buffers, opt_state, batch,
                    lr, key):
            from ..ops.fused_ops import gspmd_tracing

            with gspmd_tracing(mesh):  # attention shard_maps per shard
                return _step_impl(block_params, rest_params, buffers,
                                  opt_state, batch, lr, key)

        def _step_impl(block_params, rest_params, buffers, opt_state,
                       batch, lr, key):
            from .. import observe as _observe

            _observe.record_compile(
                "hybrid_step", signature=_observe.signature_of(batch))
            loss, (gb, gr) = jax.value_and_grad(
                loss_of, argnums=(0, 1))(block_params, rest_params,
                                         buffers, batch, key)
            if grad_constraint is not None:
                gb, gr = grad_constraint(gb, gr)
            gb = opt.decay_gradients_tree(block_params, gb, block_metas)
            gr = opt.decay_gradients_tree(rest_params, gr, rest_metas)
            gc = getattr(opt, "_grad_clip", None)
            if gc is not None:
                gb, gr = gc._clip_fn((gb, gr))
            nb, ob = opt.apply_gradients_tree(block_params, gb,
                                              opt_state["blocks"], lr,
                                              metas=block_metas)
            nr, orr = opt.apply_gradients_tree(rest_params, gr,
                                               opt_state["rest"], lr,
                                               metas=rest_metas)
            if _asp_block_masks or _asp_rest_masks:
                from ..incubate.asp import apply_masks_tree

                nb = apply_masks_tree(self.model, nb,
                                      engine_name="HybridParallelEngine",
                                      masks=_asp_block_masks)
                nr = apply_masks_tree(self.model, nr,
                                      engine_name="HybridParallelEngine",
                                      masks=_asp_rest_masks)
            # buffers pass through as an output so they can be donated:
            # every engine-state leaf is arg<->output aliased
            return loss, nb, nr, buffers, {"blocks": ob, "rest": orr}

        sh = self._shardings
        self._step_fn = jax.jit(
            step_fn,
            in_shardings=(sh["blocks"], sh["rest"], sh["buffers"],
                          sh["opt"], (sh["data"], sh["data"]),
                          sh["repl"], sh["repl"]),
            out_shardings=(sh["repl"], sh["blocks"], sh["rest"],
                           sh["buffers"], sh["opt"]),
            donate_argnums=(0, 1, 2, 3))
        # raw (unjitted) step for bench harnesses that re-jit it inside
        # a scan (bench_attrib._timed_scan_ms)
        self._step_fn._raw_step_fn = step_fn

    def train_batch(self, tokens, labels):
        if self._step_fn is None:
            self._build()
            if self.offload:
                # opt state rests in pinned host memory between steps
                # (ref sharding/offload_helper.py); initial state stays
                # on device — the first step would only round-trip it
                from ..engine import host_offload_shardings

                self._offload_sh = host_offload_shardings(
                    self.mesh, self._shardings["opt"])
        t = tokens._value if isinstance(tokens, Tensor) else \
            jnp.asarray(tokens)
        l = labels._value if isinstance(labels, Tensor) else \
            jnp.asarray(labels)
        self._last_batch = (t, l)
        key = _random.default_generator.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        opt_state = self.opt_state
        if self._offload_sh is not None:
            opt_state = jax.device_put(opt_state, self._offload_sh[0])
        if self._step_protos is None:
            self._step_protos = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (self.block_params, self.rest_params, self.rest_buffers,
                 opt_state, (t, l), lr, key))
            self._mem_analysis = None
        loss, self.block_params, self.rest_params, self.rest_buffers, \
            new_opt = self._step_fn(self.block_params, self.rest_params,
                                    self.rest_buffers, opt_state, (t, l),
                                    lr, key)
        if self._offload_sh is not None:
            new_opt = jax.device_put(new_opt, self._offload_sh[1])
        self.opt_state = new_opt
        return Tensor(loss)

    # -- step introspection --------------------------------------------------
    def schedule(self):
        """The ordered phase list of ONE compiled hybrid step — embed,
        the N transformer blocks, head, gradient reduction, optimizer —
        each with its per-phase sharding specs. Pure metadata built from
        the engine's sharding rules (no tracing, no device work), stable
        across rebuilds of the same configuration: the introspection
        hook the sharded serving engine starts from (ROADMAP item 1)."""
        sh = self._shardings
        block_specs = OrderedDict(
            (k, sh["blocks"][k].spec) for k in sorted(sh["blocks"]))
        embed = OrderedDict()
        head = OrderedDict()
        for k in sorted(self.rest_params):
            target = embed if "embedding" in k else head
            target[k] = sh["rest"][k].spec
        phases = [dict(name="embed", kind="embed", params=embed)]
        for i in range(self.num_layers):
            phases.append(dict(
                name=f"block{i}", kind="block",
                stage=i // self.layers_per_stage, params=block_specs))
        phases.append(dict(name="head", kind="head", params=head))
        reduce_axes = [DP_AXIS]
        if self.zero_stage >= 2 and \
                self.mesh.shape.get(SHARDING_AXIS, 1) > 1:
            reduce_axes.append(SHARDING_AXIS)
        phases.append(dict(name="grad-reduce", kind="collective",
                           axes=tuple(reduce_axes), params=OrderedDict()))
        opt_specs = OrderedDict()
        for group in ("blocks", "rest"):
            for k in sorted(sh["opt"][group]):
                opt_specs[f"{group}.{k}"] = jax.tree.map(
                    lambda s: s.spec, sh["opt"][group][k])
        phases.append(dict(name="opt", kind="opt", params=opt_specs))
        return phases

    def memory_analysis(self) -> dict:
        """MEASURED per-step device memory of the compiled hybrid step
        (same keys as Engine.memory_analysis; `alias` is the donated
        arg<->output reuse the donation audit asserts on)."""
        if self._mem_analysis is None:
            from .. import observe as _observe

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
            _observe.annotate("hybrid_step", peak_bytes=peak)
        return dict(self._mem_analysis)

    def compiled_text(self) -> str:
        """Optimized, partitioned HLO of the compiled hybrid step (see
        Engine.compiled_text)."""
        return compile_step(self._step_fn, self._step_protos).as_text()

    def attribute_step(self, logdir=None, steps=1, top=10):
        """Capture an xplane trace of `steps` replays of the LAST
        train_batch shape and classify device time into the observe
        buckets. State is donated, so these are REAL steps."""
        if self._last_batch is None:
            raise RuntimeError("run train_batch() once first")
        import tempfile

        from .. import observe as _observe, profiler as _profiler

        if logdir is None:
            logdir = tempfile.mkdtemp(prefix="paddle-attrib-")
        tokens, labels = self._last_batch
        _profiler.start_trace(logdir)
        try:
            for _ in range(steps):
                self.train_batch(tokens, labels)
            jax.block_until_ready(self.rest_params)
        finally:
            _profiler.stop_trace()
        return _observe.attribute(logdir, top=top)

    def overlap_report(self, logdir=None, steps=1):
        """Capture a trace of `steps` real steps and pair the collective
        bucket against concurrently-resident matmul/attention time:
        returns observe.overlap_report's dict, whose headline
        `exposed_collective_frac` is the share of device time spent in
        collectives with NO compute in flight."""
        if self._last_batch is None:
            raise RuntimeError("run train_batch() once first")
        import tempfile

        from .. import observe as _observe, profiler as _profiler

        if logdir is None:
            logdir = tempfile.mkdtemp(prefix="paddle-overlap-")
        tokens, labels = self._last_batch
        _profiler.start_trace(logdir)
        try:
            for _ in range(steps):
                self.train_batch(tokens, labels)
            jax.block_until_ready(self.rest_params)
        finally:
            _profiler.stop_trace()
        return _observe.overlap_report(logdir)


# -- adapters for the nlp model family --------------------------------------


def values_sub(values, prefix):
    return {k[len(prefix):]: v for k, v in values.items()
            if k.startswith(prefix)}


def make_gpt_hybrid_engine(model, criterion, optimizer, hcg, *,
                           accumulate_steps=1, zero_stage=0,
                           offload=False):
    from ..engine import functional_call

    def embed_fn(m, values, tokens):
        return functional_call(m.gpt.embeddings,
                               values_sub(values, "gpt.embeddings."),
                               Tensor(tokens))

    def head_fn(m, values, h, labels):
        fn_values = values_sub(values, "gpt.final_norm.")
        h = functional_call(m.gpt.final_norm, fn_values, Tensor(h))
        # tied embedding logits: weight lives in the rest params
        w = values["gpt.embeddings.word_embeddings.weight"]
        from ..ops import lowp as _lowp

        if _lowp.mode() != "off":
            # dynamic scales: the hybrid per-block scan has no
            # delayed-scaling region (the ScaleState carry rides the
            # plain Engine only)
            hv = h._value if isinstance(h, Tensor) else h
            logits = _lowp.scaled_matmul(
                hv, w.T, qdtype=_lowp.mode(),
                out_dtype=jnp.result_type(hv, w))
        else:
            logits = jnp.matmul(h, w.T)
        loss = criterion(Tensor(logits), Tensor(labels))
        return loss._value if isinstance(loss, Tensor) else loss

    return HybridParallelEngine(
        model, criterion, optimizer, hcg,
        block_regex=r"gpt\.layers\.(\d+)\.(.*)",
        template_block=model.gpt.layers[0],
        embed_fn=embed_fn, head_fn=head_fn,
        accumulate_steps=accumulate_steps, zero_stage=zero_stage,
        offload=offload)


def make_ernie_hybrid_engine(model, criterion, optimizer, hcg, *,
                             accumulate_steps=1, zero_stage=0,
                             offload=False):
    """ERNIE pretraining (MLM-only in the hybrid path: NSP head needs the
    pooler over the full sequence, kept in the head_fn)."""
    from ..engine import functional_call

    def embed_fn(m, values, tokens):
        return functional_call(m.ernie.embeddings,
                               values_sub(values, "ernie.embeddings."),
                               Tensor(tokens))

    def head_fn(m, values, h, labels):
        pooled = functional_call(m.ernie.pooler,
                                 values_sub(values, "ernie.pooler."),
                                 Tensor(h))
        cls_vals = values_sub(values, "cls.")
        # the tied decoder weight dedups under the embedding's name in the
        # model-level state dict; re-route it to cls's local registry name
        cls_vals["_tied"] = values[
            "ernie.embeddings.word_embeddings.weight"]
        scores, rel = functional_call(
            m.cls, cls_vals, Tensor(h), Tensor(pooled))
        loss = criterion(Tensor(scores), Tensor(rel), Tensor(labels))
        return loss._value if isinstance(loss, Tensor) else loss

    return HybridParallelEngine(
        model, criterion, optimizer, hcg,
        block_regex=r"ernie\.encoder\.(\d+)\.(.*)",
        template_block=model.ernie.encoder[0],
        embed_fn=embed_fn, head_fn=head_fn,
        accumulate_steps=accumulate_steps, zero_stage=zero_stage,
        offload=offload)
