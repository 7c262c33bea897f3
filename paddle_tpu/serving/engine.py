"""Continuous-batching decode engine over a block-paged KV cache.

Orca-style iteration-level scheduling (PAPERS.md: continuous batching)
mapped onto XLA's compile-per-shape reality, with vLLM-style paged KV
allocation and SGLang-style prefix sharing:

- The MODEL states what a block holds (`model.cache_layout()`, a
  `paging.CacheLayout`): dense attention K and V pools of
  ``[num_blocks, block_size, nh, hd]`` a layer (token-major, the order
  the step writes and reads them in), latent attention one pool of
  ``[num_blocks, block_size, width]``. The model also owns the scatter
  through the block table and the attention over it
  (`model.paged_forward`); the engine allocates, donates, copies and
  recovers "a layer's pools" whatever their number and rank, and keeps
  a per-slot block table ``[max_slots, blocks_per_slot]``. A request
  holds only the blocks its actual length needs, so pool HBM caps
  *total tokens in flight*, not
  ``max_slots * max_seq`` — short requests no longer pay for long ones
  and concurrency scales with the pool, not the worst case.
- A layout may declare a second kind of array (`CacheLayout.state`):
  what one SLOT keeps of each state-holding layer whatever its context
  (a recurrent layer's state, a filter's tail). The engine allocates
  each as ``[max_slots + snapshot_entries + 1, *shape]``: a row a slot,
  which the step is handed and hands back with the pools (`_held`); a
  row an entry of the SNAPSHOT pool; and a last row that stays zero.
  One compiled row copy (`serving_snapshot`, traced once, beside the
  CoW copy) does all that moves a state: a slot admitted without a hit
  is reset from the zero row (`state_resets`; what a freed slot left
  never reaches its next occupant); whenever a live slot's position
  lands exactly on a block boundary at the end of a step its state is
  copied into the request's one working entry, over the older copy
  (`_take_snapshots`, no host read-back); at `_evict` the prefix cache
  records that entry on the block that ends at its depth, and a later
  request's match is cut at the deepest snapshot on its chain
  (`PrefixCache.match_snapshot`: K and V rows of a prefix are shared
  block by block, the state at a boundary cannot be rebuilt from
  them), the slot's state restored from it (`state_snapshot_hits`),
  deeper matched blocks computed again
  (`prefix_tokens_lost_to_state`) and no copy-on-write inside a deeper
  block. An entry lives as long as the block it is recorded on;
  recording a deeper one on a chain frees the shallower, and a request
  that finds no free working entry takes the least recently used
  recorded one (`state_snapshot_evictions`). Speculation, KV export /
  adoption / migration and the spill tier carry K/V blocks only and
  refuse such a layout by name (`_refuse_state_arrays`). A layout
  that declares no state takes none of this: no arrays, no entries,
  no third program.
- A layout may declare two BLOCK GROUPS (`CacheLayout.groups`): the
  layers whose blocks are kept for a slot's life, and the layers that
  read only the last `window` keys. Each group has its own pool size
  (`num_blocks` a group), `BlockAllocator` and block table a slot; the
  window group's table is short and MOVES (`paging.WindowTables`):
  before every launch `_move_window` drops the slot's reference to
  each block whose last key no query of the step admits any more
  (first indexed in the prefix cache, so that a later request with
  the same prefix finds it for as long as it lives; `reclaim_window`
  takes the coldest when the group runs short), allocates the blocks
  the step writes ahead, and rewrites the row. Both tables and the
  window row's base position ride in the one `batch` array; the model
  is handed ``{group name: (table, base)}`` and its window layers loop
  over the short table only, whatever any row's depth. Admission
  matches both groups at once (`PrefixCache.match_window`: a prefix
  is usable only as deep as the window group still holds the `window`
  tokens before that depth; what the full group matched deeper is
  counted in `prefix_tokens_lost_to_window` and computed again), a
  copy-on-write copies in both groups (one program), and `_evict`
  indexes the last window's blocks with the full chain. Speculation,
  KV export / adoption / migration and the spill tier know one kind
  of block and refuse such a layout by name (`_refuse_block_groups`).
  A layout of one group takes none of this.
- The pools (and the state arrays) are DONATED to every program that
  returns them (the step, the CoW copy, the row copy, the draft
  micro-step) and updated in place: the arrays
  handed in are dead after the call and `_pools` is rebound to its
  outputs under `_pool_lock`. Everything else that reads or
  rebinds a pool runs on the loop's thread between steps, or takes
  that lock (`export_prefix_blocks`). The counter `pool_inplace_steps`
  counts the steps whose pools really went in place; it equals `steps`.
- ONE compiled step. Every iteration runs the whole pool through a
  single jitted function over a fixed ``[max_slots, chunk]`` token
  matrix: decoding slots occupy one column, *prefilling* slots up to
  ``chunk`` prompt columns (chunked prefill), padding routes to the
  reserved null block. The old per-rung prefill ladder — one compile
  per padded prompt length, each stalling the decode loop — is gone;
  the decode program compiles exactly once, certified by the trace-time
  compile counters and `observe.no_retrace()`. What a step reads of
  the cache follows what the batch holds, not the table's width: the
  models' attention walks each slot's table in tiles of keys and stops
  behind the longest live row (the bound is a value of the trace, so
  the program is still one). The dense model returns the turns it ran
  in `aux`: counters `attn_key_tiles` of `attn_key_tiles_max`, their
  ratio the share of the table the steps read.
- Little crosses between host and device a step. Everything the host
  says goes in as one int32 array (`_stage`); the step picks each
  slot's greedy token itself (`out["pick"]`, after any adapter delta)
  and the host reads back 4 bytes a slot and the model's counts. The
  logits stay on the device: a slot carries a handle (`_LogitRow`)
  that fetches its one row for a sampling request or a check. The
  counters `device_picks`, `logit_rows_fetched` and `readback_bytes`
  say what crossed.
- The loop keeps ONE STEP IN FLIGHT. A step has two halves: `_launch`
  (the sweep over the slots, the one array staged, the dispatch, and
  every piece of bookkeeping that positions alone decide) and `_land`
  (the read-back of `pick` and `aux`, the tokens, the counts). `_step()`
  is one whole step, launch then land; `_iterate` orders the halves the
  other way, `new = launch(); land(old)`, so the host reads step n
  while step n+1 runs. The token a decoding row feeds back then never
  visits the host first: the row's column 0 says `_FROM_PICK` and the
  compiled step takes it from the previous step's `pick`, which it is
  handed as a device array (`extras["prev_pick"]`). One program, the
  same in both orders. The loop lands a step before it launches the
  next whenever only the host can make the next token (`_host_draws`:
  a live `do_sample` row, a speculative engine), so no knob says which.
  A step lands with its tokens taken at once when a later launch has
  swept it (every one of its rows is then staged behind its pick, or
  was not staged because the pick is its last); a step landed by hand
  or in order leaves `next_token` to the next sweep, as it always did.
  What the host then learns a step late, and why each case is safe:
  (a) `max_new_tokens` is known at launch, counting the pick in
  flight, and such a row is not staged again: no wasted column. (b)
  EOS, a cancel and a deadline are seen with one more column of the
  row in flight; its pick is dropped, never appended
  (`columns_wasted`). (c) A pick lands on the `_Slot` it was launched
  for, not on the slot's index, which may hold another request by
  then. (d) The blocks an eviction frees may be written once more by
  the step in flight, at the position `written` itself: beyond every
  row `PrefixCache.insert` donates, and a later owner of the block
  writes its own rows (behind that step, in device order) before its
  mask admits them. (e) The row copies of a layout with state arrays
  (`_seed_state`, `_take_snapshots`) are enqueued between the same two
  steps as when the host waited, since positions alone decide them; a
  snapshot taken behind a wasted column lies deeper than what the
  request wrote and is freed, not recorded. (f) A step that failed is
  learnt of at `_land`: the step launched on its outputs is dropped
  with it, every live request fails and the pools are rebuilt, as
  before. Whatever must see the engine between two steps (a boundary
  call, an abort, an idle or drained loop) lands the step in flight
  first (`_settle`). Counters `steps_launched_ahead` (of `steps`) and
  `columns_wasted`.
- Prefix sharing: finished sequences index their fully written blocks
  in a radix `PrefixCache` keyed on cumulative token-prefix hashes.
  A new request reuses every matching block physically (refcounted),
  prefills only the tail, and a divergence *inside* a cached block
  triggers copy-on-write: the block is copied once (second compiled
  helper, also traced exactly once) and the divergent rows overwritten.
- Admission is by free blocks, not free slots: a request needing more
  blocks than the whole pool sheds with the retriable 429
  `CapacityExhaustedError`; one that merely has to wait for in-flight
  frees stays queued and joins at a later step boundary.

Eviction on EOS / max_new_tokens / deadline / cancel frees the slot and
releases its block references at the next step boundary. Stale KV from
a previous occupant of a recycled block is harmless: the per-row causal
mask only admits keys <= the request's own position, all of which its
own prefill/decode overwrote first (same argument covers chunked-
prefill padding rows, whole-block CoW copies, and the rejected-suffix
rows of speculative verify steps — see below).

Fast decode (ISSUE 16) rides the same one-trace contract:

- Speculative decoding (``spec_len`` / FLAGS_serving_spec_len = k > 0)
  lives behind one object, `speculation.Speculation`, which the engine
  holds or not: each round it drafts up to k tokens a slot between the
  engine's consume and its dispatch, the unified step verifies them in
  the chunk columns the slot already owns (`out["verify"]`), and it
  accepts or resamples after the engine's commit. A plain engine is
  the case in which nothing was drafted. Compile counters certify
  ``{decode: 1, draft: 1, cow: 1}`` for life; spec-disabled engines
  build no draft trace at all and keep ``{decode: 1, cow: 1}``.
- Int8 weight path (``quantize`` / FLAGS_serving_quantize): weights are
  frozen per-tensor to int8 + `@scale` companions
  (quantization.quantize_state_int8) and cross the jit boundary as
  int8 — the HBM win. The trace dequantizes in-body via the one
  canonical formula (ops.quant_ops.dequant_int8) and routes the tied
  LM head through the `dequant_matmul` epilogue kernel. Engines handed
  a pre-frozen values dict (rollout artifacts) adopt it as-is.

Durable sessions (ISSUE 18): when ``FLAGS_serving_kv_spill_dir`` names
a directory, the engine attaches the process-shared `KVSpillStore`
(kvstore.py) as the radix cache's spill hook — a cold block evicted
from the cache persists its KV rows to SSD *before* the allocator frees
it, and a later request whose token prefix extends a spilled record
restores the blocks through `_maybe_restore` (the same all-or-nothing
alloc→scatter→insert staging as KV adoption). A torn, bit-rotted, or
generation-fenced record degrades to re-prefill, never to wrong tokens;
the session "handle" is the token prefix itself — content-addressed, so
a session resumes on ANY replica sharing the spill directory, including
after its original replica died between turns.

Fault sites: ``serving.step`` fires once per decode step (a `raise`
action fails every in-flight request deterministically while the engine
stays up); ``serving.alloc_block`` on every physical block allocation
(deterministic pool exhaustion); ``serving.cow_split`` before every
copy-on-write block copy; ``serving.draft`` and ``serving.verify`` on a
speculative engine only (speculation.py); ``serving.dequant`` once per
step on an
int8-frozen engine; ``serving.kv_restore`` before each spilled-block
restore (raise = restore abort, leak-free, the request re-prefills);
``serving.adapter_swap`` before each adapter-bank hot-swap mutates
anything (raise = all-or-nothing abort, the OLD adapter bank keeps
serving bitwise).
Supervised (fleet-owned) engines additionally
fire ``serving.replica_heartbeat`` every loop iteration and
``serving.replica_step`` before each decode step, both tagged with the
replica name — the fleet chaos sites (see framework/faults.py).

Measured from inside, always on. The loop's thread spans each part of
an iteration (`observe.span` / `observe.phase`: a `RecordEvent`, hence
on a profiler capture's clock, plus a timeline aggregate):
``serving.loop`` round a working iteration, ``loop.idle`` round the
wait when nothing is live or queued; inside an iteration ``step.admit``,
``step.sample``, then ``serving.step`` round ``step.dispatch`` (the
host's one array built, the jit call) and ``step.readback`` (the wait
for a step's picks: the step just dispatched when driven by hand or in
order, the one before it when the loop runs ahead), then
``step.commit``. A step's sample in the `decode` / `prefill` series
and the timeline's ``device-step`` is the time it held the device as
the host sees it: from the later of its own dispatch and the previous
step's landing to its own landing. A request's own stamps
(queueing.Request) are folded
once, when it leaves its slot with an answer: into the series `ttft`,
`prefill_req`, `itl`, and into the profiler ring as ``request.queue``,
``request.prefill`` (with `steps` and `prefix_hit_tokens`) and
``request.decode`` (with `token_s`, the token stamps), all three under
the request's `id`. A request that fails folds nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import numpy as np

from .. import observe, profiler
from ..core.tensor import Tensor
from ..engine import functional_apply, state_values
from ..framework import faults
from ..framework.flags import flag
from . import kvstore
from .metrics import ServingMetrics
from .paging import (
    BLOCK_ROW_ORDER, NULL_BLOCK, BlockAllocator, PoolExhausted, PrefixCache,
    SnapshotEntries, WindowTables,
)
from .queueing import (
    AdmissionQueue, CapacityExhaustedError, DeadlineExceededError, Request,
    RequestCancelled,
)
from .speculation import Speculation

__all__ = ["SlotEngine"]

# a Request is stamped on time.monotonic(), as its arrival and deadline
# are; the profiler ring is on time.perf_counter() (the same clock on
# Linux, a fixed offset apart elsewhere)
_RING_CLOCK_OFFSET = time.perf_counter() - time.monotonic()


class _Slot:
    """One in-flight request's decode state (host side)."""

    def __init__(self, req, ids, fill, blocks):
        self.req = req
        self.prompt = np.asarray(ids, np.int32)
        self.prompt_len = int(self.prompt.size)
        self.tokens = [int(t) for t in ids]  # full sequence so far
        self.fill = fill        # prompt positions already in the cache
        self.blocks = blocks    # physical block ids, table order
        self.state = "prefill" if fill < self.prompt_len else "decode"
        self.advance = 0        # positions this step will write
        self.produced = 0
        # what the next pick reads: the step's own pick of this slot's
        # row (the argmax, taken on the device) and a `_LogitRow` that
        # fetches the row itself for whoever asks (a sampling request,
        # a check). A speculative round may leave a host row with no
        # pick behind it, or None (a resample: nothing to pick from)
        self.next_token = None
        self.next_logits = None
        # this slot's picks still on the device: columns launched whose
        # pick has not landed (two between a launch and the landing of
        # the step before it, else at most one)
        self.flying = 0
        self.rng = None
        if req.gen.get("do_sample"):
            self.rng = np.random.RandomState(req.gen.get("seed", 0))
        # a speculative engine's draft-side state for this slot
        # (speculation._SlotDraft); None on a plain one
        self.spec = None
        # a layout with state arrays: the request's one working entry
        # of the snapshot pool (None = none was free) and the position
        # its state was last copied there at (0 = never)
        self.entry = None
        self.snap_depth = 0
        # a layout with a windowed group: the window-group blocks this
        # request holds, ``{block index: block id}``, the most it may
        # hold at once (reserved at admission), and its own chain of
        # prefix keys, for the blocks it indexes as it goes
        self.held = None
        self.demand = 0
        self.chain = None


class _LogitRow:
    """One slot's row of a step's logits, which stay on the device: a
    new one a commit, so two steps' rows tell apart by identity.
    `np.asarray(row)` brings that one row to the host (`[V]` float32,
    what the step handed to sampling) and counts it in
    `logit_rows_fetched`; nothing crosses until somebody asks."""

    __slots__ = ("_logits", "_slot", "_metrics")

    def __init__(self, logits, slot, metrics):
        self._logits, self._slot, self._metrics = logits, slot, metrics

    def __array__(self, dtype=None, copy=None):
        row = np.asarray(self._logits[self._slot])
        self._metrics.inc("logit_rows_fetched")
        return row if dtype is None else row.astype(dtype, copy=False)


# column 0 of a row whose last token the host has not seen: the step
# takes it from the previous step's `pick` (no token id is negative)
_FROM_PICK = -1


@dataclasses.dataclass(eq=False)
class _Flight:
    """A step from its launch to its landing: what it left on the
    device (`out`), when it was dispatched (`at`), what `_launch`
    counted for `_land` to report, and the slots whose next token is
    among its picks (`rows`, by `_Slot` and not by index alone).
    `swept` says that a later launch has swept the slots since."""

    out: dict
    at: float
    ahead: bool
    inplace: bool
    live: int
    decoding: int
    prefill_tokens: int
    computed: int
    context: int
    window_context: int = 0
    rows: list = dataclasses.field(default_factory=list)
    swept: bool = False


class _StepSpan:
    """The span `serving.step`: round the dispatch and the read-back of
    one call of `_step()` or one iteration of the loop, which share it.
    Whichever half comes first opens it; the read-back closes it."""

    def __init__(self):
        self._event = None

    def open(self):
        if self._event is None:
            self._event = profiler.RecordEvent("serving.step",
                                               cat="serving").__enter__()

    def close(self):
        if self._event is not None:
            self._event.__exit__(None, None, None)
            self._event = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class SlotEngine:
    """Continuous-batching greedy/sampling decode over a causal LM.

    `model` is any layer that offers the serving seam (eval mode is
    forced): `config` (`vocab_size`, `hidden_size`, `max_seq_len`),
    `cache_layout()` (what a block of a layer holds, and what a slot
    keeps of a state-holding layer),
    `paged_forward(tok, pos, nvalid, tables, pools) -> (hidden, pools,
    aux)` (one step over the paged pools; `aux` a dict of int arrays
    the step counts, summed into `aux_totals` and counters of the same
    names; an entry that is a plain `int` counts the same every step
    and is added on the host, not returned by the program); a model
    whose layout declares state arrays takes the slots' rows of them as
    a sixth argument and returns them after the pools:
    `paged_forward(tok, pos, nvalid, tables, pools, state) -> (hidden,
    pools, state, aux)`;
    `logits(hidden)`, and optionally `serving_gauges()`.
    `GPTForPretraining`, `LatentMoEForCausalLM` and
    `HybridLinearForCausalLM` do. `snapshot_entries` sizes the state
    snapshot pool of a layout with state arrays (default three a
    slot: a working entry a live request, a recorded one a resting
    session, and slack). Requests
    carry `max_new_tokens`, optional `eos_token_id`, and sampling
    params; results are the full [prompt + generated] int32 id array,
    token-identical to `generate()` / full re-forwarding for greedy.

    Ownership contract (same as the reference's one-predictor-per-
    thread rule): while the engine is serving, it owns the model —
    tracing temporarily swaps the model's parameter handles
    (engine.functional_apply), so run eager forwards on it only while
    the engine is idle, or on a separate instance.
    """

    def __init__(self, model, *, max_slots=None, max_seq_len=None,
                 block_size=None, num_blocks=None, prefill_chunk=None,
                 prefix_cache=None, cache_dtype=None, metrics=None,
                 queue=None, strict_shapes=False, name=None,
                 supervised=False, values=None, weight_version=0,
                 draft_model=None, spec_len=None, quantize=None,
                 w8a8=None, mesh=None, spill_dir=None,
                 max_adapters=None, lora_rank=None, snapshot_entries=None):
        import jax
        import jax.numpy as jnp

        from ..quantization import (
            SCALE_SUFFIX, dequantize_state, is_quantized_state,
            quantize_state_int8,
        )
        from .sharding import ShardingPlan, mesh_spec_of, resolve_mesh

        model.eval()
        self.model = model
        self.name = name or "engine"
        # mesh-sharded serving (ISSUE 17): None consults
        # FLAGS_serving_mesh; a 'dpD.mpM' string builds the 2-axis
        # serving mesh. Weights/pools are placed by the partition rules
        # in serving/sharding.py and the ONE compiled step carries
        # explicit in/out shardings — still exactly one trace per mesh
        # shape for engine life.
        self.mesh = resolve_mesh(mesh)
        self.mesh_spec = mesh_spec_of(self.mesh)
        self._plan = ShardingPlan(self.mesh) \
            if self.mesh is not None else None
        self.supervised = supervised
        self.last_beat = time.monotonic()
        self.heartbeats = 0
        self._abort_error = None
        self.max_slots = max_slots or flag("FLAGS_serving_max_batch")
        self.max_seq_len = min(max_seq_len or model.config.max_seq_len,
                               model.config.max_seq_len)
        self.block_size = block_size or flag("FLAGS_serving_kv_block_size")
        self.blocks_per_slot = -(-self.max_seq_len // self.block_size)
        # the model says what a block holds; the engine carries it
        self._layout = model.cache_layout()
        # `num_blocks` a group: one number sizes every group alike, a
        # dict names each group's own (0 / absent = auto)
        sized = num_blocks if isinstance(num_blocks, dict) \
            else {g.name: num_blocks for g in self._layout.groups}
        unknown = set(sized) - {g.name for g in self._layout.groups}
        if unknown:
            raise ValueError(
                f"num_blocks names {sorted(unknown)}; the model's cache "
                f"layout has {[g.name for g in self._layout.groups]}")
        self._num_blocks = {}
        for g in self._layout.groups:
            n = sized.get(g.name)
            if n is None:
                n = flag("FLAGS_serving_kv_blocks")
            if not n:   # auto: dense-equivalent worst case + null
                n = self.max_slots * self.blocks_per_slot + 1
            if n < 2:
                raise ValueError(f"num_blocks must be >= 2, got {n}")
            self._num_blocks[g.name] = int(n)
        self.num_blocks = self._num_blocks[self._layout.groups[0].name]
        self.prefill_chunk = min(
            prefill_chunk or flag("FLAGS_serving_prefill_chunk"),
            self.max_seq_len)
        self.spec_len = flag("FLAGS_serving_spec_len") \
            if spec_len is None else int(spec_len)
        if self.spec_len:
            # verify needs k+1 chunk columns per slot; the draft trace
            # is a separate, narrower program of the same width
            self.prefill_chunk = max(self.prefill_chunk, self.spec_len + 1)
            self.prefill_chunk = min(self.prefill_chunk, self.max_seq_len)
            if self.spec_len + 1 > self.max_seq_len:
                raise ValueError(
                    f"spec_len {self.spec_len} needs {self.spec_len + 1} "
                    f"chunk columns but max_seq_len is {self.max_seq_len}")
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.queue = queue if queue is not None else AdmissionQueue(
            flag("FLAGS_serving_queue_cap"), metrics=self.metrics)
        # weights are a jit ARGUMENT of the compiled step, not a trace
        # constant: an engine rebuilt with same-shape `values` from a
        # different weight version re-traces nothing beyond its own
        # fresh compile-once warmup
        self._values = dict(values) if values is not None \
            else dict(state_values(model))
        self.weight_version = int(weight_version)
        if quantize is None:
            quantize = flag("FLAGS_serving_quantize")
        if is_quantized_state(self._values):
            self.quantized = True   # pre-frozen artifact (e.g. rollout)
        elif quantize:
            self._values = quantize_state_int8(self._values)
            self.quantized = True
        else:
            self.quantized = False
        self._dequantize_state = dequantize_state
        # tied-embedding LM head on the dequant-matmul epilogue: find
        # the int8 table + scale once; fall back to the operand-dequant
        # head when untied or the table didn't freeze
        self._head_key = None
        if self.quantized:
            self.metrics.set_gauge("dequant_path", 1.0)
            for k in self._values:
                if k.endswith("word_embeddings.weight") and \
                        (k + SCALE_SUFFIX) in self._values and \
                        getattr(model.config, "tie_word_embeddings", False):
                    self._head_key = (k, k + SCALE_SUFFIX)
                    break
        # w8a8 (ISSUE 19): extend the weights-only int8 tied head to
        # activation quant — the decode matmul's input rows quantize
        # in-trace against a per-tensor scale calibrated over warmup +
        # the first few real steps, then frozen. The scale is a runtime
        # argument of the SAME compiled step (a lax.cond picks the
        # weights-only branch while it is 0), so compile counters stay
        # {decode: 1, cow: 1} and a faulted step degrades leak-free.
        if w8a8 is None:
            w8a8 = flag("FLAGS_serving_w8a8")
        self.w8a8 = bool(w8a8) and self._head_key is not None
        self._act_scale = jnp.zeros((), jnp.float32)
        self._act_calib = 0
        self._act_frozen = False
        self._w8a8_degraded = False
        if self.w8a8:
            self.metrics.set_gauge("w8a8_path", 1.0)
        cfg = model.config
        # batched LoRA adapters (ISSUE 20): stacked [n, r, H] / [n, V, r]
        # A/B banks ride the compiled step as swappable jit ARGUMENTS;
        # each slot carries an adapter_id (row 0 = base model, all-zero)
        # and the head's logits pick up a gathered low-rank delta inside
        # the ONE trace — compile counters stay {decode: 1, cow: 1} and
        # banks hot-swap with zero retraces (fixed shapes)
        if max_adapters is None:
            max_adapters = flag("FLAGS_serving_max_adapters")
        self.max_adapters = int(max_adapters or 0)
        if lora_rank is None:
            lora_rank = flag("FLAGS_serving_lora_rank")
        self.lora_rank = int(lora_rank)
        self.adapter_version = 0
        if self.max_adapters:
            if self.lora_rank < 1:
                raise ValueError(
                    f"lora_rank must be >= 1, got {self.lora_rank}")
            self._lora_a = jnp.zeros(
                (self.max_adapters, self.lora_rank, cfg.hidden_size),
                jnp.float32)
            self._lora_b = jnp.zeros(
                (self.max_adapters, cfg.vocab_size, self.lora_rank),
                jnp.float32)
            self.metrics.set_gauge("max_adapters",
                                   float(self.max_adapters))
        else:
            self._lora_a = None
            self._lora_b = None
        self._pool_dtype = cache_dtype or jnp.float32
        # held from a donating dispatch to the rebind of its outputs
        # (the arrays in between are deleted), and by a foreign
        # thread's gather (`export_prefix_blocks`)
        self._pool_lock = threading.Lock()
        self._pools = self._zero_pools(self._layout)
        # the layout's second kind of array: what a SLOT keeps of each
        # state-holding layer, whatever its context. One allocation an
        # array, `[max_slots + snapshot_entries + 1, *shape]`: a row a
        # slot (the step reads and writes those), a row an entry of the
        # snapshot pool, and a last row that stays zero, so that a
        # snapshot taken, a snapshot restored and a slot reset are all
        # the one compiled row copy (`_copy_state`). No state declared:
        # no arrays, no entries, and the code below takes none of it.
        self._state: list = []
        self._snapshots = None
        self.snapshot_entries = self._state_rows = 0
        if self._layout.state:
            self.snapshot_entries = 3 * self.max_slots \
                if snapshot_entries is None else int(snapshot_entries)
            self._snapshots = SnapshotEntries(self.snapshot_entries)
            self._state_rows = self.max_slots + self.snapshot_entries + 1
            self._state = self._zero_state()
        if self._plan is not None:
            # weights by partition rule, KV pools over the head axis
            # (replicated when heads don't divide mp); block tables and
            # the allocator stay host-side numpy — replica-global
            self._values = self._plan.place_values(self._values)
            self.metrics.set_gauge("mesh_devices", float(self.mesh.size))
            self.metrics.note_mesh(self.mesh_spec, int(self.mesh.size))
        self.kv_pool_bytes = self._pool_bytes(self._layout)
        self.state_bytes = self._state_rows \
            * self._layout.state_bytes_per_slot()
        # sums of what the model's step counts (`aux`), by name
        self.aux_totals: dict = {}
        self._aux_const: dict = {}
        itemsize = jnp.dtype(self._pool_dtype).itemsize
        self.metrics.set_gauge("kv_bytes_per_token",
                               self._layout.bytes_per_token(itemsize))
        if len(self._layout.groups) > 1:
            for g in self._layout.groups:
                self.metrics.set_gauge(f"kv_bytes_per_token_{g.name}",
                                       g.bytes_per_token(itemsize))
        self.metrics.set_gauge("weight_bytes", sum(
            int(getattr(v, "nbytes", 0)) for v in self._values.values()))
        if self._layout.state:
            self.metrics.set_gauge("state_bytes_per_slot",
                                   self._layout.state_bytes_per_slot())
            self.metrics.set_gauge("snapshot_entries",
                                   self.snapshot_entries)
        for gauge, value in getattr(model, "serving_gauges",
                                    dict)().items():
            self.metrics.set_gauge(gauge, value)
        self._alloc = BlockAllocator(self.num_blocks)
        # the windowed group's allocator and moving tables (None for a
        # layout of one group, which takes none of that code)
        self._window = None
        if len(self._layout.groups) > 1:
            wgroup = self._layout.groups[1]
            self._window = WindowTables(
                BlockAllocator(self._num_blocks[wgroup.name]),
                wgroup.window, self.block_size, self.prefill_chunk,
                self.max_slots)
            self.metrics.set_gauge("window_tokens", wgroup.window)
        if prefix_cache is None:
            prefix_cache = flag("FLAGS_serving_prefix_cache")
        self._cache = PrefixCache(self._alloc, self.block_size,
                                  snapshots=self._snapshots,
                                  window=self._window) \
            if prefix_cache else None
        if self._cache is not None and self._layout.state:
            self._cache.snapshot_evicted_hook = \
                lambda: self.metrics.inc("state_snapshot_evictions")
        # persistent KV spill tier (ISSUE 18): one shared store per
        # spill directory, so every replica of the process spills into
        # — and can resume from — the same tier. None = disabled.
        self.spill_store = kvstore.open_spill_store(
            spill_dir, metrics=self.metrics) \
            if self._cache is not None else None
        if self.spill_store is not None:
            self._refuse_state_arrays("the KV spill tier")
            self._refuse_block_groups("the KV spill tier")
            if self._layout.row_order != BLOCK_ROW_ORDER:
                # a spill record is K and V rows of [block_size, nh,
                # hd]: any other block is refused, never written as one
                raise ValueError(
                    f"the KV spill tier stores {BLOCK_ROW_ORDER!r} "
                    f"blocks; this model's cache layout is "
                    f"{self._layout.row_order!r}")
            self._cache.spill_hook = self._spill_block
        # per-engine prefix stats (the shared ServingMetrics registry
        # aggregates fleet-wide; per-replica hit rates need local ones)
        self.prefix_lookups = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        self._pos = np.zeros((self.max_slots,), np.int32)
        # per-slot adapter row (0 = base model); a jit argument of the
        # one compiled step, so changing it never retraces
        self._aid = np.zeros((self.max_slots,), np.int32)
        self._bt = np.full((self.max_slots, self.blocks_per_slot),
                           NULL_BLOCK, np.int32)
        self._slots: list = [None] * self.max_slots
        self._free = list(range(self.max_slots))
        self._compiles: dict = {}
        self._strict = strict_shapes
        self._warmed = False
        self._abort = threading.Event()
        self._thread = None
        # what rebinds a thing the compiled step reads (KV adoption
        # after a prefill->decode migration, an adapter-bank hot-swap)
        # lands at a step boundary: callers enqueue here and the serve
        # loop applies, so a rebind never races the step's own updates
        self._boundary_q: list = []
        self._boundary_lock = threading.Lock()
        # the step the loop has launched and not yet landed (`_Flight`;
        # None when driven by hand, between `_step()` calls), the clock
        # at the last landing, and the last dispatched step's `pick`,
        # which the next is handed on the device
        self._flight = None
        self._landed = 0.0
        self._prev_pick = self._no_pick()

        self._build_programs(self.spec_len + 1 if self.spec_len else 0)
        # what only speculation knows: the draft model, its pools and
        # program, and the accept rule. A plain engine holds None, builds
        # no draft trace and keeps {decode: 1, cow: 1} exactly.
        self._spec = None
        if self.spec_len:
            self._spec = Speculation(self, draft_model, self.spec_len)
            self.kv_pool_bytes += self._spec.pool_bytes

    # -- the compiled programs ----------------------------------------------

    def _count_compile(self, key):
        """Trace-time only: one more trace of program `key`."""
        self._compiles[key] = self._compiles.get(key, 0) + 1

    def _build_programs(self, verify_cols):
        """Jit the unified step and the CoW copy. With `_stage`, this is
        the one place that knows what the step takes and returns:

            serving_step(values, batch, pools, extras) -> (out, pools)

        `batch` is everything the host says a step: ONE int32 array, a
        row a slot, whose columns (`_batch_cols`) are the token chunk,
        `pos`, `nvalid`, the slot's block table and, on an engine with
        adapters, `aid`; the step slices it apart in its first lines.
        `extras` holds the device-resident arguments that are not
        weights: `prev_pick` (the `pick` of the step dispatched before
        this one, never read by the host for this purpose: a row whose
        column 0 of the token chunk says `_FROM_PICK` takes its entry
        of it for that column, every other row computes what the host
        wrote), `act_scale` under w8a8, `lora_a` and `lora_b` with
        adapters. `out` is what the step
        leaves on the device, `pools` what it is handed donated and
        hands back (`_held`: the layers' pools, or for a layout with
        state arrays `{"blocks": pools, "state": state arrays}`, of
        which the model sees the slots' rows). `out` holds: `pick`
        (int32 `[max_slots]`, the argmax
        of each slot's logits row, adapter delta included) and `aux`,
        which the host reads back every step, `verify` on a speculative
        engine (`verify_cols` > 0: the first k+1 columns' logits, read
        back too), `logits` (float32 `[max_slots, V]`, never read back
        whole: a `_LogitRow` fetches one row when somebody asks) and
        `amax` under w8a8 (folded on the device). What an engine does
        not have is absent from the trees, so each configuration has
        exactly one signature and traces once. The pools are donated."""
        import jax
        import jax.numpy as jnp

        # where `_stage` puts, and the step finds, each of the host's
        # arguments in a slot's row of `batch`
        chunk = self.prefill_chunk
        end = chunk + 2 + self.blocks_per_slot
        self._batch_cols = {"tok": slice(0, chunk), "pos": chunk,
                            "nvalid": chunk + 1,
                            "tables": slice(chunk + 2, end)}
        if self._window is not None:
            # the window group's short table and its base position
            self._batch_cols["wtables"] = slice(end,
                                                end + self._window.entries)
            end += self._window.entries + 1
            self._batch_cols["wbase"] = end - 1
        if self.max_adapters:
            self._batch_cols["aid"] = end
        self._batch_width = end + bool(self.max_adapters)

        def _head(m, values, hrows, act_scale=None):
            """Project hidden rows (.., H) to f32 logits (.., V): the
            dequant-matmul epilogue against the int8 tied table when
            frozen, the model's own head otherwise. With `act_scale`
            (w8a8) the rows also quantize to int8 — a lax.cond inside
            the one compiled step falls back to the weights-only
            epilogue while the scale is 0 (calibration, fault
            degrade)."""
            if self._head_key is not None:
                from ..ops.quant_ops import dequant_matmul

                qk, sk = self._head_key
                if act_scale is None:
                    return dequant_matmul(hrows, values[qk], values[sk])
                from ..ops import lowp as _lowp

                def quant_head(h):
                    # int8 x int8 with int32 accumulation; the frozen
                    # table is [V, H], contraction-ready as its
                    # transpose (XLA fuses the relayout into the read)
                    return _lowp.w8a8_matmul(
                        h, values[qk].T, values[sk], act_scale)

                def plain_head(h):
                    return dequant_matmul(h, values[qk], values[sk])

                from jax import lax
                return lax.cond(act_scale > 0.0, quant_head, plain_head,
                                hrows)
            squeeze = hrows.ndim == 2
            if squeeze:
                hrows = hrows[:, None, :]
            out = m.logits(Tensor(hrows))
            out = out._value if isinstance(out, Tensor) else out
            return (out[:, 0, :] if squeeze else out).astype(jnp.float32)

        def serving_step(values, batch, pools, extras):
            cols = {name: batch[:, at]
                    for name, at in self._batch_cols.items()}
            tok, pos, nvalid = cols["tok"], cols["pos"], cols["nvalid"]
            tables, aid = cols["tables"], cols.get("aid")
            if "wtables" in cols:
                # two block groups: each its table and the position of
                # the table's first entry (None = position 0)
                full, window = self._layout.groups
                tables = {full.name: (tables, None),
                          window.name: (cols["wtables"], cols["wbase"])}
            # the token a row fed back while the host had not seen it
            tok = jnp.where(tok == _FROM_PICK,
                            extras["prev_pick"][:, None], tok)
            act_scale = extras.get("act_scale")
            la, lb = extras.get("lora_a"), extras.get("lora_b")
            # trace-time only: the compile counter + retrace registry
            self._count_compile("decode")
            observe.record_compile(
                "serving.step",
                signature=observe.signature_of(tok, pos, cols["tables"]))
            # int8-frozen weights dequantize IN-trace (one canonical
            # formula; XLA fuses it into operand reads) — except the
            # head, which _head routes through the epilogue kernel
            fvals = self._dequantize_state(values) if self.quantized \
                else values

            def run(m):
                if self._layout.state:
                    # a layout with state arrays: the step is handed
                    # {"blocks": pools, "state": state arrays}, the
                    # model the slots' rows of the state, and what it
                    # returns goes back into those rows in place
                    slots = self.max_slots
                    hv, blocks, rows, aux = m.paged_forward(
                        tok, pos, nvalid, tables, pools["blocks"],
                        [tuple(a[:slots] for a in layer)
                         for layer in pools["state"]])
                    new_pools = {"blocks": blocks, "state": [
                        tuple(a.at[:slots].set(r.astype(a.dtype))
                              for a, r in zip(layer, new))
                        for layer, new in zip(pools["state"], rows)]}
                else:
                    hv, new_pools, aux = m.paged_forward(
                        tok, pos, nvalid, tables, pools)
                # a plain int is the same every step: it stays on the
                # host (noted here, at trace time) and out of the program
                self._aux_const = {k: v for k, v in aux.items()
                                   if isinstance(v, int)}
                out = {"aux": {k: v for k, v in aux.items()
                               if k not in self._aux_const}}
                # only each slot's last valid position feeds sampling:
                # skip the full-vocab projection of the rest of the chunk
                # (an idle slot has no valid column; its row is unread)
                last = hv[jnp.arange(hv.shape[0]),
                          jnp.maximum(nvalid - 1, 0)]
                out["logits"] = _head(m, values, last, act_scale)
                if act_scale is not None:
                    # w8a8 calibration: this step's head-input abs-max
                    # rides the outputs so the host can fold it into the
                    # frozen activation scale without an extra device
                    # pass (taken BEFORE any adapter delta — the scale
                    # calibrates the shared trunk, not one tenant's
                    # adapter)
                    out["amax"] = jnp.max(jnp.abs(last.astype(jnp.float32)))
                if verify_cols:
                    # speculative verify: the first k+1 chunk columns
                    # ([next, d_1..d_k]) all feed accept/reject
                    out["verify"] = _head(m, values, hv[:, :verify_cols],
                                          act_scale)
                if la is not None:
                    # batched LoRA head delta: gather each slot's
                    # adapter row by index inside the trace; row 0 is
                    # all-zero so base-model slots add exactly 0.0
                    from ..nlp.transformers.gpt import lora_logits_delta

                    out["logits"] = out["logits"] + lora_logits_delta(
                        last, aid, la, lb)
                    if verify_cols:
                        out["verify"] = out["verify"] + lora_logits_delta(
                            hv[:, :verify_cols], aid, la, lb)
                # the greedy token of every slot, taken here so that 4
                # bytes a slot cross to the host and not a row of V
                # floats; after the adapter delta, so a tenant's adapter
                # still decides its token. The first maximum, as
                # np.argmax on the host took it
                out["pick"] = jnp.argmax(out["logits"], axis=-1) \
                    .astype(jnp.int32)
                return out, new_pools

            return functional_apply(self.model, fvals, run, mesh=self.mesh)

        def serving_cow(pools, src, dst):
            from jax import lax

            self._count_compile("cow")
            observe.record_compile("serving.cow", signature="(block, block)")

            def copy(pool, src, dst):
                blk = lax.dynamic_slice_in_dim(pool, src, 1, axis=0)
                return lax.dynamic_update_slice_in_dim(pool, blk, dst,
                                                       axis=0)

            if src.ndim:
                # two block groups: a block id a group, in the groups'
                # order (null to null where a group has nothing to copy)
                groups = self._layout.groups
                group_at = [groups.index(self._layout.group_of(i))
                            for i in range(len(pools))]
                return [tuple(copy(a, src[g], dst[g]) for a in layer)
                        for layer, g in zip(pools, group_at)]
            return jax.tree_util.tree_map(
                lambda pool: copy(pool, src, dst), pools)

        def serving_snapshot(state, src, dst):
            """Row `src` of every state array copied over row `dst`:
            a snapshot taken (slot -> entry), restored (entry -> slot)
            or a slot reset (the zero row -> slot)."""
            from jax import lax

            self._count_compile("snapshot")
            observe.record_compile("serving.snapshot",
                                   signature="(row, row)")

            def copy(a):
                row = lax.dynamic_slice_in_dim(a, src, 1, axis=0)
                return lax.dynamic_update_slice_in_dim(a, row, dst, axis=0)

            return jax.tree_util.tree_map(copy, state)

        if self._plan is None:
            self._decode = jax.jit(serving_step, donate_argnums=(2,))
            self._cow = jax.jit(serving_cow, donate_argnums=(0,))
            self._snapshot = jax.jit(serving_snapshot, donate_argnums=(0,))
            return
        # explicit in/out shardings: weights follow the partition rules,
        # pools keep their head sharding through the step (GSPMD then has
        # no freedom to reshard the hot loop between steps), and whatever
        # `batch`, `extras` and `out` hold is replicated (one sharding
        # stands for every leaf of its tree)
        rep = self._plan.replicated()
        pools = self._pool_shardings(self._layout)
        state = self._state_shardings()
        held = {"blocks": pools, "state": state} if state else pools
        self._decode = jax.jit(
            serving_step,
            in_shardings=(self._plan.values_shardings(self._values), rep,
                          held, rep),
            out_shardings=(rep, held),
            donate_argnums=(2,))
        self._snapshot = jax.jit(
            serving_snapshot,
            in_shardings=(state, rep, rep),
            out_shardings=state,
            donate_argnums=(0,))
        self._cow = jax.jit(
            serving_cow,
            in_shardings=(pools, rep, rep),
            out_shardings=pools,
            donate_argnums=(0,))

    # -- introspection ------------------------------------------------------

    @property
    def compile_counts(self):
        """'decode' -> traces of the unified prefill+decode step,
        'cow' -> traces of the copy-on-write block copy, 'draft' ->
        traces of the speculative draft micro-step (present only when
        spec_len > 0). The paged engine's compile invariant is every
        value == 1 — there is no prefill bucket ladder anymore, and
        draft/verify batches reuse the same two programs for life."""
        return dict(self._compiles)

    def mesh_info(self):
        """Mesh introspection for fleet snapshots: canonical spec label,
        device count, and whether the pools are actually sharded over
        the head axis the model's layout names (it has one, and it
        divides mp) or replicated."""
        if self.mesh is None:
            return {"spec": "", "devices": 1, "kv_sharded": False}
        return {
            "spec": self.mesh_spec,
            "devices": int(self.mesh.size),
            "kv_sharded": any(
                not sh.is_fully_replicated
                for sh in self._pool_shardings(self._layout)[0]),
        }

    @property
    def active(self):
        return sum(1 for s in self._slots if s is not None)

    @property
    def free_blocks(self):
        """Currently unreferenced physical blocks."""
        return self._alloc.free_blocks

    @property
    def blocks_in_use(self):
        return self._alloc.blocks_in_use

    @property
    def prefix_cache_size(self):
        return len(self._cache) if self._cache is not None else 0

    def _blocks_needed(self, n_positions):
        return -(-int(n_positions) // self.block_size)

    # -- the pools ----------------------------------------------------------

    def _pool_shapes(self, layout):
        """One list of the pools' shapes a layer of the step's list,
        each as its group sizes it."""
        by_group = {g.name: g.pool_shapes(
            self._num_blocks.get(g.name, self.num_blocks), self.block_size)
            for g in layout.groups}
        return [by_group[layout.group_of(i).name]
                for i in range(layout.layers)]

    def _pool_shardings(self, layout):
        """One tuple of the layer's arrays' shardings a layer."""
        return [tuple(self._plan.pool_sharding(layout.group_of(i), shape)
                      for shape in shapes)
                for i, shapes in enumerate(self._pool_shapes(layout))]

    def _zero_pools(self, layout, place=True):
        """Fresh zeroed pools, ``[(array, ...), ...]``: one tuple of the
        layout's arrays a layer. At construction, and again when a
        program that was handed the pools raised and took them with it
        (`_recover_pools`)."""
        import jax
        import jax.numpy as jnp

        shapes = self._pool_shapes(layout)
        shardings = self._pool_shardings(layout) \
            if place and self._plan is not None \
            else [[None] * len(layer) for layer in shapes]

        def pool(shape, sharding):
            zeros = jnp.zeros(shape, self._pool_dtype)
            return zeros if sharding is None \
                else jax.device_put(zeros, sharding)

        return [tuple(pool(sh, sd) for sh, sd in zip(layer, placed))
                for layer, placed in zip(shapes, shardings)]

    def _state_shardings(self):
        """One tuple of the layout's state arrays' shardings a
        state-holding layer (none for a layout without)."""
        layout = self._layout
        return [tuple(
            self._plan.state_sharding(layout, name, (self._state_rows,)
                                      + shape)
            for name, shape, _ in layout.state)] * layout.state_layers

    def _zero_state(self):
        """Fresh zeroed state arrays, ``[(array, ...), ...]``: one tuple
        of the layout's state arrays a state-holding layer, each
        ``[max_slots + snapshot_entries + 1, *shape]``. At construction
        and, with the pools, in `_recover_pools`."""
        import jax
        import jax.numpy as jnp

        layout = self._layout
        shardings = self._state_shardings()[0] \
            if self._plan is not None else [None] * len(layout.state)

        def array(shape, dtype, sharding):
            zeros = jnp.zeros((self._state_rows,) + shape, dtype)
            return zeros if sharding is None \
                else jax.device_put(zeros, sharding)

        return [tuple(array(shape, dtype, sd)
                      for (_, shape, dtype), sd in zip(layout.state,
                                                       shardings))
                for _ in range(layout.state_layers)]

    def _no_pick(self):
        """`prev_pick` for a step with no step before it (the first,
        and the first after the pools were rebuilt): no row reads it."""
        import jax
        import jax.numpy as jnp

        zeros = jnp.zeros((self.max_slots,), jnp.int32)
        return zeros if self._plan is None \
            else jax.device_put(zeros, self._plan.replicated())

    def _held(self):
        """What a step is handed, donated, and hands back: the pools,
        and with them the state arrays of a layout that declares
        some."""
        return {"blocks": self._pools, "state": self._state} \
            if self._state else self._pools

    def _rebind(self, held):
        if self._state:
            self._pools, self._state = held["blocks"], held["state"]
        else:
            self._pools = held

    def _refuse_state_arrays(self, what):
        """Blocks of a layout with state arrays are only usable with
        the snapshot of that state, which `what` does not carry."""
        if self._layout.state:
            raise ValueError(
                f"{what} carries K/V blocks only; this model's cache "
                f"layout also keeps per-slot state arrays "
                f"{[name for name, _, _ in self._layout.state]}, and a "
                f"block without the state snapshot taken at its end is "
                f"no prefix to resume from")

    def _refuse_block_groups(self, what):
        """`what` knows one kind of block and one table a slot: a
        prefix of a layout with a windowed group is its full chain AND
        the window group's blocks before its end."""
        if self._window is not None:
            raise ValueError(
                f"{what} carries one group of blocks; this model's cache "
                f"layout has the groups "
                f"{[(g.name, g.window) for g in self._layout.groups]}, "
                f"and a prefix without the window group's blocks before "
                f"its end is no prefix to resume from")

    def _pool_bytes(self, layout):
        import jax.numpy as jnp

        itemsize = jnp.dtype(self._pool_dtype).itemsize
        return sum(self._num_blocks.get(g.name, self.num_blocks)
                   * self.block_size * g.bytes_per_token(itemsize)
                   for g in layout.groups)

    @staticmethod
    def _arrays(pools):
        """Every array of ``[(array, ...), ...]``, layer-major."""
        return [a for layer in pools for a in layer]

    @staticmethod
    def _lost(pools):
        """Whether a program took any of these pools with it. What is
        left of them then goes too: half a set of pools serves nothing
        and would stand beside the fresh ones in the device's memory."""
        if not any(a.is_deleted() for a in pools):
            return False
        for a in pools:
            a.delete()
        return True

    def _recover_pools(self, error):
        """After a program that was handed the pools raised. One that
        raised before its dispatch (a fault point, a trace error) donated
        nothing and nothing is done here. One that raised after it took
        the pools with it: every live slot's KV is gone, so they fail
        with `error`, the prefix index is dropped without spilling (the
        rows it names no longer exist) and the engine goes on with empty
        pools."""
        if not self._lost(self._arrays(self._pools + self._state)):
            return
        self._fail_all_active(error)
        if self._cache is not None:
            self._cache.clear(spill=False)
        with self._pool_lock:
            self._pools = self._zero_pools(self._layout)
            if self._state:
                self._state = self._zero_state()
        # the lost step's pick went with it
        self._prev_pick = self._no_pick()
        self.metrics.inc("pool_rebuilds")

    # -- w8a8 activation scale (frozen after a short calibration) -----------

    # warmup + this many real steps feed the running abs-max before the
    # activation scale freezes; until the first absorb lands the scale
    # is 0 and the in-trace lax.cond keeps the weights-only epilogue
    _W8A8_CALIB_STEPS = 8

    def _absorb_act_amax(self, amax):
        """Fold one step's head-input abs-max into the frozen scale.
        Pure device ops (jnp.maximum on scalars) — no host sync, and
        the scale is an argument of the one compiled step, so the
        running update never retraces."""
        if self._act_frozen or self._w8a8_degraded:
            return
        import jax.numpy as jnp

        self._act_scale = jnp.maximum(self._act_scale, amax)
        self._act_calib += 1
        if self._act_calib > self._W8A8_CALIB_STEPS:
            self._act_frozen = True

    # -- the step's arguments -----------------------------------------------

    def _stage(self, tok, pos, nvalid):
        """The step's `batch` and `extras` (see `_build_programs`) for
        one call: the host's arrays, the block tables and the adapter
        rows with them, written into ONE fresh int32 array, which the
        jit call takes as it is (numpy) and moves to the device in one
        transfer of its own. (Staged here with `jnp.asarray` it costs
        0.2 ms a step more on the v5e, four arrays 0.6-0.8 ms more:
        PERF.md, PR 32.) Fresh each call, so nothing the host writes
        later can reach a step in flight. Warmup, the step and the
        tests all come through here, so jax.jit sees exactly one
        signature per engine configuration — the compile-once
        invariant survives any mix of the w8a8 and adapter options."""
        import jax.numpy as jnp

        said = {"tok": tok, "pos": pos, "nvalid": nvalid,
                "tables": self._bt, "aid": self._aid}
        if self._window is not None:
            said["wtables"] = self._window.table
            said["wbase"] = self._window.base
        batch = np.empty((self.max_slots, self._batch_width), np.int32)
        for name, at in self._batch_cols.items():
            batch[:, at] = said[name]
        extras = {"prev_pick": self._prev_pick}
        if self.w8a8:
            # 0 degrades the step to the weights-only dequant path
            # inside the same trace
            extras["act_scale"] = jnp.zeros((), jnp.float32) \
                if self._w8a8_degraded else self._act_scale
        if self.max_adapters:
            extras["lora_a"], extras["lora_b"] = self._lora_a, self._lora_b
        return batch, extras

    def _dispatch(self, tok, pos, nvalid):
        """One call of the compiled step. The pools are donated: dead
        from the dispatch to the rebind of its outputs, under
        `_pool_lock`. Returns the step's `out`, still on the device
        (w8a8's abs-max folded into the scale there, never read)."""
        batch, extras = self._stage(tok, pos, nvalid)
        with self._pool_lock:
            out, held = self._decode(self._values, batch, self._held(),
                                     extras)
            self._rebind(held)
        self._prev_pick = out["pick"]
        if self.w8a8:
            self._absorb_act_amax(out.pop("amax"))
        return out

    # -- batched adapter bank (ISSUE 20) ------------------------------------

    def _at_step_boundary(self, call, what, timeout):
        """`call()` where no step is in flight: on the loop's thread
        between two steps when the serve loop is running, inline
        otherwise. Returns its result or raises its error."""
        if self._thread is None or not self._thread.is_alive():
            return call()
        done = threading.Event()
        box: dict = {}
        with self._boundary_lock:
            self._boundary_q.append((call, done, box))
        if not done.wait(timeout):
            raise TimeoutError(
                f"engine {self.name!r} did not reach a step boundary "
                f"within {timeout:.3f}s to {what}")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _drain_boundary_calls(self):
        while True:
            with self._boundary_lock:
                if not self._boundary_q:
                    return
                call, done, box = self._boundary_q.pop(0)
            self._settle()
            try:
                box["result"] = call()
            except Exception as e:  # noqa: BLE001 — caller re-raises
                box["error"] = e
            finally:
                done.set()

    def swap_adapters(self, lora_a, lora_b, version=None, timeout=5.0):
        """Hot-swap the stacked adapter bank (the rollout commit path),
        at a step boundary: the bank rebind must not race the compiled
        step's reads. All-or-nothing: a fault (``serving.adapter_swap``)
        or validation error leaves the OLD bank serving bitwise. Shapes
        are fixed by construction, so a swap never retraces. Returns
        the new adapter_version."""
        if not self.max_adapters:
            raise ValueError(
                "engine built without adapters (max_adapters=0 / "
                "FLAGS_serving_max_adapters)")
        return self._at_step_boundary(
            lambda: self._apply_adapter_swap(lora_a, lora_b, version),
            "swap adapters", timeout)

    def _apply_adapter_swap(self, lora_a, lora_b, version):
        import jax.numpy as jnp

        # the fault fires BEFORE any mutation: a faulted swap leaves
        # the old adapter bank serving bitwise
        faults.fault_point("serving.adapter_swap", tag=self.name)
        la = jnp.asarray(lora_a, jnp.float32)
        lb = jnp.asarray(lora_b, jnp.float32)
        if la.shape != self._lora_a.shape or \
                lb.shape != self._lora_b.shape:
            raise ValueError(
                f"adapter bank shapes {la.shape}/{lb.shape} != engine "
                f"{self._lora_a.shape}/{self._lora_b.shape}: rebuild "
                "the engine to change adapter capacity or rank")
        if np.asarray(la[0]).any() or np.asarray(lb[0]).any():
            raise ValueError(
                "adapter row 0 is the base model and must stay all-zero")
        if self._plan is not None:
            import jax

            rep = self._plan.replicated()
            la = jax.device_put(la, rep)
            lb = jax.device_put(lb, rep)
        self._lora_a, self._lora_b = la, lb
        self.adapter_version = int(version) if version is not None \
            else self.adapter_version + 1
        self.metrics.inc("adapter_swaps")
        return self.adapter_version

    # -- warmup -------------------------------------------------------------

    def warmup(self, mesh=None):
        """Trace the unified step and the CoW copy before traffic so the
        hot path never compiles. All tables point at the null block, so
        the dummy step's writes land in reserved scratch; its outputs are
        discarded, the pools (donated, like in any step) rebound.
        Returns `compile_counts`.

        `mesh` (optional) asserts the caller's mesh matches the one the
        engine compiled for — a shard restart that rebuilt topology must
        land on the same shape or it would silently retrace. A repeat
        warmup (re-entering the serve path after a shard restart) runs
        under `observe.no_retrace()`: same shapes + same mesh = zero new
        compiles for engine life."""
        if mesh is not None:
            from .sharding import mesh_spec_of, resolve_mesh

            want = mesh_spec_of(resolve_mesh(mesh))
            if want != self.mesh_spec:
                raise ValueError(
                    f"warmup mesh {want!r} != engine mesh "
                    f"{self.mesh_spec!r}: rebuild the engine for a new "
                    "mesh shape instead of re-warming")
        guard = observe.no_retrace() if self._warmed \
            else contextlib.nullcontext()
        with guard:
            tok = np.zeros((self.max_slots, self.prefill_chunk), np.int32)
            pos = np.zeros((self.max_slots,), np.int32)
            nvalid = np.ones((self.max_slots,), np.int32)
            self._dispatch(tok, pos, nvalid)
            self._copy_block(NULL_BLOCK, NULL_BLOCK)
            if self._state:
                # the dummy step wrote every slot's state; admission
                # resets a slot before its first real step
                self._copy_state(self._state_rows - 1,
                                 self._state_rows - 1)
            if self._spec is not None:
                self._spec.warmup(pos, nvalid)
        self._warmed = True
        return self.compile_counts

    # -- request lifecycle --------------------------------------------------

    def submit(self, prompt_ids, *, max_new_tokens=16, eos_token_id=None,
               timeout=None, priority=0, do_sample=False, temperature=1.0,
               top_k=0, seed=0, adapter_id=0, tenant=None):
        """Admit one request (or shed); returns its `Request` future.

        Length beyond the model's positional range is a hard
        `ValueError` (client error); a request whose block demand
        exceeds the whole physical pool sheds with the retriable
        `CapacityExhaustedError` (HTTP 429) instead — paged capacity,
        not slot count, is the admission limit."""
        if timeout is None:
            timeout = flag("FLAGS_serving_default_timeout_s") or None
        adapter_id = int(adapter_id or 0)
        if adapter_id < 0 or adapter_id >= max(self.max_adapters, 1):
            raise ValueError(
                f"adapter_id {adapter_id} outside the engine's bank "
                f"(max_adapters={self.max_adapters}; 0 = base model)")
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if ids.size + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine max_seq_len {self.max_seq_len}")
        need = self._blocks_needed(ids.size + max_new_tokens)
        if need > self._alloc.usable:
            self.metrics.inc("rejected_capacity")
            raise CapacityExhaustedError(
                f"request needs {need} KV blocks but the pool holds "
                f"{self._alloc.usable} (block_size={self.block_size}); "
                "retry with a smaller request or grow "
                "FLAGS_serving_kv_blocks")
        if self._window is not None:
            demand = self._window.demand(ids.size + max_new_tokens)
            if demand > self._window.alloc.usable:
                self.metrics.inc("rejected_capacity")
                raise CapacityExhaustedError(
                    f"request needs {demand} blocks of the window group "
                    f"at once but its pool holds "
                    f"{self._window.alloc.usable}")
        return self.queue.submit(Request(
            ids, timeout=timeout, priority=priority,
            max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            seed=seed, adapter_id=adapter_id, tenant=tenant))

    def _stage_blocks(self, ids, need_total):
        """Reserve the physical blocks for one admission: reuse every
        prefix-cached block, allocate the rest, copy-on-write when the
        divergence falls inside a cached block. Returns
        ``(blocks, fill, entry, held)`` or raises (`PoolExhausted` =
        wait and retry; anything else = fail the request).
        All-or-nothing: partial reservations are rolled back.

        A layout with state arrays cuts the match at the deepest state
        snapshot recorded on the matched chain (`match_snapshot`):
        `entry` is that snapshot's, to restore the slot's state from
        (None = the state starts from zero), blocks that matched deeper
        are computed again (`prefix_tokens_lost_to_state`), and there
        is no copy-on-write inside a deeper block.

        A layout with a windowed group matches both groups at once
        (`match_window`): the match is cut at the deepest depth the
        window group can still serve (`prefix_tokens_lost_to_window`
        counts what the full chain matched deeper), `held` is the
        window-group blocks the request resumes over (``{block index:
        block id}``; None for a layout of one group), a copy-on-write
        copies in both groups, and the request's `demand` of
        window-group blocks is reserved against the group's pool."""
        shared, n_shared, cow, entry = [], 0, None, None
        window = self._window
        held = {} if window is not None else None
        if self._cache is not None:
            if self.spill_store is not None:
                # session resume: pull spilled records extending the
                # live cached prefix back into the pool first, so the
                # match below sees them as ordinary cache hits
                self._maybe_restore(ids)
            # always leave >= 1 prompt token to compute: the last
            # token's logits seed decode
            if self._layout.state:
                shared, n_shared, entry, matched = \
                    self._cache.match_snapshot(ids, ids.size - 1)
                self.metrics.inc("prefix_tokens_lost_to_state",
                                 matched - n_shared)
            elif window is not None:
                shared, n_shared, cow, held, matched = \
                    self._cache.match_window(ids, ids.size - 1)
                self.metrics.inc("prefix_tokens_lost_to_window",
                                 matched - n_shared)
            else:
                shared, n_shared, cow = self._cache.match(ids,
                                                          ids.size - 1)
            self.metrics.inc("prefix_lookups")
            self.metrics.inc("prompt_tokens", int(ids.size))
            hit_tokens = n_shared + (cow[1] if cow else 0)
            if shared:
                self.metrics.inc("prefix_hit_blocks", len(shared))
            if hit_tokens:
                self.metrics.inc("prefix_hit_tokens", hit_tokens)
            self.prefix_lookups += 1
            self.prefix_prompt_tokens += int(ids.size)
            self.prefix_hit_tokens += hit_tokens
        n_new = need_total - len(shared)
        taken, new, pinned_src = [], [], None
        wtaken, wpinned = [], None
        try:
            # pin every matched block (and the CoW source) BEFORE any
            # reclaim: eviction under pressure must never free a block
            # `match` just handed us — an unpinned matched leaf could be
            # reclaimed here and its id recycled by our own alloc loop,
            # turning a prefix hit into silent KV corruption
            for bid in shared:
                self._alloc.incref(bid)
                taken.append(bid)
            if cow is not None:
                self._alloc.incref(cow[0])
                pinned_src = cow[0]
            if window is not None:
                for wbid in held.values():
                    window.alloc.incref(wbid)
                    wtaken.append(wbid)
                if cow is not None:
                    window.alloc.incref(cow[2])
                    wpinned = cow[2]
                demand = window.demand(need_total * self.block_size)
                if window.reserved + demand > window.alloc.usable:
                    raise PoolExhausted(
                        f"need {demand} blocks of the window group beside "
                        f"the {window.reserved} reserved, the pool holds "
                        f"{window.alloc.usable}")
            if self._alloc.free_blocks < n_new and self._cache is not None:
                self._cache.reclaim(n_new - self._alloc.free_blocks)
            if self._alloc.free_blocks < n_new:
                raise PoolExhausted(
                    f"need {n_new} free KV blocks, have "
                    f"{self._alloc.free_blocks}")
            for _ in range(n_new):
                new.append(self._alloc.alloc())
            fill = n_shared
            if cow is not None:
                src, rows = cow[:2]
                faults.fault_point("serving.cow_split")
                with profiler.RecordEvent("serving.cow", cat="serving"):
                    if window is None:
                        self._copy_block(src, new[0])
                    else:
                        wdst = self._alloc_window_block()
                        wtaken.append(wdst)
                        held[len(shared)] = wdst
                        self._copy_block(np.asarray([src, cow[2]], np.int32),
                                         np.asarray([new[0], wdst],
                                                    np.int32))
                self.metrics.inc("cow_splits")
                fill += rows
        except Exception:
            for bid in taken:
                self._alloc.decref(bid)
            for bid in new:
                self._alloc.decref(bid)
            if pinned_src is not None:
                self._alloc.decref(pinned_src)
            for wbid in wtaken:
                window.alloc.decref(wbid)
            if wpinned is not None:
                window.alloc.decref(wpinned)
            raise
        if pinned_src is not None:
            self._alloc.decref(pinned_src)
        if wpinned is not None:
            window.alloc.decref(wpinned)
        return taken + new, fill, entry, held

    def _alloc_window_block(self):
        """One fresh block of the window group; the index gives up its
        coldest when the group has none free (admission reserved what a
        live slot needs, so one is free or reclaimable)."""
        alloc = self._window.alloc
        if not alloc.free_blocks and self._cache is not None:
            self._cache.reclaim_window(1)
        return alloc.alloc()

    def _resume_block(self, slot):
        """The first window-group block a request that resumes at the
        END of this one's prompt would read. Blocks before it lie deep
        inside the prompt: the index records them as its coldest (they
        serve only a request that diverges there), the blocks from it
        on as its most recent (the prompt's end and the sequence's end
        are where a later request with this prefix resumes)."""
        return self._window.first_block(slot.prompt_len)

    def _move_window(self, i, slot, n):
        """Before the launch of a step that computes positions ``[pos,
        pos + n)`` of slot `i`: the window group's table moves. Blocks
        whose last key lies before ``pos - window + 1`` are behind every
        query from now on: they are indexed in the prefix cache (with
        the full chain up to them, so a later request with this prefix
        finds them for as long as they live) and the slot's reference
        is dropped; blocks the step writes ahead are allocated; the
        slot's row of the table is rewritten from its lowest block."""
        w, bs = self._window, self.block_size
        pos = int(self._pos[i])
        first = w.first_block(pos)
        behind = sorted(k for k in slot.held if k < first)
        if behind:
            if self._cache is not None:
                upto = (behind[-1] + 1) * bs
                self._cache.insert(
                    slot.prompt if upto <= slot.prompt_len else slot.tokens,
                    slot.blocks, upto,
                    window={k: slot.held[k] for k in behind},
                    chain=slot.chain, cold_below=self._resume_block(slot))
            for k in behind:
                w.alloc.decref(slot.held.pop(k))
            self.metrics.inc("window_blocks_freed", len(behind))
        ahead = [k for k in range(first, (pos + n - 1) // bs + 1)
                 if k not in slot.held]
        for k in ahead:
            slot.held[k] = self._alloc_window_block()
        if behind or ahead:
            w.sync(i, slot.held)

    def _copy_block(self, src, dst):
        """The compiled copy-on-write copy, every layer's pools at
        once; they are donated to it like to the step."""
        import jax.numpy as jnp

        if self._window is not None and np.ndim(src) == 0:
            # a layout of two groups names a block a group
            src, dst = np.asarray([src, src]), np.asarray([dst, dst])
        with self._pool_lock:
            self._pools = self._cow(self._pools, jnp.asarray(src, jnp.int32),
                                    jnp.asarray(dst, jnp.int32))

    def _copy_state(self, src, dst):
        """The compiled row copy over every state array at once (row
        `src` over row `dst`); they are donated to it like to the
        step."""
        # numpy scalars: the jit call moves them itself, more cheaply
        # than staged with `jnp.int32` first (PERF.md, PR 32 and 33)
        with self._pool_lock:
            self._state = self._snapshot(self._state, np.int32(src),
                                         np.int32(dst))

    def _seed_state(self, slot, entry):
        """A slot just admitted: its state restored from the snapshot
        `entry` its prefix hit ends at, or reset to zero without one
        (whatever the slot's last occupant left never reaches it), and
        a working entry of its own to snapshot into, if one is free or
        the least recently used recorded one can go."""
        with self._snapshot_span():
            if entry is None:
                self._copy_state(self._state_rows - 1, slot)
                self.metrics.inc("state_resets")
            else:
                self._copy_state(self.max_slots + entry, slot)
                self.metrics.inc("state_snapshot_hits")
        mine = self._snapshots.alloc()
        if mine is None and self._cache is not None \
                and self._cache.evict_lru_snapshot():
            mine = self._snapshots.alloc()
        self._slots[slot].entry = mine

    @contextlib.contextmanager
    def _snapshot_span(self):
        """Span `serving.snapshot`, phase `snapshot`: round every take
        and restore."""
        t0 = time.perf_counter()
        with profiler.RecordEvent("serving.snapshot", cat="serving"):
            yield
        observe.timeline.add("snapshot", time.perf_counter() - t0)

    def _take_snapshots(self, live):
        """After a step's commit: every live slot whose position now
        lies exactly on a block boundary has its state copied into its
        working entry, over the older copy; no host read-back. A slot
        that goes on prefilling whole chunks skips it: it lands on a
        deeper boundary before it can end."""
        due = []
        for i in live:
            slot, at = self._slots[i], int(self._pos[i])
            if slot.entry is None or at % self.block_size:
                continue
            if slot.prompt_len - slot.fill >= self.prefill_chunk \
                    and self.prefill_chunk % self.block_size == 0:
                continue
            due.append((i, slot, at))
        if not due:
            return
        with self._snapshot_span():
            for i, slot, at in due:
                self._copy_state(i, self.max_slots + slot.entry)
                slot.snap_depth = at
        self.metrics.inc("state_snapshots_taken", len(due))

    def _admit(self):
        """Join-at-step: fill free slots from the queue while block
        capacity lasts (no waiting). A request the pool cannot hold
        *right now* is pushed back to the queue head and retried after
        the next eviction frees blocks."""
        while self._free:
            req = self.queue.pop(timeout=0.0)
            if req is None:
                return
            ids = req.payload
            need = self._blocks_needed(
                ids.size + req.gen.get("max_new_tokens", 16))
            try:
                blocks, fill, entry, held = self._stage_blocks(ids, need)
            except PoolExhausted:
                # FIFO head-of-line wait: blocks free at step boundaries
                self.queue.requeue(req)
                return
            except Exception as e:  # noqa: BLE001 — fail req, stay up
                self.metrics.inc("failed")
                req._fail(e)
                self._recover_pools(e)   # a CoW copy that took them
                continue
            slot = self._free.pop()
            self._bt[slot, :] = NULL_BLOCK
            self._bt[slot, :len(blocks)] = blocks
            self._pos[slot] = fill
            self._aid[slot] = int(req.gen.get("adapter_id", 0) or 0)
            self._slots[slot] = _Slot(req, ids, fill, blocks)
            if self._state:
                self._seed_state(slot, entry)
            if held is not None:
                admitted = self._slots[slot]
                admitted.held = held
                admitted.demand = self._window.demand(need * self.block_size)
                self._window.reserved += admitted.demand
                self._window.sync(slot, held)
                if self._cache is not None:
                    admitted.chain = self._cache.chain()
            req.admitted = time.monotonic()
            req.queue_wait = req.admitted - req.arrival
            req.prefix_hit_tokens = fill
            self.metrics.inc("admitted")
            self.metrics.observe_latency("queue", req.queue_wait)

    # -- KV migration (prefill->decode disaggregation, ISSUE 17) ------------

    def export_prefix_blocks(self, prompt_ids):
        """Gather this engine's fully-written cached KV blocks covering
        `prompt_ids` into host numpy for migration. Returns a payload
        dict (tokens / per-layer tuples of the layout's arrays' rows,
        ``[n_blocks, block_size, *row]`` each / geometry, the rows'
        axis order included) or None when nothing is cached. The
        matched blocks are pinned (incref) for the duration of the gather so a
        concurrent reclaim cannot recycle them mid-copy; block tables
        were host-side all along, so only block payload bytes leave the
        engine.

        Callable from any thread while the loop runs (`migrate.
        migrate_prefix`). The pools are donated to every step, so an
        array read here could be deleted under the reader: the
        device-side gathers are enqueued under `_pool_lock`, which the
        loop holds from a dispatch to the rebind of its outputs; the
        copies to the host wait outside it."""
        self._refuse_state_arrays("export_prefix_blocks")
        self._refuse_block_groups("export_prefix_blocks")
        if self._cache is None:
            return None
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size < 2:
            return None
        shared, n_shared, _cow = self._cache.match(ids, ids.size - 1)
        if not shared:
            return None
        for bid in shared:
            self._alloc.incref(bid)
        try:
            # the pinned blocks' rows were fully written before the
            # cache ever indexed them, and a gather enqueued here reads
            # them before any later step's write
            idx = np.asarray(shared, np.int64)
            with self._pool_lock:
                rows = [tuple(a[idx] for a in layer)
                        for layer in self._pools]
            layers = [tuple(np.asarray(a) for a in layer)
                      for layer in rows]
        finally:
            for bid in shared:
                self._alloc.decref(bid)
        return {
            "tokens": [int(t) for t in ids[:n_shared]],
            "n_tokens": int(n_shared),
            "block_size": self.block_size,
            "row_order": self._layout.row_order,
            "layers": layers,
        }

    def adopt_prefix_blocks(self, payload, timeout=5.0):
        """Adopt migrated KV blocks into this engine's pool + prefix
        cache, at a step boundary: pool rebinds must not race the
        compiled step, which is handed the pools and deletes them.
        Returns the number of prompt tokens now served from cache (0 =
        incompatible payload: another block size, layer count, block
        shape or row order). All-or-nothing: any fault
        mid-adoption frees every block taken so far — the pool is
        leak-free and the request simply prefills from scratch."""
        self._refuse_state_arrays("adopt_prefix_blocks")
        self._refuse_block_groups("adopt_prefix_blocks")
        return self._at_step_boundary(
            lambda: self._apply_adoption(payload),
            "adopt migrated KV", timeout)

    def _apply_adoption(self, payload):
        if self._cache is None or payload is None:
            return 0
        if payload.get("block_size") != self.block_size:
            return 0
        if payload.get("row_order") != self._layout.row_order:
            # another model's kind of block, or head-major rows (an
            # engine from before the pool went token-major): with nh ==
            # block_size the shapes agree and only this says the block
            # is transposed
            return 0
        layers = payload["layers"]
        if len(layers) != len(self._pools):
            return 0
        nb = int(layers[0][0].shape[0]) if layers else 0
        if nb == 0 or [a.shape[1:] for a in layers[0]] \
                != [a.shape[1:] for a in self._pools[0]]:
            return 0
        if self._alloc.free_blocks < nb and self._cache is not None:
            self._cache.reclaim(nb - self._alloc.free_blocks)
        taken: list = []
        try:
            for _ in range(nb):
                faults.fault_point("serving.kv_migrate", tag=self.name)
                taken.append(self._alloc.alloc())
            idx = np.asarray(taken, np.int64)
            self._write_blocks(idx, layers)
            n_tokens = nb * self.block_size
            self._cache.insert(payload["tokens"][:n_tokens], taken,
                               n_tokens)
        except Exception:
            for bid in taken:
                self._alloc.decref(bid)
            raise
        # the cache increfed every NEW entry; dropping our allocation
        # refs hands ownership over (and frees duplicate-key blocks the
        # cache already held under another id)
        for bid in taken:
            self._alloc.decref(bid)
        return nb * self.block_size

    def _write_blocks(self, idx, layers):
        """Write whole blocks' rows (one tuple of arrays a layer, as
        exported or spilled) at block ids `idx`; on the loop's thread,
        between steps."""
        self._pools = [
            tuple(a.at[idx].set(rows) for a, rows in zip(layer, new))
            for layer, new in zip(self._pools, layers)]

    # -- persistent KV spill tier (ISSUE 18) --------------------------------

    def _spill_block(self, key, tokens, bid, n_rows):
        """PrefixCache donation hook: persist one evicted block's KV
        rows to the SSD tier BEFORE the freeing decref (append-before-
        evict). Best-effort by contract — a spill fault (full/failing
        disk, injected ``serving.spill``) loses durability for this
        block, never the eviction or the allocator balance."""
        if n_rows != self.block_size:
            return
        try:
            # the cache evicts on the loop's thread, outside a dispatch
            # (or with no loop running), so the pools are whole: a step
            # in flight has left its outputs in their place, and the
            # read below waits for it; the block is still
            # cache-referenced, so its rows cannot be recycled before
            # the hook returns
            layers = [tuple(np.asarray(a[bid]) for a in layer)
                      for layer in self._pools]
            self.spill_store.append(key, self.weight_version, tokens,
                                    layers)
        except Exception:  # noqa: BLE001 — durability is best-effort
            self.metrics.inc("kv_spill_errors")

    def _maybe_restore(self, ids):
        """Resume staging: walk the prompt's cumulative-prefix digest
        chain past the live cached prefix and re-stage every matching
        spilled record through the all-or-nothing admission path
        (alloc → scatter → cache.insert, exactly like KV adoption).
        Fault site ``serving.kv_restore`` fires per block, tagged with
        the engine name; any failure — fault, fenced generation, torn
        or bit-rotted record, geometry/token mismatch, pool pressure —
        stops the walk leak-free and the request re-prefills the rest.
        Returns the number of tokens restored."""
        store, cache = self.spill_store, self._cache
        ids = np.asarray(ids, np.int32).reshape(-1)
        if store is None or cache is None or ids.size < 2:
            return 0
        bs = self.block_size
        limit = ids.size - 1
        chain, n, _cow = cache.match(ids, limit)
        chain = list(chain)
        # gather every restorable record past the live chain first, then
        # stage them with ONE scatter per layer pool — per-block
        # .at[].set dispatches cost more host time than the prefill
        # chunks the restore is supposed to save
        recs = []
        while n + len(recs) * bs + bs <= limit:
            m = n + len(recs) * bs
            key = cache._digest(ids[:m + bs])
            if key in cache._blocks:
                if recs:
                    break   # restored gap already ends at a live entry
                chain.append(cache._blocks[key])
                n += bs
                continue
            try:
                rec = store.get(key)
            except kvstore.SpillFencedError:
                # rollout fenced this generation's records: the caller
                # re-prefills on the live weights (bitwise-safe)
                self.metrics.inc("kv_restore_fenced")
                break
            if rec is None:
                break
            if (rec["generation"] != self.weight_version
                    or rec["block_size"] != bs
                    or len(rec["layers"]) != len(self._pools)
                    or rec["layers"][0][0].shape
                    != self._pools[0][0].shape[1:]
                    or not np.array_equal(rec["tokens"], ids[:m + bs])):
                break
            recs.append(rec)
        if self._alloc.free_blocks < len(recs):
            # no reclaim here: it could evict our own chain
            recs = recs[:max(self._alloc.free_blocks, 0)]
        if not recs:
            return 0
        bids, inserted = [], 0
        try:
            for _ in recs:
                faults.fault_point("serving.kv_restore", tag=self.name)
                bids.append(self._alloc.alloc())
            idx = np.asarray(bids, np.int64)
            self._write_blocks(idx, [
                tuple(np.stack([r["layers"][li][ai] for r in recs])
                      for ai in range(len(layer)))
                for li, layer in enumerate(self._pools)])
            for bid in bids:
                chain.append(bid)
                cache.insert(ids[:n + bs], chain, n + bs)
                # the cache now owns its own ref; drop ours
                self._alloc.decref(bid)
                self.metrics.inc("kv_restored_blocks")
                n += bs
                inserted += 1
        except Exception:  # noqa: BLE001 — leak-free abort
            for bid in bids[inserted:]:
                if chain and chain[-1] == bid:
                    chain.pop()
                self._alloc.decref(bid)
        return inserted * bs

    def spill_cache(self):
        """Drain the radix cache through the spill tier (graceful-drain
        / bench pressure lever): every evictable entry takes the normal
        eviction path, so blocks whose last reference is the cache's
        persist to SSD before they free. Returns #entries dropped."""
        if self._cache is None:
            return 0
        n = len(self._cache)
        self._cache.clear()
        return n

    def prefix_hit_rate(self):
        """This engine's own prompt-token prefix hit rate (the shared
        metrics registry aggregates fleet-wide; this is per-replica)."""
        return self.prefix_hit_tokens / self.prefix_prompt_tokens \
            if self.prefix_prompt_tokens else 0.0

    @staticmethod
    def _warp_probs(logits, gen):
        """Temperature + top-k warped softmax, exactly the transform
        `_pick` samples from — speculative accept/reject must compare
        target and draft through the SAME warp or the emitted
        distribution shifts."""
        scaled = logits / max(gen.get("temperature", 1.0), 1e-6)
        top_k = gen.get("top_k", 0)
        if top_k:
            kth = np.sort(scaled)[-min(top_k, scaled.size)]
            scaled = np.where(scaled < kth, -np.inf, scaled)
        z = scaled - scaled.max()
        p = np.exp(z)
        p /= p.sum()
        return p

    def _pick(self, slot: _Slot):
        """The slot's next token. A greedy request takes the step's
        own pick of its row (`next_token`, counted in `device_picks`):
        nothing of the row crosses to the host. A sampling request
        fetches its row through the handle and samples here, so each
        request carries its own sampling config and rng stream. A row
        that speculation handed over is on the host already and has no
        pick behind it: its argmax is taken here."""
        gen = slot.req.gen
        if not gen.get("do_sample"):
            if slot.next_token is None:
                return int(slot.next_logits.argmax())
            self.metrics.inc("device_picks")
            return slot.next_token
        p = self._warp_probs(np.asarray(slot.next_logits), gen)
        return int(slot.rng.choice(p.size, p=p))

    def _evict(self, idx, error=None):
        slot = self._slots[idx]
        self._slots[idx] = None
        self._free.append(idx)
        # a column of this slot still in flight is wasted: its pick is
        # dropped when it lands, and what it writes (one row, at the
        # position `written` itself, in a block freed below) lies beyond
        # every row the prefix index is given; whoever owns the block
        # next writes its own rows behind that step, in device order,
        # before its mask admits them. A state snapshot taken behind
        # such a column is deeper than `written`: `insert` frees it
        if slot.flying:
            self.metrics.inc("columns_wasted", slot.flying)
        written = int(self._pos[idx]) - slot.flying
        snapshot = None if slot.entry is None \
            else (slot.entry, slot.snap_depth)
        if error is None and self._cache is not None:
            # donate fully written blocks to the prefix index before
            # releasing our references — shared system prompts survive;
            # with them the request's state snapshot, recorded on the
            # block that ends at its depth
            # and the window-group blocks the request still holds, its
            # last window
            self._cache.insert(slot.tokens, slot.blocks, written,
                               snapshot=snapshot, window=slot.held,
                               chain=slot.chain,
                               cold_below=self._resume_block(slot)
                               if slot.held is not None else 0)
        elif snapshot is not None:
            self._snapshots.free(slot.entry)
        for bid in slot.blocks:
            self._alloc.decref(bid)
        if slot.held is not None:
            for wbid in slot.held.values():
                self._window.alloc.decref(wbid)
            self._window.reserved -= slot.demand
            self._window.clear(idx)
        self._bt[idx, :] = NULL_BLOCK
        self._pos[idx] = 0
        self._aid[idx] = 0
        req = slot.req
        tenant = req.gen.get("tenant")
        req.finished = time.monotonic()
        if error is not None:
            self.metrics.inc("failed")
            if tenant:
                self.metrics.tenant_inc(tenant, "failed")
            req._fail(error)
        else:
            self.metrics.inc("completed")
            self.metrics.observe_latency("e2e", req.finished - req.arrival)
            self._fold_request(req)
            if tenant:
                self.metrics.tenant_inc(tenant, "completed")
                self.metrics.tenant_inc(tenant, "tokens_out",
                                        slot.produced)
                self.metrics.tenant_observe_latency(
                    tenant, req.finished - req.arrival)
            req._complete(np.asarray(slot.tokens, np.int32))

    def _fold_request(self, req):
        """A finished request's stamps, once: into the series `ttft`,
        `prefill_req` and `itl`, and into the profiler ring as the
        request's three spans, which tile arrival -> finished."""
        took = req.timings()
        self.metrics.observe_latency("ttft", took["first_token_s"])
        self.metrics.observe_latency("prefill_req", took["prefill_s"])
        self.metrics.observe_latencies("itl", took["token_gaps_s"])
        ring = _RING_CLOCK_OFFSET
        first = req.token_times[0]
        profiler.record_span(
            "request.queue", req.arrival + ring, req.queue_wait,
            cat="request", id=req.id, queue_s=req.queue_wait)
        profiler.record_span(
            "request.prefill", req.admitted + ring, took["prefill_s"],
            cat="request", id=req.id, steps=req.prefill_steps,
            prefix_hit_tokens=req.prefix_hit_tokens)
        profiler.record_span(
            "request.decode", first + ring, req.finished - first,
            cat="request", id=req.id, token_s=req.token_times)

    def _fail_all_active(self, error):
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._evict(i, error)

    def _step(self):
        """One whole continuous-batching step, by hand: launch it, then
        land it. (The loop orders the halves the other way, `_iterate`.)
        A speculative engine drafts inside the launch and accepts
        inside the landing (speculation.py); a plain one is the round
        in which nothing was drafted."""
        with _StepSpan() as span:
            flight = self._launch(span)
            if flight is not None:
                self._land(flight, span)

    def _launch(self, span):
        """The first half of a step, all the host can do before the
        step's picks exist: take each decoding slot's landed token
        (finishing slots that hit EOS/max/deadline), stage the next
        chunk for prefilling slots and `_FROM_PICK` for a slot whose
        token is still on the device, ONE batched dispatch over the
        whole pool, then what positions alone decide: `_pos`, `fill`,
        prefill -> decode, the handle on the step's logits, state
        snapshots. Returns the `_Flight` to land, or None when nothing
        was dispatched (nothing live, or a fault at ``serving.step``)."""
        if self.mesh is not None:
            # raise here propagates to _loop like any step error: the
            # engine survives and the Router replays the in-flight work
            faults.fault_point("serving.shard_step", tag=self.name)
        if self.quantized:
            # raise here propagates to _loop like any step error
            faults.fault_point("serving.dequant")
        self._w8a8_degraded = False
        if self.w8a8:
            # a fault here degrades THIS step to the weights-only
            # dequant path (act scale 0 -> the lax.cond's plain branch
            # inside the same compiled step) — leak-free: no eviction,
            # no retrace, the step still commits its tokens
            try:
                faults.fault_point("serving.w8a8")
            except Exception:  # noqa: BLE001 — deterministic degrade
                self._w8a8_degraded = True
                self.metrics.inc("w8a8_degraded_steps")
        try:
            faults.fault_point("serving.step")
        except Exception as e:  # noqa: BLE001 — deterministic mid-decode
            self._fail_all_active(e)
            return None
        now = time.monotonic()
        tok = np.zeros((self.max_slots, self.prefill_chunk), np.int32)
        # an idle slot has no valid column
        nvalid = np.zeros((self.max_slots,), np.int32)
        live: list = []
        with observe.phase("sample", cat="serving"):
            prefill_tokens = self._consume(now, tok, nvalid, live)
        if self._flight is not None:
            # of the rows of the step in flight, each is by now staged
            # behind its pick, evicted, or waiting for its last token
            self._flight.swept = True
        if not live:
            return None
        if self._spec is not None:
            self._spec.propose(live, tok, nvalid)
        window_context = 0
        if self._window is not None:
            for i in live:
                self._move_window(i, self._slots[i], int(nvalid[i]))
                # the keys a window layer admits for the step's real
                # columns: a column's position + 1, at most the window
                first = int(self._pos[i]) + 1
                window_context += int(np.minimum(
                    np.arange(first, first + int(nvalid[i])),
                    self._window.window).sum())
        computed, context = self._columns(live, nvalid)
        decoding = sum(1 for i in live if self._slots[i].state == "decode")
        handed = self._arrays(self._pools + self._state)
        at = time.monotonic()
        span.open()
        with observe.phase("dispatch", cat="serving"):
            out = self._dispatch(tok, self._pos, nvalid)
        logits = out.pop("logits")
        # the step is handed the pools and updates them in place: the
        # arrays that went in read `is_deleted()` from the dispatch on.
        # A backend that copied instead leaves them alive, and
        # `pool_inplace_steps` behind `steps`
        flight = _Flight(
            out, at, ahead=self._flight is not None,
            inplace=all(a.is_deleted() for a in handed), live=len(live),
            decoding=decoding, prefill_tokens=prefill_tokens,
            computed=computed, context=context,
            window_context=window_context)
        for i in live:
            slot = self._slots[i]
            self._pos[i] += slot.advance
            if slot.state == "prefill":
                slot.req.prefill_steps += 1
                slot.fill += slot.advance
                if slot.fill < slot.prompt_len:
                    continue
                slot.state = "decode"
                self.metrics.inc("prefills")
            slot.flying += 1
            slot.next_logits = _LogitRow(logits, i, self.metrics)
            flight.rows.append((i, slot))
        if self._state:
            self._take_snapshots(live)
        return flight

    def _land(self, flight, span):
        """The second half of a step: the read-back of what the host
        needs of it (each slot's `pick`, what the model's step counted
        in `aux`, a speculative engine's verify logits: one blocking
        transfer, its size counted in `readback_bytes`; `logits` stay
        the device's), then the tokens and the counts. A pick lands on
        the `_Slot` it was launched for; one whose slot has gone since
        (EOS, a cancel, a deadline, a failure) is dropped. A step that
        a later launch has swept takes its tokens here, at once: that
        launch staged each of its rows behind this pick or, the pick
        being the row's last, not at all, so the host has nothing left
        to decide. Any other step leaves `next_token` to the next
        launch's sweep, which takes it and stages it."""
        import jax

        span.open()
        try:
            with observe.phase("readback", cat="serving"):
                out = jax.device_get(flight.out)
        except Exception:
            # dispatched, and its picks cannot be read: what it (and
            # any step launched on its outputs) left in place of the
            # pools is no KV to serve from
            for a in self._arrays(self._pools + self._state):
                a.delete()
            raise
        finally:
            span.close()
        done = time.monotonic()
        with observe.phase("commit", cat="serving"):
            self._observe_step_latency(
                done - max(flight.at, self._landed), flight.prefill_tokens,
                flight.decoding)
            self._landed = done
            if flight.inplace:
                self.metrics.inc("pool_inplace_steps")
            if flight.ahead:
                self.metrics.inc("steps_launched_ahead")
            self.metrics.inc("readback_bytes", sum(
                a.nbytes for a in jax.tree_util.tree_leaves(out)))
            self.metrics.inc("computed_tokens", flight.computed)
            self.metrics.inc("attn_context_tokens", flight.context)
            if flight.window_context:
                self.metrics.inc("attn_window_context_tokens",
                                 flight.window_context)
            self._count_aux(out["aux"])
            pick = out["pick"]
            for i, slot in flight.rows:
                if self._slots[i] is not slot:
                    continue
                slot.flying -= 1
                slot.next_token = int(pick[i])
                if flight.swept:
                    self._take(i, slot, done)
            if self._spec is not None:
                self._spec.commit(out["verify"], done)
            self.metrics.inc("steps")
            if flight.prefill_tokens:
                self.metrics.inc("prefill_tokens", flight.prefill_tokens)
            self.metrics.observe_occupancy(flight.live, self.max_slots)
            self.metrics.observe_blocks(self._alloc.blocks_in_use,
                                        self._alloc.usable)

    def _observe_step_latency(self, dt, prefill_tokens, n_decoding):
        """Attribute one device step to the phase-latency series: the
        time it held the device as the host sees it, from the later of
        its own dispatch and the previous step's landing to its own
        landing (dispatch to picks on the host when nothing was in
        flight before it; the period when the loop runs ahead). A step
        staging prompt tokens is a 'prefill' sample, a step with at
        least one slot decoding behind it is a 'decode' sample (a mixed
        colocated step is honestly both — decoding slots really did
        wait for the chunk-wide prefill program). These feed the decode
        p99 / prefill p50 columns the disaggregation bench compares.
        The same interval is the timeline's `device-step`, the
        productive time of `observe.goodput()`."""
        observe.timeline.add("device-step", dt)
        if prefill_tokens:
            self.metrics.observe_latency("prefill", dt)
        if n_decoding:
            self.metrics.observe_latency("decode", dt)

    def _columns(self, live, nvalid):
        """What a step's REAL columns cost, before the launch moves
        `_pos`: ``(computed_tokens, attn_context_tokens)``, the prompt
        tokens computed, not hit, plus tokens fed back, and the keys
        each of them attends (its position + 1); padding columns count
        nothing."""
        computed = context = 0
        for i in live:
            n, at = int(nvalid[i]), int(self._pos[i])
            computed += n
            context += n * at + n * (n + 1) // 2
        return computed, context

    def _count_aux(self, aux):
        """What the model's own step counted (`aux`, and the constants
        it named at trace time), into `aux_totals` and a counter of the
        same name. `expert_rows` ``[expert layers, held experts]`` also
        counts `expert_groups_run`, its non-empty (layer, expert)
        groups: what the grouped product's time is proportional to."""
        for name, value in {**aux, **self._aux_const}.items():
            value = np.asarray(value, np.int64)
            self.aux_totals[name] = self.aux_totals.get(name, 0) + value
            self.metrics.inc(name, int(value.sum()))
            if name == "expert_rows":
                self.metrics.inc("expert_groups_run", int((value > 0).sum()))

    def _take(self, i, slot, now):
        """The slot's landed token (`_pick`) joins its answer, stamped
        `now`; EOS or `max_new_tokens` ends the request here. Returns
        whether the request goes on."""
        nxt = self._pick(slot)
        slot.tokens.append(nxt)
        slot.produced += 1
        slot.req.token_times.append(now)
        self.metrics.inc("tokens_out")
        gen = slot.req.gen
        eos = gen.get("eos_token_id")
        if (eos is not None and nxt == eos) or \
                slot.produced >= gen.get("max_new_tokens", 16):
            self._evict(i)
            return False
        return True

    def _consume(self, now, tok, nvalid, live):
        """The sweep that opens a launch: evict what was cancelled or
        is past its deadline, take each decoding slot's landed token
        (`_take`), stage the next prompt chunk for prefilling slots,
        and fill the fixed [max_slots, chunk] token matrix for the
        unified dispatch. Returns the number of prompt tokens staged
        this step."""
        prefill_tokens = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            req = slot.req
            if req.cancelled:
                self.metrics.inc("cancelled")
                self._evict(i, RequestCancelled(
                    f"request {req.id} cancelled mid-decode"))
                continue
            if req.expired(now):
                self.metrics.inc("timeouts")
                self._evict(i, DeadlineExceededError(
                    f"request {req.id} deadline exceeded mid-decode "
                    f"after {slot.produced} tokens"))
                continue
            if slot.state == "prefill":
                n = min(self.prefill_chunk, slot.prompt_len - slot.fill)
                tok[i, :n] = slot.prompt[slot.fill:slot.fill + n]
                nvalid[i] = n
                slot.advance = n
                prefill_tokens += n
                live.append(i)
                continue
            if slot.flying:
                # its last token is a pick still on the device. If that
                # pick is also its last (max_new_tokens counts it), the
                # slot waits for it to land: no column
                if slot.produced + 1 >= req.gen.get("max_new_tokens", 16):
                    continue
                tok[i, 0] = _FROM_PICK
            elif slot.next_logits is None or self._take(i, slot, now):
                # (no logits: the last commit appended a token with
                # none behind it, a speculative round's resample; it
                # was counted and EOS-checked there, its KV write
                # happens now)
                tok[i, 0] = slot.tokens[-1]
            else:
                continue
            nvalid[i] = 1
            slot.advance = 1
            live.append(i)
        return prefill_tokens

    # -- serve loop ---------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return self
        self._abort.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="serving-engine", daemon=True)
        self._thread.start()
        return self

    def _beat(self):
        """One liveness heartbeat per loop iteration. The fault point
        fires only for supervised (fleet-owned) engines so a standalone
        engine's loop never consumes fleet fault occurrences; a `delay`
        action here stalls the beat (watchdog declares the replica
        dead), a `raise` kills the engine THREAD (detected as a crash)."""
        if self.supervised:
            faults.fault_point("serving.replica_heartbeat", tag=self.name)
        self.heartbeats += 1
        self.last_beat = time.monotonic()

    def _loop(self):
        guard = observe.no_retrace() if self._strict and self._warmed \
            else contextlib.nullcontext()
        with guard:
            while True:
                self._beat()
                self._drain_boundary_calls()
                if self._abort.is_set():
                    self._settle()
                    self._fail_all_active(
                        self._abort_error or RequestCancelled(
                            "server aborted (non-drain shutdown)"))
                    return
                if self.active == 0 and self.queue.depth == 0 \
                        and self._flight is None:
                    if self.queue.drained():
                        return
                    with observe.span("loop.idle", cat="serving"):
                        self.queue.wait_nonempty(0.02)
                    continue
                with observe.span("serving.loop", cat="serving"):
                    self._iterate()

    def _iterate(self):
        """One working iteration: join-at-step admission, then the next
        step is launched and the one in flight landed, in that order,
        so that the device is handed step n+1 before the host turns to
        step n's picks. Where only the host can make the next step's
        tokens the step just launched is landed too, and the iteration
        is `_step()`'s; and with nothing to launch (everything queued
        expired, or every live slot waits for its last pick) the
        iteration only lands."""
        with observe.phase("admit", cat="serving"):
            self._admit()
        if self.active == 0 and self._flight is None:
            return
        try:
            if self.supervised:
                faults.fault_point("serving.replica_step", tag=self.name)
            with _StepSpan() as span:
                new = self._launch(span)
                if self._flight is not None:
                    self._land(self._flight, span)
                self._flight = new
                if new is not None and self._host_draws():
                    self._flight = None
                    self._land(new, span)
        except Exception as e:  # noqa: BLE001 — engine stays up
            self._survive(e)

    def _host_draws(self):
        """Whether the next step needs a token that only the host can
        make, so that the step in flight has to land before it is
        staged: a live decoding slot that samples (its row is fetched
        and drawn from here), or a speculative engine (`propose` reads
        every slot's tokens)."""
        return self._spec is not None or any(
            slot is not None and slot.rng is not None
            and slot.state == "decode" for slot in self._slots)

    def _settle(self):
        """Land the step in flight, if any: whatever must see the
        engine between two steps (a boundary call, an abort, an idle
        loop) comes through here first."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        try:
            with _StepSpan() as span:
                self._land(flight, span)
        except Exception as e:  # noqa: BLE001 — engine stays up
            self._survive(e)

    def _survive(self, error):
        """A step raised, in either half: every live request fails with
        its error, a step in flight (launched on the failed one's
        outputs, or never to be read) is dropped, and the engine goes
        on. A step that raised after its dispatch took the donated
        pools with it; one that raised before it left them whole."""
        self.metrics.inc("step_errors")
        self._flight = None
        self._fail_all_active(error)
        self._recover_pools(error)

    def abandon(self, error):
        """Supervisor-side takeover of a dead/hung replica: stop the
        loop at its next boundary, fail every in-flight and queued
        request with `error` (typically `ReplicaDiedError`, which the
        fleet Router intercepts and replays elsewhere). Never joins the
        thread — a hung replica's thread may be sleeping inside an
        injected delay (or real stuck I/O) for a long time; the replica
        object is simply discarded and rebuilt."""
        self._abort_error = error
        self._abort.set()
        self.queue.close(drain=False)
        # a thread already dead (crashed loop) never reaches the abort
        # branch — sweep its stranded slots from the supervisor thread
        if self._thread is not None and not self._thread.is_alive():
            self._fail_all_active(error)

    def shutdown(self, drain=True, timeout=None):
        """Stop. drain=True finishes queued + in-flight requests first;
        drain=False sheds the queue and evicts in-flight requests at the
        next step boundary."""
        self.queue.close(drain=drain)
        if not drain:
            self._abort.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if drain and self.spill_store is not None:
            # graceful drain persists the radix cache through the SSD
            # tier, so sessions resume decode-only after a clean
            # restart (a crash only keeps what eviction already wrote)
            self.spill_cache()
