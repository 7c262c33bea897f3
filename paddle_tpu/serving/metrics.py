"""Serving metrics registry: counters, occupancy, latency percentiles.

Ref parity: the reference's serving stack exports brpc/bvar counters
(qps, latency quantiles, queue depth); here one registry aggregates the
same signals host-side and exports them as JSON.

Latency series (seconds, each a bounded FIFO window): `queue` (arrival
-> a slot), `prefill` / `decode` (one engine step, dispatch -> logits on
the host, by what the step held), `e2e` (arrival -> whole answer), and
from a request's own stamps, folded in once when it finishes: `ttft`
(arrival -> first token), `prefill_req` (a slot -> first token), `itl`
(every gap between consecutive tokens of one request). `snapshot()`
and the Prometheus text give each one's count and p50/p95/p99/max;
`latency_mark()` / `latency_since()` give a caller the samples of its
own window. The series are not spans: what the engine also puts into
the `profiler` ring is named in its own docstring.
"""

from __future__ import annotations

import json
import threading
import time

from ..framework import monitor
from ..utils.stats import percentile  # noqa: F401  (shared quantile math)

__all__ = ["ServingMetrics", "percentile"]

# keep at most this many samples per latency series (fifo window) so a
# long-lived server doesn't grow without bound
_MAX_SAMPLES = 65536


class ServingMetrics:
    """Thread-safe counters + occupancy + latency series.

    Counter names mirror the admission queue's (`submitted`, `accepted`,
    `rejected_queue_full`, `rejected_closed`, `timeouts`, `cancelled`)
    plus engine-side `completed`, `failed`, `steps`, `batches`,
    `tokens_out`, `prefills`, and the paged-KV set: `prefill_tokens`
    (prompt positions written by chunked prefill), `prompt_tokens` /
    `prefix_lookups` / `prefix_hit_blocks` / `prefix_hit_tokens` /
    `cow_splits` (prefix-cache traffic), `rejected_capacity` (429 sheds
    whose block demand exceeds the pool), the recurrent-state set of a
    layout with per-slot state arrays: `state_snapshots_taken` (a live
    slot's state copied into its entry at a block boundary),
    `state_snapshot_hits` (admissions restored from a recorded
    snapshot), `state_resets` (admissions that started from zero),
    `state_snapshot_evictions` (recorded snapshots dropped by reclaim
    or for want of a free entry) and `prefix_tokens_lost_to_state`
    (tokens whose blocks matched but lay deeper than any snapshot, so
    were computed again), `pool_inplace_steps` (steps
    whose donated KV pools were updated in place: equals `steps`) and
    `pool_rebuilds` (a program raised after it was handed the pools;
    the engine went on with empty ones), what crosses from the device
    a step: `device_picks` (tokens committed from the compiled step's
    own pick, no logits read), `logit_rows_fetched` (rows of a step's
    logits brought to the host, one at a time, for a sampling request
    or a check) and `readback_bytes` (bytes of the step's outputs the
    host read, summed over steps), what the loop's one step in flight
    did: `steps_launched_ahead` (steps dispatched while the step before
    them was still unread; of `steps`) and `columns_wasted` (a row's
    column whose pick was dropped because the request had ended by the
    time it landed: EOS, a cancel, a deadline or a failure seen a step
    late), and the fast-decode set:
    `spec_drafted_tokens` / `spec_accepted_tokens` /
    `spec_rejected_tokens` / `spec_rounds` / `spec_draft_faults`
    (speculative decoding, fed via `observe_spec`, surfaced under
    snapshot()["speculative"] with per-slot acceptance rates and the
    `dequant_path` gauge). The fleet (fleet.py) adds its
    own family over the same registry: `fleet_submitted` /
    `fleet_completed` / `fleet_failed` (client-level, exactly-once),
    `routed`, `retries`, `replays`, `hedges`, `hedge_wins`,
    `duplicates_suppressed`, `stale_attempts`, `parked`,
    `replica_deaths`, `replica_restarts`, `brownout_entries`,
    `brownout_sheds`, `retry_budget_exhausted`, `supervisor_errors`,
    and the elastic set: `replicas_added` / `replicas_removed` (scale
    events that landed), `drains_started`, `drain_errors`,
    `scale_failures` (autoscaler actions that raised). Mesh-sharded
    serving adds `kv_migrations` / `kv_migrate_blocks` /
    `kv_migrate_bytes` / `kv_migrate_faults` (prefill->decode KV block
    streaming) surfaced with the mesh shape, per-shard occupancy and
    disaggregation role under snapshot()["mesh"] (see `note_mesh` /
    `note_role`). The persistent KV tier (kvstore.py) adds
    `kv_spilled_blocks` / `kv_restored_blocks` / `kv_invalidated_blocks`
    / `kv_spill_bytes` / `kv_restore_corrupt` / `kv_restore_fenced` /
    `kv_spill_errors`, surfaced under snapshot()["kvstore"], and the
    prefix-affinity Router adds `affinity_hits` / `affinity_faults`.
    Multi-tenant serving bills per-tenant counters/latency/gauges via
    `tenant_inc` / `tenant_observe_latency` / `tenant_set_gauge`,
    surfaced under snapshot()["tenants"] and the paddle_tenant_*
    Prometheus families (qps, tokens, shed, p50/p95/p99, budget).
    Every inc() also bumps the global `framework.monitor` counter
    ``serving.<name>`` so serving shows up in the same stat registry as
    the rest of the runtime.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict = {}
        self._latency: dict = {}      # kind -> [seconds]
        self._latency_seen: dict = {}  # kind -> samples ever observed
        self._mesh = None             # (spec, devices) when mesh-sharded
        self._role = None             # disagg role ('prefill'/'decode')
        self._occ_sum = 0.0
        self._occ_n = 0
        self._occ_max = 0.0
        self._blk_last = (0, 0)       # (in_use, total) at last step
        self._blk_sum = 0.0
        self._blk_n = 0
        self._blk_max = 0.0
        self._gauges: dict = {}       # name -> float (last-write-wins)
        self._spec_slots: dict = {}   # slot -> [drafted, accepted]
        # per-tenant accounting (ISSUE 20): tenant name ->
        # {"counters": {...}, "latency": [s], "gauges": {...}} — fed by
        # tenant_inc/tenant_observe_latency/tenant_set_gauge, surfaced
        # under snapshot()["tenants"] and the paddle_tenant_* Prometheus
        # families. Created lazily; absent in single-tenant serving.
        self._tenants: dict = {}
        self._started = time.monotonic()

    def set_gauge(self, name, value):
        """Last-write-wins scalar (e.g. `dequant_path` = 1.0 while an
        int8-frozen engine serves)."""
        with self._lock:
            self._gauges[name] = float(value)

    def note_mesh(self, spec, devices):
        """Record the serving mesh shape (e.g. 'dp1.mp2' over 2
        devices): turns on the snapshot()['mesh'] section and the
        paddle_serving_mesh_* Prometheus family."""
        with self._lock:
            self._mesh = (str(spec), int(devices))

    def note_role(self, role):
        """Disaggregation role of the replica this registry serves
        ('any' / 'prefill' / 'decode') — surfaced as the mesh-family
        role gauge."""
        with self._lock:
            self._role = str(role)

    def observe_spec(self, slot, drafted, accepted):
        """One speculative round's outcome for one slot: `drafted`
        proposals went into the verify step, `accepted` survived.
        Feeds the spec_* counters and the per-slot acceptance gauges."""
        with self._lock:
            cell = self._spec_slots.setdefault(int(slot), [0, 0])
            cell[0] += int(drafted)
            cell[1] += int(accepted)
        self.inc("spec_drafted_tokens", int(drafted))
        self.inc("spec_accepted_tokens", int(accepted))
        self.inc("spec_rejected_tokens", int(drafted) - int(accepted))

    def inc(self, name, n=1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n
        monitor.stat_add(f"serving.{name}", n)

    # -- per-tenant accounting (ISSUE 20) -----------------------------------

    def _tenant_cell(self, tenant):
        # caller holds self._lock
        cell = self._tenants.get(tenant)
        if cell is None:
            cell = {"counters": {}, "latency": [], "gauges": {}}
            self._tenants[str(tenant)] = cell
        return cell

    def tenant_inc(self, tenant, name, n=1):
        """Bump one tenant-scoped counter (`submitted`, `accepted`,
        `shed`, `completed`, `failed`, `tokens_out`, ...)."""
        if tenant is None:
            return
        with self._lock:
            c = self._tenant_cell(tenant)["counters"]
            c[name] = c.get(name, 0) + n
        monitor.stat_add(f"serving.tenant.{tenant}.{name}", n)

    def tenant_observe_latency(self, tenant, seconds):
        """One end-to-end latency sample billed to `tenant`."""
        if tenant is None:
            return
        with self._lock:
            series = self._tenant_cell(tenant)["latency"]
            series.append(float(seconds))
            if len(series) > _MAX_SAMPLES:
                del series[:len(series) - _MAX_SAMPLES]

    def tenant_set_gauge(self, tenant, name, value):
        """Last-write-wins tenant-scoped scalar (e.g. remaining token
        budget)."""
        if tenant is None:
            return
        with self._lock:
            self._tenant_cell(tenant)["gauges"][name] = float(value)

    def tenant_get(self, tenant, name):
        with self._lock:
            cell = self._tenants.get(tenant)
            return cell["counters"].get(name, 0) if cell else 0

    def tenant_latency_percentiles(self, tenant, ps=(50, 95, 99)):
        with self._lock:
            cell = self._tenants.get(tenant)
            series = list(cell["latency"]) if cell else []
        if not series:
            return {p: None for p in ps}
        return {p: percentile(series, p) for p in ps}

    def get(self, name):
        with self._lock:
            return self._counters.get(name, 0)

    def observe_latency(self, kind, seconds):
        self.observe_latencies(kind, (seconds,))

    def observe_latencies(self, kind, samples):
        """Several samples of one series under one lock (a finished
        request's token gaps)."""
        samples = [float(s) for s in samples]
        with self._lock:
            series = self._latency.setdefault(kind, [])
            series.extend(samples)
            self._latency_seen[kind] = \
                self._latency_seen.get(kind, 0) + len(samples)
            if len(series) > _MAX_SAMPLES:
                del series[:len(series) - _MAX_SAMPLES]

    def latency_mark(self):
        """A mark for `latency_since`: how many samples each series has
        seen so far (a count, so it survives the FIFO trim)."""
        with self._lock:
            return dict(self._latency_seen)

    def latency_since(self, mark, kind):
        """The samples of series `kind` observed after `mark` was
        taken, oldest first; those the FIFO window has already dropped
        are gone."""
        with self._lock:
            n = self._latency_seen.get(kind, 0) - mark.get(kind, 0)
            series = self._latency.get(kind, ())
            return list(series[max(len(series) - n, 0):]) if n > 0 else []

    def observe_occupancy(self, active, capacity):
        """One decode-step sample of slot utilisation (active/capacity)."""
        frac = active / max(capacity, 1)
        with self._lock:
            self._occ_sum += frac
            self._occ_n += 1
            self._occ_max = max(self._occ_max, frac)

    def observe_blocks(self, in_use, total):
        """One decode-step sample of KV block-pool utilisation."""
        frac = in_use / max(total, 1)
        with self._lock:
            self._blk_last = (int(in_use), int(total))
            self._blk_sum += frac
            self._blk_n += 1
            self._blk_max = max(self._blk_max, frac)

    def latency_percentiles(self, kind, ps=(50, 95, 99), last=None):
        """{p: seconds} over the recorded `kind` series. ``last``
        restricts to the most recent N samples — the autoscaler's
        sliding SLO window, so old congestion doesn't pin the signal
        high after the fleet recovers."""
        with self._lock:
            series = list(self._latency.get(kind, ()))
        if last is not None:
            series = series[-int(last):]
        if not series:
            return {p: None for p in ps}
        return {p: percentile(series, p) for p in ps}

    def snapshot(self, queue_depth=None):
        """One JSON-able view: counters, QPS, tokens/s, occupancy,
        p50/p95/p99 per latency series."""
        with self._lock:
            counters = dict(self._counters)
            latency = {k: list(v) for k, v in self._latency.items()}
            occ_avg = self._occ_sum / self._occ_n if self._occ_n else 0.0
            occ_max = self._occ_max
            blk_last, blk_n = self._blk_last, self._blk_n
            blk_avg = self._blk_sum / self._blk_n if self._blk_n else 0.0
            blk_max = self._blk_max
            elapsed = max(time.monotonic() - self._started, 1e-9)
        snap = {
            "counters": counters,
            "uptime_s": elapsed,
            "qps": counters.get("completed", 0) / elapsed,
            "tokens_per_s": counters.get("tokens_out", 0) / elapsed,
            "batch_occupancy": {"avg": occ_avg, "max": occ_max,
                                "samples": self._occ_n},
            "latency_s": {},
        }
        if blk_n:
            snap["kv_blocks"] = {
                "in_use": blk_last[0], "total": blk_last[1],
                "occupancy": blk_avg, "occupancy_max": blk_max,
                "samples": blk_n,
            }
        if counters.get("prefix_lookups"):
            prompt = counters.get("prompt_tokens", 0)
            hit = counters.get("prefix_hit_tokens", 0)
            snap["prefix_cache"] = {
                "lookups": counters["prefix_lookups"],
                "hit_blocks": counters.get("prefix_hit_blocks", 0),
                "hit_tokens": hit,
                "prompt_tokens": prompt,
                "hit_rate": hit / prompt if prompt else 0.0,
            }
        if counters.get("kv_spilled_blocks") \
                or counters.get("kv_restored_blocks") \
                or counters.get("kv_invalidated_blocks") \
                or counters.get("kv_restore_corrupt"):
            snap["kvstore"] = {
                "spilled_blocks": counters.get("kv_spilled_blocks", 0),
                "restored_blocks": counters.get("kv_restored_blocks", 0),
                "invalidated_blocks":
                    counters.get("kv_invalidated_blocks", 0),
                "spill_bytes": counters.get("kv_spill_bytes", 0),
                "restore_corrupt": counters.get("kv_restore_corrupt", 0),
                "restore_fenced": counters.get("kv_restore_fenced", 0),
                "spill_errors": counters.get("kv_spill_errors", 0),
            }
        if counters.get("prefill_tokens"):
            steps = counters.get("steps", 0)
            snap["chunked_prefill"] = {
                "tokens": counters["prefill_tokens"],
                "tokens_per_step":
                    counters["prefill_tokens"] / steps if steps else 0.0,
            }
        with self._lock:
            gauges = dict(self._gauges)
            spec_slots = {k: tuple(v) for k, v in self._spec_slots.items()}
        if counters.get("spec_drafted_tokens") or spec_slots \
                or gauges.get("dequant_path"):
            drafted = counters.get("spec_drafted_tokens", 0)
            accepted = counters.get("spec_accepted_tokens", 0)
            snap["speculative"] = {
                "drafted_tokens": drafted,
                "accepted_tokens": accepted,
                "rejected_tokens": counters.get("spec_rejected_tokens", 0),
                "rounds": counters.get("spec_rounds", 0),
                "draft_faults": counters.get("spec_draft_faults", 0),
                "acceptance_rate": accepted / drafted if drafted else 0.0,
                "per_slot_acceptance": {
                    str(s): a / d if d else 0.0
                    for s, (d, a) in sorted(spec_slots.items())},
                "dequant_path": gauges.get("dequant_path", 0.0),
            }
        model = {k: gauges[k] for k in ("kv_bytes_per_token",
                                         "weight_bytes", "experts_held",
                                         "state_bytes_per_slot",
                                         "snapshot_entries")
                 if k in gauges}
        if model:
            # what the served model holds: cache bytes a token over all
            # layers, weight bytes on the device, routed experts held,
            # and for a layout with per-slot state arrays the bytes of
            # one slot's state and the entries of the snapshot pool
            snap["model"] = model
        with self._lock:
            mesh, role = self._mesh, self._role
        if mesh is not None or role is not None \
                or counters.get("kv_migrations") \
                or counters.get("kv_migrate_faults"):
            spec, devices = mesh if mesh is not None else ("", 1)
            snap["mesh"] = {
                "spec": spec,
                "devices": devices,
                "role": role or "any",
                # GSPMD runs the SAME program on every shard, so each
                # shard's slot occupancy equals the replica's — emitted
                # per shard anyway so a future uneven layout shows up
                "per_shard_occupancy": [
                    {"shard": i, "occupancy": occ_avg}
                    for i in range(devices)],
                "kv_migrations": counters.get("kv_migrations", 0),
                "kv_migrate_blocks": counters.get("kv_migrate_blocks", 0),
                "kv_migrate_bytes": counters.get("kv_migrate_bytes", 0),
                "kv_migrate_faults": counters.get("kv_migrate_faults", 0),
            }
        with self._lock:
            tenants = {
                t: {"counters": dict(c["counters"]),
                    "latency": list(c["latency"]),
                    "gauges": dict(c["gauges"])}
                for t, c in self._tenants.items()}
        if tenants:
            snap["tenants"] = {}
            for t in sorted(tenants):
                cell = tenants[t]
                c, series = cell["counters"], cell["latency"]
                entry = {
                    "counters": c,
                    "qps": c.get("completed", 0) / elapsed,
                    "tokens_per_s": c.get("tokens_out", 0) / elapsed,
                    "gauges": cell["gauges"],
                }
                if series:
                    entry["latency_s"] = {
                        "count": len(series),
                        "p50": percentile(series, 50),
                        "p95": percentile(series, 95),
                        "p99": percentile(series, 99),
                        "max": max(series),
                    }
                snap["tenants"][t] = entry
        if queue_depth is not None:
            snap["queue_depth"] = queue_depth
        for kind, series in latency.items():
            if series:
                snap["latency_s"][kind] = {
                    "count": len(series),
                    "p50": percentile(series, 50),
                    "p95": percentile(series, 95),
                    "p99": percentile(series, 99),
                    "max": max(series),
                }
        return snap

    def to_json(self, queue_depth=None, **dump_kw):
        return json.dumps(self.snapshot(queue_depth=queue_depth),
                          **dump_kw)
