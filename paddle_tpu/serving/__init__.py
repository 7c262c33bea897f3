"""paddle_tpu.serving — request-level inference runtime.

Ref parity: paddle/fluid/inference/api/ (AnalysisPredictor zero-copy
run loop, paddle_infer::services::PredictorPool) plus the serving shell
the reference deploys around it. The TPU-native redesign is
iteration-level ("continuous") batching in the Orca lineage:

- `AdmissionQueue` — bounded queue, per-request deadline, fast 429-style
  shed on overload, graceful drain (queueing.py);
- `DynamicBatcher` — coalesces concurrent requests into shape-bucketed,
  padded batches; every bucket compiles exactly once (batcher.py);
- `SlotEngine` — continuous-batching GPT decode over a block-paged KV
  cache (vLLM-style block tables + SGLang-style radix prefix sharing,
  paging.py) with chunked prefill folded into one compiled step,
  join-at-step admission by free blocks, and eviction on
  EOS/max-len/deadline — plus fast decode: draft-model speculative
  decoding with rejection sampling (FLAGS_serving_spec_len) and an
  int8 frozen-weight path through a dequant-matmul epilogue
  (FLAGS_serving_quantize) (engine.py);
- `ServingMetrics` — QPS, queue depth, batch occupancy, latency
  percentiles; JSON-exportable, spans mirrored into the profiler's
  chrome trace (metrics.py);
- `ReplicaSet` / `Router` — the resilient fleet: N supervised engine
  replicas with heartbeat watchdogs and backed-off restarts, fronted
  by failover replay, budgeted retries, hedging, per-replica circuit
  breakers, and brownout shedding (fleet.py) — now elastic:
  `add_replica`/`remove_replica(drain=True)` scale membership under
  load with zero lost/duplicated requests;
- `Autoscaler` — grows/shrinks the fleet from the SLO error budget
  (windowed p99 vs FLAGS_fleet_slo_p99_ms, utilisation watermarks,
  brownout) with hysteresis + cooldown (autoscale.py);
- `WeightRegistry` / `RolloutController` — zero-downtime model
  rollout: versioned checkpoint ingestion with READABLE/checksum
  gates, rolling canary upgrades through drain→rebuild, golden-prompt
  bitwise + SLO burn gates, and auto-rollback to the pinned previous
  version (rollout.py);
- `ShardingPlan` / `match_partition_rules` — mesh-sharded serving:
  partition-rule-driven TP/GSPMD weight + paged-KV sharding over a
  (dp, mp) device mesh, reusing the training Column/RowParallel
  layout conventions (sharding.py, FLAGS_serving_mesh);
- `KVMailbox` / `migrate_prefix` — disaggregated prefill/decode:
  deadline-guarded prefill→decode KV-block streaming behind the
  Router (migrate.py, FLAGS_serving_disagg);
- `KVSpillStore` / `open_spill_store` — the global KV fabric: cold
  KV blocks spill to a crash-safe, crc-framed SSD tier on eviction
  and restore on session resume through the all-or-nothing admission
  path; weight-rollout commits generation-fence stale records
  (`SpillFencedError`), and the Router's prefix-affinity routing
  steers each request to the replica holding the longest live prefix
  match (kvstore.py, FLAGS_serving_kv_spill_dir,
  FLAGS_serving_prefix_affinity);
- `TenantDirectory` / `TenantFairQueue` / `ArtifactCatalog` /
  `AdapterRollout` — the multi-tenant platform: batched LoRA adapter
  banks inside the one compiled decode step (``submit(...,
  adapter_id=k)``, hot-swapped with zero retraces through the
  rollout-commit path), a catalog of named (model, adapter, version)
  artifacts with sha256 manifests, weighted-fair (deficit round
  robin) per-tenant admission with token budgets, SLO classes, and
  tier-based brownout shedding (tenancy.py, queueing.py,
  FLAGS_serving_max_adapters, FLAGS_tenant_default_budget);
- `Scenario` / `Arrival` / `replay` — the seeded open-loop traffic
  simulator every serving bench replays (workload.py);
- `Server` / `http_front` — the user-facing shell (server.py);
  ``Server(model, replicas=2)`` serves through the fleet.

Everything runs and certifies on CPU (`JAX_PLATFORMS=cpu`) with
thread-based clients; no network required.
"""

from .autoscale import Autoscaler  # noqa: F401
from .batcher import (  # noqa: F401
    DynamicBatcher, bucket_for, bucket_ladder, pad_batch,
)
from .engine import SlotEngine  # noqa: F401
from .fleet import (  # noqa: F401
    CircuitBreaker, Replica, ReplicaSet, Router, retriable,
)
from .kvstore import (  # noqa: F401
    KVSpillStore, SpillFencedError, open_spill_store, reset_spill_stores,
)
from .metrics import ServingMetrics, percentile  # noqa: F401
from .migrate import KVMailbox, migrate_prefix  # noqa: F401
from .paging import (  # noqa: F401
    NULL_BLOCK, BlockAllocator, BlockGroup, CacheLayout, PoolExhausted,
    PrefixCache, positions_to_rows,
)
from .queueing import (  # noqa: F401
    AdmissionQueue, BrownoutShedError, CapacityExhaustedError,
    DeadlineExceededError, QueueFullError, ReplicaDiedError, Request,
    RequestCancelled, RetriesExhaustedError, ServerClosedError,
    ServingError, TenantBudgetError, TenantFairQueue,
    VersionRetiredError,
)
from .rollout import (  # noqa: F401
    RolloutController, RolloutError, RolloutGateError, WeightRegistry,
    WeightVersion, golden_digests,
)
from .autoscale import SLOWindow  # noqa: F401
from .server import Server, http_front  # noqa: F401
from .tenancy import (  # noqa: F401
    DEFAULT_TENANT, AdapterRollout, Artifact, ArtifactCatalog,
    TenantDirectory, TenantSpec,
)
from .sharding import (  # noqa: F401
    GPT_PARTITION_RULES, ShardingPlan, build_mesh, match_partition_rules,
    mesh_spec_of, parse_mesh_spec, resolve_mesh,
)
from .workload import Arrival, Scenario, replay  # noqa: F401

__all__ = [
    "AdapterRollout", "AdmissionQueue", "Arrival", "Artifact",
    "ArtifactCatalog", "Autoscaler", "BlockAllocator",
    "BlockGroup", "BrownoutShedError", "CacheLayout",
    "CapacityExhaustedError", "CircuitBreaker", "DEFAULT_TENANT",
    "DeadlineExceededError",
    "DynamicBatcher", "GPT_PARTITION_RULES", "KVMailbox", "KVSpillStore",
    "NULL_BLOCK",
    "PoolExhausted", "PrefixCache",
    "QueueFullError", "Replica", "ReplicaDiedError", "ReplicaSet",
    "Request", "RequestCancelled", "RetriesExhaustedError",
    "RolloutController", "RolloutError", "RolloutGateError", "Router",
    "SLOWindow", "Scenario", "Server", "ServerClosedError",
    "ServingError", "ServingMetrics", "ShardingPlan", "SlotEngine",
    "SpillFencedError", "TenantBudgetError", "TenantDirectory",
    "TenantFairQueue", "TenantSpec", "VersionRetiredError",
    "WeightRegistry", "WeightVersion",
    "bucket_for", "bucket_ladder", "build_mesh", "golden_digests",
    "http_front", "match_partition_rules", "mesh_spec_of",
    "migrate_prefix", "open_spill_store",
    "pad_batch", "parse_mesh_spec", "percentile", "positions_to_rows",
    "replay", "reset_spill_stores", "resolve_mesh", "retriable",
]
