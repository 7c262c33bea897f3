"""In-process serving front: one object tying admission control, the
continuous-batching engine (or the dynamic batcher), and metrics.

Ref parity: paddle/fluid/inference/api + paddle_serving's server shell —
`Server` plays the role of the predictor-pool-plus-brpc-service pair,
collapsed to a thread-safe `submit()/result()` API so it runs anywhere
(CPU tier-1 included) with no network dependency. `http_front` is the
optional stdlib front door mapping the same API onto HTTP.

    cfg = GPTConfig(..., use_parallel=False)
    model = GPTForPretraining(cfg)
    with serving.Server(model, max_slots=4) as srv:
        fut = srv.submit([1, 2, 3], max_new_tokens=8)
        ids = fut.result()              # np.int32 [prompt + generated]
        print(srv.snapshot()["qps"])

Pass ``replicas=N`` (N >= 2) to serve through the resilient fleet
(fleet.Router): N supervised engine replicas with failover replay,
retries, hedging, circuit breakers, and brownout shedding — same
`submit()/generate()` API, plus `priority=` on submit. Extra Router
knobs ride in ``fleet=dict(...)``.
"""

from __future__ import annotations

import json
import threading

import numpy as np

from ..framework.flags import flag
from .batcher import DynamicBatcher
from .engine import SlotEngine
from .metrics import ServingMetrics
from .queueing import ServingError

__all__ = ["Server", "http_front"]


class Server:
    """Serving front over a model.

    mode="generate" (default): `model` is a GPTForPretraining; requests
    are prompts and the backend is the continuous-batching `SlotEngine`.
    mode="batch": `fn` is a batch function (or pass a jax-traceable
    callable as `model`); requests are single samples coalesced by the
    `DynamicBatcher`.

    Fast-decode knobs (forwarded to every engine, single or fleet):
    ``spec_len``/``draft_model`` enable speculative decoding (self-draft
    when no draft model is given), ``quantize`` freezes weights to int8
    for the dequant decode path. Defaults come from
    FLAGS_serving_spec_len / FLAGS_serving_quantize.

    Mesh-sharded serving: ``mesh='dpD.mpM'`` (or a prebuilt Mesh;
    default FLAGS_serving_mesh) shards every engine's weights and paged
    KV pool over a (dp, mp) device mesh via serving/sharding.py. Fleet
    mode composes with disaggregated prefill/decode — pass
    ``fleet=dict(roles=[...], role_kw={...}, disagg=True)``.

    Durable sessions: ``spill_dir=`` (default
    FLAGS_serving_kv_spill_dir) turns on the persistent SSD KV tier —
    every engine of the server spills evicted prefix-cache blocks
    there and restores them on session resume (serving/kvstore.py);
    fleet mode pairs it with prefix-affinity routing
    (FLAGS_serving_prefix_affinity or
    ``fleet=dict(prefix_affinity=...)``).

    A model whose cache layout keeps per-slot state arrays (recurrent
    layers) gets ``snapshot_entries=`` entries of state snapshot pool
    (default three a slot): what lets a session's next turn resume.

    Multi-tenant serving: ``max_adapters=N`` gives every engine an
    N-row batched LoRA adapter bank (``submit(..., adapter_id=k)``;
    row 0 = base model) and ``tenancy=TenantDirectory(...)`` switches
    admission to weighted-fair per-tenant queues with token budgets
    and tier-based brownout (``submit(..., tenant=name)``).
    """

    def __init__(self, model=None, *, mode="generate", fn=None,
                 max_slots=None, max_seq_len=None, block_size=None,
                 num_blocks=None, prefill_chunk=None, prefix_cache=None,
                 queue_cap=None, max_batch=None, max_wait_s=0.002,
                 cache_dtype=None, jit=True, strict_shapes=False,
                 warmup=True, replicas=1, fleet=None, spec_len=None,
                 draft_model=None, quantize=None, w8a8=None, mesh=None,
                 spill_dir=None, max_adapters=None, lora_rank=None,
                 tenancy=None, snapshot_entries=None):
        self.mode = mode
        self.metrics = ServingMetrics()
        self._warmup = warmup
        self.router = None
        self.tenancy = tenancy
        if mode == "generate":
            if model is None:
                raise ValueError("generate mode needs a GPT model")
            # every engine's options, the one engine's or each replica's
            engine_kw = dict(
                max_slots=max_slots, max_seq_len=max_seq_len,
                block_size=block_size, num_blocks=num_blocks,
                prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                cache_dtype=cache_dtype, strict_shapes=strict_shapes,
                spec_len=spec_len, draft_model=draft_model,
                quantize=quantize, w8a8=w8a8, mesh=mesh,
                spill_dir=spill_dir, max_adapters=max_adapters,
                lora_rank=lora_rank, snapshot_entries=snapshot_entries)
            self.batcher = None
        if mode == "generate" and (replicas > 1 or fleet is not None):
            from .fleet import Router

            fleet_kw = dict(fleet or {})
            if tenancy is not None:
                fleet_kw.setdefault("tenancy", tenancy)
            self.router = Router(
                model, max(replicas, 1), engine_kw=engine_kw,
                metrics=self.metrics, queue_cap=queue_cap,
                warmup=warmup, **fleet_kw)
            self.engine = None
        elif mode == "generate":
            from .queueing import AdmissionQueue, TenantFairQueue

            cap = queue_cap or flag("FLAGS_serving_queue_cap")
            if tenancy is not None:
                queue = TenantFairQueue(cap, tenancy=tenancy,
                                        metrics=self.metrics)
            else:
                queue = AdmissionQueue(cap, metrics=self.metrics)
            self.engine = SlotEngine(model, metrics=self.metrics,
                                     queue=queue, **engine_kw)
        elif mode == "batch":
            target = fn if fn is not None else model
            if target is None or not callable(target):
                raise ValueError("batch mode needs a callable fn")
            self.batcher = DynamicBatcher(
                target, max_batch=max_batch, max_wait_s=max_wait_s,
                queue_cap=queue_cap, metrics=self.metrics, jit=jit)
            self.engine = None
        else:
            raise ValueError(f"unknown serving mode {mode!r}")
        self._started = False

    @classmethod
    def from_router(cls, router):
        """Wrap an already-built (and possibly already-started) fleet
        Router so `http_front` / `version_info()` / `snapshot()` serve
        it — the rollout tests drive a Router directly and still want
        the HTTP surface. The wrapper shares the Router's metrics and
        never owns lifecycle beyond forwarding start/shutdown."""
        srv = cls.__new__(cls)
        srv.mode = "generate"
        srv.metrics = router.metrics
        srv._warmup = False
        srv.router = router
        srv.engine = None
        srv.batcher = None
        srv._started = router._sup is not None
        return srv

    @classmethod
    def from_predictor(cls, predictor, **kw):
        """Batch-mode server over an inference.Predictor's loaded
        program (shares its weights; the exported program manages its
        own compilation, so jit wrapping is off)."""
        layer = predictor._layer

        def fn(x):
            out = layer(x)
            return out._value if hasattr(out, "_value") else out

        kw.setdefault("jit", False)
        return cls(fn=fn, mode="batch", **kw)

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        if not self._started:
            if self.router is not None:
                self.router.start()
            else:
                if self.engine is not None and self._warmup \
                        and not self.engine._warmed:
                    self.engine.warmup()
                (self.engine or self.batcher).start()
            self._started = True
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)
        return False

    def shutdown(self, drain=True):
        """Graceful drain (finish queued + in-flight work) or fast stop
        (shed the queue, evict in-flight at the next step). Idempotent:
        a server never started — or already shut down — is a no-op, so
        double-shutdown (e.g. an explicit call inside a `with` block)
        never re-runs drain against stopped backends."""
        if not self._started:
            return
        self._started = False
        if self.router is not None:
            self.router.shutdown(drain=drain)
        elif self.engine is not None:
            self.engine.shutdown(drain=drain)
        else:
            self.batcher.close(drain=drain)

    # -- request API --------------------------------------------------------

    @property
    def queue(self):
        """The single backend's admission queue (engine/batcher modes).
        Fleet mode has one queue per replica — use `queue_depth()`."""
        backend = self.engine or self.batcher
        if backend is None:
            raise AttributeError(
                "fleet mode has a queue per replica; use queue_depth()")
        return backend.queue

    def queue_depth(self):
        if self.router is not None:
            return self.router.queue_depth
        return (self.engine or self.batcher).queue.depth

    def submit(self, payload, **kw):
        """Admit one request; returns a `Request` future. Generate mode
        takes a 1-D prompt + generation kwargs (plus `priority=` in
        fleet mode); batch mode one sample."""
        if not self._started:
            self.start()
        if self.router is not None:
            return self.router.submit(payload, **kw)
        if self.engine is not None:
            return self.engine.submit(payload, **kw)
        return self.batcher.submit(payload, **kw)

    def generate(self, prompt_ids, timeout=None, **kw):
        """Synchronous submit+wait."""
        return self.submit(prompt_ids, **kw).result(timeout)

    def snapshot(self):
        snap = self.metrics.snapshot(queue_depth=self.queue_depth())
        if self.router is not None:
            snap["fleet"] = self.router.snapshot()
        return snap

    def version_info(self):
        """Model-version view: current/previous version ids, rollout
        state, and the per-replica version map (`GET /v1/version` over
        `http_front` returns exactly this). Fleet mode delegates to the
        Router (which folds in an attached `RolloutController`); a
        single-engine server is always `static` on its build version."""
        if self.router is not None:
            return self.router.version_info()
        if self.engine is not None:
            return {"current": self.engine.weight_version,
                    "previous": None, "target": None,
                    "state": "static", "error": None,
                    "versions_live": [self.engine.weight_version],
                    "replicas": {self.engine.name:
                                 self.engine.weight_version}}
        return {"current": 0, "previous": None, "target": None,
                "state": "static", "error": None,
                "versions_live": [], "replicas": {}}

    def metrics_json(self, **kw):
        return json.dumps(self.snapshot(), **kw)

    def metrics_prometheus(self):
        """Prometheus text exposition of this server's metrics unified
        with the global monitor/timeline/goodput registries
        (observe.prometheus_text); fleet mode adds the per-replica
        state/restart/breaker gauges."""
        from .. import observe

        fleet = self.router.snapshot() if self.router is not None else None
        return observe.prometheus_text(serving=self.metrics,
                                       queue_depth=self.queue_depth(),
                                       fleet=fleet)


def http_front(server: Server = None, host="127.0.0.1", port=0, *,
               ranker=None):
    """Optional stdlib front door (bonus deliverable — the in-process
    API above is the contract). POST /v1/generate with a JSON body
    ``{"prompt": [ids...], "max_new_tokens": n, ...}`` returns
    ``{"ids": [...]}``; GET /metrics returns the snapshot and
    GET /v1/version the model-version view (current/previous ids,
    rollout state, per-replica version map). Serving errors map to
    their HTTP status (429 shed, 504 deadline, 503 version retired,
    ...), with a ``Retry-After`` backoff hint on 429/503. Requests may
    carry a tenant identity as an ``X-Tenant`` header or a ``tenant``
    body field (on both /v1/generate and /v1/rank); a tenant over its
    token budget gets a per-tenant 429 whose ``Retry-After`` is that
    tenant's own bucket refill time.

    Pass ``ranker=`` (a `rec.RankingService`) to also serve
    POST /v1/rank: ``{"dnn_ids": [...], "lr_ids": [...]}`` (wide&deep)
    or ``{"fields": [...]}`` (DeepFM) returns ``{"scores": [...]}``;
    2-D id arrays rank a whole candidate list in one call (the rows
    coalesce in the dynamic batcher). A front may serve both a `server`
    and a `ranker`; at least one is required.

    Returns the started `ThreadingHTTPServer`; its bound port is
    ``httpd.server_address[1]``. Call ``httpd.shutdown()`` to stop."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    if server is None and ranker is None:
        raise ValueError("http_front needs a server and/or a ranker")
    metrics_src = server if server is not None else ranker

    def rank_scores(req):
        timeout = req.pop("timeout", None)
        if "fields" in req:
            arrs = [np.asarray(req.pop("fields"), np.int64)]
        else:
            arrs = [np.asarray(req.pop("dnn_ids"), np.int64),
                    np.asarray(req.pop("lr_ids"), np.int64)]
        if arrs[0].ndim == 2:
            futs = [ranker.submit(*[a[i] for a in arrs], timeout=timeout)
                    for i in range(arrs[0].shape[0])]
            return [float(np.asarray(f.result(timeout)).reshape(-1)[0])
                    for f in futs]
        return [ranker.rank(*arrs, timeout=timeout)]

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def _reply(self, code, obj, headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _reply_text(self, code, text,
                        ctype="text/plain; version=0.0.4; charset=utf-8"):
            body = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path, _, query = self.path.partition("?")
            if path == "/metrics":
                # content negotiation: JSON snapshot by default (the
                # original contract — a bare GET keeps working), the
                # Prometheus exposition when a scraper asks for it via
                # Accept: text/plain / openmetrics or ?format=prometheus
                accept = self.headers.get("Accept", "")
                if ("format=prometheus" in query
                        or "text/plain" in accept
                        or "openmetrics" in accept):
                    self._reply_text(200, metrics_src.metrics_prometheus())
                else:
                    self._reply(200, metrics_src.snapshot())
            elif path == "/v1/version" and server is not None:
                self._reply(200, server.version_info())
            else:
                self._reply(404, {"error": "not found"})

        def do_POST(self):
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                # tenant identity rides either as an `X-Tenant` header
                # or a `tenant` body field (body wins on conflict); the
                # tenant's admission budget answers 429s with its own
                # Retry-After refill time below
                xt = self.headers.get("X-Tenant")
                if xt and not req.get("tenant"):
                    req["tenant"] = xt
                if self.path == "/v1/generate" and server is not None:
                    prompt = req.pop("prompt")
                    timeout = req.pop("timeout", None)
                    out = server.generate(prompt, timeout=timeout, **req)
                    self._reply(200, {"ids": np.asarray(out).tolist()})
                elif self.path == "/v1/rank" and ranker is not None:
                    req.pop("tenant", None)   # ranker bills nothing yet
                    self._reply(200, {"scores": rank_scores(req)})
                else:
                    self._reply(404, {"error": "not found"})
            except ServingError as e:
                # clients get the same backoff contract the in-process
                # Router uses: `retriable` says whether resubmitting the
                # identical request can succeed, and overload/unavailable
                # responses carry a Retry-After hint
                headers = {}
                if e.status in (429, 503):
                    # instance attribute first: a TenantBudgetError
                    # carries the tenant's actual bucket refill time
                    headers["Retry-After"] = \
                        f"{e.retry_after_s:g}"
                self._reply(e.status, {
                    "error": str(e),
                    "type": type(e).__name__,
                    "retriable": bool(e.retriable),
                }, headers=headers)
            except Exception as e:  # noqa: BLE001 — bad request shape
                self._reply(400, {"error": str(e), "retriable": False})

    httpd = ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=httpd.serve_forever,
                              name="serving-http", daemon=True)
    thread.start()
    return httpd
