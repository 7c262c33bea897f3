"""Prefill->decode KV-block migration (ISSUE 17 tentpole c).

A disaggregated fleet runs prefill-specialized and decode-specialized
replicas; after a prefill replica finishes a prompt, its fully-written
KV blocks move to the decode replica that will produce the response
tokens. In-process replicas share no device state (each engine owns its
pool), so migration is an explicit export -> stream -> adopt pipeline:

  * export: the prefill engine gathers the prompt's cached prefix
    blocks from its pools into host numpy (`export_prefix_blocks`),
    one tuple of arrays a layer as the model's cache layout states
    (K and V rows ``[n_blocks, block_size, nh, hd]``, or latent rows
    ``[n_blocks, block_size, width]``) with their axis order named in
    the payload (`CacheLayout.row_order`): a payload in another order
    is refused by the adopting engine, never adopted transposed or as
    another kind of block;
  * stream: the payload rides `KVMailbox`, an in-process loopback that
    mirrors the gang-layer ``dist.p2p_*`` mailbox contract exactly —
    `deadline_guard("dist.p2p_send")` before the enqueue and
    `deadline_guard("dist.p2p_recv")` before the dequeue wait — so the
    PR-14 chaos specs (delay eats the deadline, drop, raise) hit the
    serving migration path with no launcher env required. Multi-host
    fleets swap in the real `dist.p2p` mailbox behind the same shape.
  * adopt: the decode engine allocates blocks, writes the rows into its
    own (possibly head-sharded) pool and indexes them in its
    PrefixCache (`adopt_prefix_blocks`) — all-or-nothing: a fault
    mid-adoption (site ``serving.kv_migrate``) frees every block taken
    so far, so the decode pool stays leak-free and the Router falls
    back to ordinary colocated dispatch.

The unit of migration is the *block table entry*, which is why the
paged pool made disaggregation cheap: block tables are host-side numpy
and replica-global, so only the block payload bytes cross the wire.
"""

from __future__ import annotations

import queue
import threading

from ..distributed.gang import PeerGoneError, deadline_guard
from ..framework import monitor

__all__ = ["KVMailbox", "migrate_prefix"]

#: default per-leg deadline for the in-process loopback (seconds); the
#: fleet Router passes its own, derived from the request budget
DEFAULT_DEADLINE_S = 5.0


class KVMailbox:
    """Deadline-guarded in-process loopback mailbox keyed by engine
    name. Same guard-then-enqueue / guard-then-get shape as
    `distributed.p2p._Mailbox`, so the ``dist.p2p_send`` /
    ``dist.p2p_recv`` fault sites cover KV streaming too."""

    def __init__(self):
        self._queues = {}
        self._lock = threading.Lock()

    def _queue(self, name):
        with self._lock:
            q = self._queues.get(name)
            if q is None:
                q = self._queues[name] = queue.Queue()
            return q

    def send(self, payload, dst, deadline_s=DEFAULT_DEADLINE_S):
        deadline_guard("dist.p2p_send", deadline_s)
        self._queue(dst).put(payload)

    def recv(self, dst, timeout=DEFAULT_DEADLINE_S):
        remaining = deadline_guard("dist.p2p_recv", timeout,
                                   tag=str(dst))
        try:
            return self._queue(dst).get(
                timeout=remaining if remaining is not None else timeout)
        except queue.Empty:
            monitor.stat_add("serving.kv_migrate_timeouts")
            raise PeerGoneError(
                f"no KV payload for {dst!r} within {timeout:.3f}s "
                "(prefill replica gone or wedged mid-migration)")


def payload_bytes(payload):
    return int(sum(a.nbytes for layer in payload["layers"] for a in layer))


def migrate_prefix(src_engine, dst_engine, ids, mailbox=None,
                   deadline_s=DEFAULT_DEADLINE_S):
    """Move the cached KV prefix for token ids `ids` from `src_engine`
    to `dst_engine`. Returns the number of prompt tokens now cached on
    the destination (0 = nothing exportable or adoption aborted); any
    mailbox/adoption error propagates to the caller, which falls back
    to colocated dispatch — the request stays replayable either way."""
    payload = src_engine.export_prefix_blocks(ids)
    if payload is None:
        return 0
    box = mailbox if mailbox is not None else KVMailbox()
    box.send(payload, dst_engine.name, deadline_s=deadline_s)
    got = box.recv(dst_engine.name, timeout=deadline_s)
    adopted = dst_engine.adopt_prefix_blocks(got)
    if adopted:
        m = dst_engine.metrics
        nblocks = len(got["layers"][0][0]) if got["layers"] else 0
        m.inc("kv_migrations")
        m.inc("kv_migrate_blocks", nblocks)
        m.inc("kv_migrate_bytes", payload_bytes(got))
    return adopted
