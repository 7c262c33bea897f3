"""Host-side bookkeeping for the block-paged KV cache: a refcounted
block allocator over a fixed physical pool, and a radix-style prefix
cache that lets requests sharing a token prefix share physical blocks.

Design (vLLM PagedAttention + SGLang RadixAttention, collapsed to the
slot engine's needs):

- The device pools are `[num_blocks, block_size, *row]` arrays, as
  many a layer and of the row shape the model's `CacheLayout` states
  (dense attention: K and V with rows `[nh, hd]`, a block's rows
  `[block_size, nh, hd]`, `BLOCK_ROW_ORDER`; latent attention: one
  array with rows `[rank + rope_dim]`); every logical sequence
  position `t` of a slot maps through its block table to physical row
  `(table[t // bs], t % bs)`, the pools' two leading axes. Block 0 is the
  reserved *null block*: it is never allocated, free slots point every
  table entry at it, and all padding/garbage scatter writes land there
  — so the compiled step can always write `[max_slots, chunk]` rows
  without host-side masking.
- `BlockAllocator` hands out blocks with a refcount. A block shared by
  N slots (prefix sharing) plus the prefix cache has refcount N+1 and
  returns to the free list only when the last reference drops.
- `PrefixCache` indexes *fully written* blocks by the cumulative hash
  of all tokens from position 0 (position-dependent KV means a chunk is
  only reusable under its exact left context, hence cumulative, not
  per-chunk, hashing — the radix property). Lookup walks the hash
  chain block by block; a partial match inside the next block yields a
  copy-on-write candidate: the caller copies the physical block and
  overwrites the divergent tail. Entries are evicted leaf-first in LRU
  order when the allocator runs dry (`reclaim`).
- A layout may also declare per-slot STATE arrays (`CacheLayout.state`:
  a recurrent layer keeps a fixed-size state a slot, not rows a token).
  K and V rows of a prefix are shared block by block; the state at a
  block boundary cannot be rebuilt from them. So the prefix cache of
  such a layout records, with a block, the SNAPSHOT of the state taken
  exactly at that block's end (an entry of `SnapshotEntries`), and
  `match_snapshot` returns blocks no deeper than the deepest snapshot
  on the matched chain: a hit is only usable as deep as a snapshot
  exists. An entry lives as long as the block it is recorded on.

Fault sites: ``serving.alloc_block`` fires on every physical block
allocation (a `raise` action is deterministic pool exhaustion mid-
admission); ``serving.cow_split`` fires before every copy-on-write
block copy.
"""

from __future__ import annotations

import hashlib

import numpy as np

from ..framework import faults

__all__ = ["BLOCK_ROW_ORDER", "NULL_BLOCK", "CacheLayout", "PoolExhausted",
           "BlockAllocator", "PrefixCache", "SnapshotEntries",
           "positions_to_rows", "stored_width"]

#: physical block 0 — reserved scratch target for padding writes
NULL_BLOCK = 0

#: axis order of one block's rows wherever they leave a pool:
#: ``[block_size (t), num_heads (h), head_dim (d)]``. It is part of a
#: migration payload's geometry and of a spill record's header, because
#: a shape cannot tell it from the head-major ``[nh, block_size, hd]``
#: of earlier pools when ``nh == block_size``: rows that do not name
#: this order are refused, not adopted transposed.
BLOCK_ROW_ORDER = "thd"

_ROOT = b"\x00root"
# numpy has no bfloat16: its itemsize is float16's
_NUMPY_NAME = {"bfloat16": "float16"}


class CacheLayout:
    """What one block of one layer holds, as the MODEL declares it
    (`model.cache_layout()`); the engine allocates, donates, copies and
    recovers the arrays it names without knowing their rank, and the
    allocator, the prefix cache and chunked prefill work on block ids.

    `arrays` is ``((name, row_shape), ...)``: each array of a layer is a
    pool ``[num_blocks, block_size, *row_shape]``, so a position's row
    is always at the two leading axes. Dense multi-head attention keeps
    ``("k", (nh, hd))`` and ``("v", (nh, hd))``; latent attention one
    ``("latent", (rank + rope_dim,))`` with no head axis. `row_order`
    names the axes of one block's rows (``"thd"``, ``"tc"``) wherever
    they leave a pool: an exported or spilled block in an order its
    reader does not know is refused by that name. `head_axis` is the
    pool axis a mesh may shard over its model-parallel degree; None =
    the pool has no head axis and is replicated.

    The second kind: `state` is ``((name, shape, dtype), ...)``, the
    arrays one SLOT keeps of each of `state_layers` state-holding
    layers, whatever its context (a recurrent state, a filter's tail).
    The engine allocates ``[rows, *shape]`` of each, a row a slot and a
    row a snapshot entry. `state_head_axis` maps an array's name to the
    axis of that allocation a mesh may shard over mp; an array it does
    not name is replicated. A layout that declares no state (dense and
    latent attention) is carried exactly as before."""

    def __init__(self, row_order, arrays, layers, head_axis=None,
                 state=(), state_layers=0, state_head_axis=None):
        self.row_order = str(row_order)
        self.arrays = tuple((str(n), tuple(int(d) for d in shape))
                            for n, shape in arrays)
        self.layers = int(layers)
        self.head_axis = head_axis
        self.state_layers = int(state_layers)
        self.state = tuple((str(n), tuple(int(d) for d in shape), str(dt))
                           for n, shape, dt in state) \
            if self.state_layers else ()
        self.state_head_axis = dict(state_head_axis or {})

    def pool_shapes(self, num_blocks, block_size):
        """The pools' shapes, one per array of a layer."""
        return [(int(num_blocks), int(block_size)) + row
                for _, row in self.arrays]

    def bytes_per_token(self, itemsize):
        """Cache bytes one token occupies over all layers."""
        return int(self.layers * itemsize
                   * sum(int(np.prod(row)) for _, row in self.arrays))

    def state_shapes(self, rows):
        """The state arrays' shapes with `rows` leading rows, one per
        array of a state-holding layer."""
        return [(int(rows),) + shape for _, shape, _ in self.state]

    def state_bytes_per_slot(self):
        """Bytes one slot's state occupies over all state layers; as
        much again a snapshot entry."""
        return int(self.state_layers * sum(
            int(np.prod(shape)) * np.dtype(_NUMPY_NAME.get(dt, dt)).itemsize
            for _, shape, dt in self.state))


def stored_width(columns):
    """Columns a headless pool keeps for a row of `columns`: rounded up
    to the 128 lanes of the chip's tiles. A 576-wide row occupies 640
    lanes of a tile anyway; declared at 576, the TPU compiler avoids
    that padding by laying the pool out block-index-minor (``{0,2,1}``)
    and copies the whole pool in and out of every layer's scatter
    (tests/test_v5e_compile.py)."""
    return -(-int(columns) // 128) * 128


def positions_to_rows(table, positions, block_size):
    """Map logical sequence positions to physical pool rows through a
    slot's block table: ``(table[t // bs], t % bs)``.

    This is the same routing the compiled step's bulk KV scatter uses —
    a speculative round scatters all ``k+1`` staged columns (next token
    plus every draft proposal) through it in one dispatch, so the rows
    of a rejected suffix land in the pool too. They are harmless:
    per-row causal masking (``key_idx <= t``) hides them from every
    attend, and the next round's staging overwrites them before the
    coverage frontier reaches their positions. Tests use this helper to
    read pool rows back and certify scatter parity.
    """
    positions = np.asarray(positions)
    table = np.asarray(table)
    return table[positions // block_size], positions % block_size


class PoolExhausted(RuntimeError):
    """No free physical blocks (after reclaim); admission must wait."""


class BlockAllocator:
    """Refcounted free-list allocator over `num_blocks` physical blocks.

    Block 0 (`NULL_BLOCK`) is reserved and never handed out; `usable`
    is therefore `num_blocks - 1`.
    """

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 physical blocks (1 reserved), got {num_blocks}")
        self.num_blocks = num_blocks
        self._ref = np.zeros((num_blocks,), np.int64)
        self._ref[NULL_BLOCK] = 1      # pinned forever
        # pop() yields ascending ids — deterministic tests
        self._free = list(range(num_blocks - 1, 0, -1))

    @property
    def usable(self):
        return self.num_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def blocks_in_use(self):
        return self.usable - len(self._free)

    def alloc(self):
        """One fresh block (refcount 1). Fault site serving.alloc_block."""
        faults.fault_point("serving.alloc_block")
        if not self._free:
            raise PoolExhausted(
                f"all {self.usable} usable KV blocks are referenced")
        bid = self._free.pop()
        self._ref[bid] = 1
        return bid

    def incref(self, bid):
        if bid == NULL_BLOCK or self._ref[bid] <= 0:
            raise ValueError(f"incref on unallocated block {bid}")
        self._ref[bid] += 1

    def decref(self, bid):
        """Drop one reference; returns True when the block was freed."""
        if bid == NULL_BLOCK or self._ref[bid] <= 0:
            raise ValueError(f"decref on unallocated block {bid}")
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)
            return True
        return False

    def refcount(self, bid):
        return int(self._ref[bid])


class SnapshotEntries:
    """Free list over the `n` entries of a state snapshot pool: each
    holds one slot's whole state as it was at a block boundary. An
    entry belongs to a live request (its working entry, overwritten at
    every boundary it lands on) or to the prefix cache (recorded on a
    block), never to both."""

    def __init__(self, n):
        self.n = int(n)
        self._free = list(range(self.n - 1, -1, -1))

    @property
    def free_entries(self):
        return len(self._free)

    def alloc(self):
        """A free entry, or None when every one is held."""
        return self._free.pop() if self._free else None

    def free(self, entry):
        if entry in self._free or not 0 <= entry < self.n:
            raise ValueError(f"free of snapshot entry {entry}, not held")
        self._free.append(entry)


class PrefixCache:
    """Radix prefix index over fully written KV blocks.

    Each entry maps `digest(tokens[0 : k*block_size])` -> the physical
    block holding positions `[(k-1)*bs, k*bs)`. The cache holds one
    allocator reference per entry, so indexed blocks survive slot
    eviction and are physically shared by later requests with the same
    prefix (`match` -> the caller increfs per consuming slot).
    """

    def __init__(self, allocator: BlockAllocator, block_size,
                 snapshots: SnapshotEntries = None):
        self._alloc = allocator
        self.block_size = block_size
        #: the entries of the state snapshot pool, for a layout with
        #: state arrays (None otherwise); `_snap` maps a key to the
        #: entry holding the state exactly at that block's end
        self.snapshots = snapshots
        self._snap: dict = {}
        #: called once for every recorded snapshot that eviction (not a
        #: deeper snapshot on its chain) dropped
        self.snapshot_evicted_hook = None
        self._blocks: dict = {}     # key -> block id
        self._chunks: dict = {}     # key -> np.int32 chunk tokens
        self._parent: dict = {}     # key -> parent key
        self._children: dict = {}   # key -> set of child keys
        self._lru: dict = {}        # key -> last-touch tick
        self._clock = 0
        #: optional spill donation: ``hook(key, prefix_tokens, bid,
        #: n_rows)`` called on eviction of an entry whose block is about
        #: to be freed, BEFORE the freeing decref (append-before-evict —
        #: the spill tier persists the rows while they still exist).
        #: The hook must not raise: a failed spill loses durability for
        #: that block, never the eviction itself.
        self.spill_hook = None

    def __len__(self):
        return len(self._blocks)

    @staticmethod
    def _digest(ids):
        return hashlib.sha1(
            np.ascontiguousarray(ids, np.int32).tobytes()).digest()

    def _touch(self, key):
        self._clock += 1
        self._lru[key] = self._clock

    def match(self, ids, limit):
        """Longest indexed prefix of ``ids[:limit]``.

        Returns ``(blocks, n_tokens, cow)``: the shared full blocks (in
        table order, NOT yet increfed — the caller increfs one ref per
        slot), the token count they cover, and an optional
        ``(src_block, n_rows)`` copy-on-write candidate when a cached
        block matches only the first `n_rows` of the next chunk (the
        divergence point lies inside it)."""
        bs = self.block_size
        blocks, n, parent = [], 0, _ROOT
        while n + bs <= limit:
            key = self._digest(ids[:n + bs])
            bid = self._blocks.get(key)
            if bid is None:
                break
            blocks.append(bid)
            parent = key
            n += bs
            self._touch(key)
        cow = None
        want = np.asarray(ids[n:limit], np.int32)
        if want.size:
            best_key, best_c = None, 0
            for child in self._children.get(parent, ()):
                chunk = self._chunks[child]
                m = min(chunk.size, want.size)
                neq = np.nonzero(chunk[:m] != want[:m])[0]
                c = int(neq[0]) if neq.size else m
                if c > best_c:
                    best_key, best_c = child, c
            if best_key is not None and best_c < bs:
                cow = (self._blocks[best_key], best_c)
                self._touch(best_key)
        return blocks, n, cow

    def match_snapshot(self, ids, limit):
        """`match` for a layout with state arrays: the longest indexed
        prefix of ``ids[:limit]``, cut at the deepest block on it that
        a state snapshot is recorded on.

        Returns ``(blocks, n_tokens, entry, n_matched)``: the shared
        blocks and the tokens they cover as far as that snapshot (none
        and 0 without one), the snapshot entry to restore the slot's
        state from (None = start from zero), and how many tokens'
        blocks matched in all; ``n_matched - n_tokens`` of them lie
        deeper than any snapshot and have to be computed again. No
        copy-on-write candidate: a state cannot be cut inside a
        block."""
        bs = self.block_size
        blocks, n, keys = [], 0, []
        while n + bs <= limit:
            key = self._digest(ids[:n + bs])
            bid = self._blocks.get(key)
            if bid is None:
                break
            blocks.append(bid)
            keys.append(key)
            n += bs
        deep = max((i for i, key in enumerate(keys) if key in self._snap),
                   default=-1) + 1
        for key in keys[:deep]:
            self._touch(key)
        entry = self._snap[keys[deep - 1]] if deep else None
        return blocks[:deep], deep * bs, entry, n

    def _record_snapshot(self, key, entry):
        """`entry` holds the state at the end of `key`'s block: record
        it there and free what it supersedes, an older entry on the
        same key and every shallower one on its chain (the sequence
        that left it resumes from the deepest)."""
        old = self._snap.get(key)
        if old is not None:
            self.snapshots.free(old)
        self._snap[key] = entry
        parent = self._parent[key]
        while parent != _ROOT:
            shallower = self._snap.pop(parent, None)
            if shallower is not None:
                self.snapshots.free(shallower)
            parent = self._parent[parent]

    def evict_lru_snapshot(self):
        """Free the least recently used recorded snapshot (its blocks
        stay indexed, unusable until a sequence records one on them
        again). Returns whether there was one."""
        if not self._snap:
            return False
        key = min(self._snap, key=lambda k: self._lru[k])
        self._drop_snapshot(key)
        return True

    def _drop_snapshot(self, key):
        entry = self._snap.pop(key, None)
        if entry is not None:
            self.snapshots.free(entry)
            if self.snapshot_evicted_hook is not None:
                self.snapshot_evicted_hook()

    def insert(self, tokens, blocks, written, snapshot=None):
        """Index every fully written block of a finished sequence.

        `tokens` is the full id sequence, `blocks` its physical block
        list (table order), `written` how many positions hold real KV
        (the last sampled token is never written). Newly indexed blocks
        gain one allocator reference (the cache's own); already-indexed
        prefixes are just LRU-refreshed. Returns #new entries.

        `snapshot` ``(entry, depth)`` hands over the snapshot entry that
        holds the sequence's state after exactly `depth` tokens: it is
        recorded on the block that ends there, or freed when no block
        does."""
        bs = self.block_size
        tokens = np.asarray(tokens, np.int32)
        parent, added = _ROOT, 0
        if snapshot is not None:
            entry, depth = snapshot
            at = self._digest(tokens[:depth]) \
                if 0 < depth <= written and depth % bs == 0 else None
        for k in range(1, written // bs + 1):
            key = self._digest(tokens[:k * bs])
            if key not in self._blocks:
                bid = blocks[k - 1]
                self._alloc.incref(bid)
                self._blocks[key] = bid
                self._chunks[key] = tokens[(k - 1) * bs:k * bs].copy()
                self._parent[key] = parent
                self._children.setdefault(parent, set()).add(key)
                added += 1
            self._touch(key)
            parent = key
        if snapshot is not None:
            if at is None:
                self.snapshots.free(entry)
            else:
                self._record_snapshot(at, entry)
        return added

    def prefix_tokens(self, key):
        """The full cumulative token prefix an entry covers (root chunk
        through this entry's own chunk, concatenated in order)."""
        chunks = []
        while key != _ROOT:
            chunks.append(self._chunks[key])
            key = self._parent[key]
        return np.concatenate(chunks[::-1]) if chunks else \
            np.zeros((0,), np.int32)

    def _evict(self, key, spill=True):
        bid = self._blocks[key]
        if spill and self.spill_hook is not None \
                and self._alloc.refcount(bid) == 1:
            # append-before-evict: persist the rows while the block
            # still exists — the decref below frees it for reuse
            self.spill_hook(key, self.prefix_tokens(key), bid,
                            len(self._chunks[key]))
        self._drop_snapshot(key)
        self._children.get(self._parent[key], set()).discard(key)
        self._children.pop(key, None)
        bid = self._blocks.pop(key)
        self._chunks.pop(key)
        self._parent.pop(key)
        self._lru.pop(key)
        return self._alloc.decref(bid)

    def reclaim(self, n_blocks):
        """Evict LRU leaf entries until `n_blocks` physical blocks were
        actually freed (entries whose block a live slot still references
        free nothing but are dropped last-resort too). Returns #freed."""
        freed = 0
        while freed < n_blocks:
            leaves = [k for k in self._blocks
                      if not self._children.get(k)]
            if not leaves:
                break
            # oldest leaf whose eviction frees a block, else oldest leaf
            freeing = [k for k in leaves
                       if self._alloc.refcount(self._blocks[k]) == 1]
            if not freeing:
                break
            victim = min(freeing, key=lambda k: self._lru[k])
            if self._evict(victim):
                freed += 1
        return freed

    def clear(self, spill=True):
        """Drop every entry (and its allocator reference). Leaves go
        before parents so the spill hook can still resolve each
        entry's full token prefix through a live parent chain.
        `spill=False` keeps the hook out of it: the pool's rows are
        gone (a failed step took them), there is nothing to persist."""
        while self._blocks:
            for key in [k for k in self._blocks
                        if not self._children.get(k)]:
                self._evict(key, spill)
