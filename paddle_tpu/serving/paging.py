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
- A layout may declare BLOCK GROUPS (`CacheLayout.groups`): which
  layers' pools a group holds, its arrays and its `window`. The first
  group keeps everything (today's kind); a second, windowed group
  holds the layers that read only the last `window` keys of a slot.
  It has its own `BlockAllocator` and pool size and, a slot, a short
  table that MOVES (`WindowTables`): entry 0 stands for the block that
  holds position ``base[slot]``, and as a slot's position passes a
  block's last admissible key the engine drops its reference and
  shifts the table. The prefix cache records a window-group block on
  the full chain's key of the same depth (`insert(window=...)`), for
  as long as it lives (a block freed deep inside a prompt as the
  coldest: a later request most likely resumes near a prompt's or a
  sequence's end); `match_window` returns the deepest depth at
  which the full chain AND the window blocks that cover the `window`
  tokens before it are present: the same shape of rule as "only as
  deep as a snapshot exists", on blocks. `reclaim_window` frees the
  window blocks of cold prefixes, least recently used first.

Fault sites: ``serving.alloc_block`` fires on every physical block
allocation (a `raise` action is deterministic pool exhaustion mid-
admission); ``serving.cow_split`` fires before every copy-on-write
block copy.
"""

from __future__ import annotations

import collections
import hashlib

import numpy as np

from ..framework import faults

__all__ = ["BLOCK_ROW_ORDER", "NULL_BLOCK", "BlockGroup", "CacheLayout",
           "PoolExhausted", "BlockAllocator", "PrefixCache",
           "SnapshotEntries", "WindowTables", "positions_to_rows",
           "stored_width"]

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


class BlockGroup:
    """The layers of a layout whose blocks are of one kind: `layers`
    indexes the step's list of pools (one tuple of arrays an entry),
    `arrays` is ``((name, row_shape), ...)`` as `CacheLayout` says,
    `window` the number of keys a query admits counting itself (None =
    every earlier key: the blocks are kept for the slot's life).
    `head_axis` is the pool axis a mesh may shard over mp and `heads`
    how many heads lie along it (None = as many as the axis is long):
    the axis is sharded only where mp divides the heads."""

    def __init__(self, name, layers, arrays, window=None, head_axis=None,
                 heads=None):
        self.name = str(name)
        self.layers = tuple(int(i) for i in layers)
        self.arrays = tuple((str(n), tuple(int(d) for d in shape))
                            for n, shape in arrays)
        self.window = None if window is None else int(window)
        self.head_axis = head_axis
        self.heads = None if heads is None else int(heads)

    def pool_shapes(self, num_blocks, block_size):
        """The pools' shapes, one per array of a layer."""
        return [(int(num_blocks), int(block_size)) + row
                for _, row in self.arrays]

    def bytes_per_token(self, itemsize):
        """Cache bytes one token occupies over this group's layers
        (while the token lies inside the window, for a windowed one)."""
        return int(len(self.layers) * itemsize
                   * sum(int(np.prod(row)) for _, row in self.arrays))


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

    A layout whose layers do not all keep the same kind of block gives
    `groups` (`BlockGroup`s) in place of `arrays`, `layers` and
    `head_axis`: the first keeps everything, a second may be windowed.
    One given `arrays` and `layers` declares the one group
    ``"blocks"`` over all its layers and is carried exactly as before;
    `arrays`, `layers` (all groups' together) and `head_axis` then
    read as they always did.

    The second kind: `state` is ``((name, shape, dtype), ...)``, the
    arrays one SLOT keeps of each of `state_layers` state-holding
    layers, whatever its context (a recurrent state, a filter's tail).
    The engine allocates ``[rows, *shape]`` of each, a row a slot and a
    row a snapshot entry. `state_head_axis` maps an array's name to the
    axis of that allocation a mesh may shard over mp; an array it does
    not name is replicated. A layout that declares no state (dense and
    latent attention) is carried exactly as before."""

    def __init__(self, row_order, arrays=None, layers=None, head_axis=None,
                 state=(), state_layers=0, state_head_axis=None,
                 groups=None):
        self.row_order = str(row_order)
        if groups is None:
            groups = (BlockGroup("blocks", range(int(layers)), arrays,
                                 head_axis=head_axis),)
        self.groups = tuple(groups)
        self.layers = sum(len(g.layers) for g in self.groups)
        if sorted(i for g in self.groups for i in g.layers) \
                != list(range(self.layers)):
            raise ValueError(
                f"the block groups' layers "
                f"{[g.layers for g in self.groups]} do not number the "
                f"step's {self.layers} pools once each")
        if self.groups[0].window is not None or len(self.groups) > 2 \
                or (len(self.groups) == 2 and self.groups[1].window is None):
            raise ValueError(
                "a cache layout declares one group that keeps every "
                "block and at most one windowed group beside it, got "
                f"{[(g.name, g.window) for g in self.groups]}")
        self.arrays = self.groups[0].arrays
        self.head_axis = self.groups[0].head_axis
        self.state_layers = int(state_layers)
        self.state = tuple((str(n), tuple(int(d) for d in shape), str(dt))
                           for n, shape, dt in state) \
            if self.state_layers else ()
        self.state_head_axis = dict(state_head_axis or {})

    def group_of(self, layer):
        """The group that pool `layer` of the step's list belongs to."""
        for g in self.groups:
            if layer in g.layers:
                return g
        raise IndexError(layer)

    def pool_shapes(self, num_blocks, block_size):
        """The first group's pools' shapes, one per array of a layer."""
        return self.groups[0].pool_shapes(num_blocks, block_size)

    def bytes_per_token(self, itemsize):
        """Cache bytes one token occupies over all layers."""
        return sum(g.bytes_per_token(itemsize) for g in self.groups)

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


class WindowTables:
    """The host's side of a windowed block group: its allocator and,
    a slot, a SHORT block table that moves. Entry `j` of slot `b`'s
    row stands for the block that holds positions ``base[b] + j *
    block_size`` onward; `base` is a multiple of the block size. A
    step that computes positions ``[pos, pos + n)`` of a slot reads
    keys from ``pos - window + 1`` on, so the row holds at most
    ``ceil((window + chunk) / block_size) + 1`` entries whatever the
    slot's depth: the length of the compiled loop over it is a
    constant of the layout.

    A slot's blocks are a dict ``{block index: block id}`` that the
    engine keeps with the slot (`held`); `sync` drops nothing itself.
    `reserved` is the sum over live slots of the most blocks each can
    hold at once (`demand`): admission keeps it within the pool, so a
    block a live slot needs ahead is always free or reclaimable."""

    def __init__(self, allocator, window, block_size, chunk, max_slots):
        self.alloc = allocator
        self.window = int(window)
        self.block_size = int(block_size)
        self.entries = -(-(self.window + int(chunk)) // self.block_size) + 1
        self.table = np.full((max_slots, self.entries), NULL_BLOCK, np.int32)
        self.base = np.zeros((max_slots,), np.int32)
        self.reserved = 0

    def demand(self, n_positions):
        """The most blocks a request of `n_positions` holds at once."""
        return min(self.entries, -(-int(n_positions) // self.block_size))

    def first_block(self, pos):
        """Index of the block that holds the earliest key a query at
        position `pos` admits (``pos - window + 1``)."""
        return max(int(pos) - self.window + 1, 0) // self.block_size

    def sync(self, slot, held):
        """Write `held` into `slot`'s row, from its lowest block on."""
        first = min(held) if held else 0
        if held and max(held) - first >= self.entries:
            raise AssertionError(
                f"slot {slot} holds window blocks {first}..{max(held)}, "
                f"more than the table's {self.entries} entries")
        row = self.table[slot]
        row[:] = NULL_BLOCK
        for k, bid in held.items():
            row[k - first] = bid
        self.base[slot] = first * self.block_size

    def clear(self, slot):
        self.table[slot, :] = NULL_BLOCK
        self.base[slot] = 0


class _Chain:
    """The prefix keys of ONE token sequence, made incrementally: key
    `k` is the digest of its first ``(k + 1) * block_size`` tokens, the
    very bytes `PrefixCache._digest` hashes at once. `indexed` counts
    the leading blocks `insert` has already walked for this sequence,
    so that a live request that indexes its blocks as it goes pays for
    each once."""

    def __init__(self):
        self.keys: list = []
        self.indexed = 0
        self._hasher = hashlib.sha1()


class PrefixCache:
    """Radix prefix index over fully written KV blocks.

    Each entry maps `digest(tokens[0 : k*block_size])` -> the physical
    block holding positions `[(k-1)*bs, k*bs)`. The cache holds one
    allocator reference per entry, so indexed blocks survive slot
    eviction and are physically shared by later requests with the same
    prefix (`match` -> the caller increfs per consuming slot).

    `window` (a `WindowTables`) is the layout's windowed group, if it
    has one: an entry may then also name the window-group block of the
    same positions (`_wblocks`, one reference of the window group's
    allocator each), recorded by `insert(window=...)` and dropped with
    its entry or, before it, by `reclaim_window`.
    """

    def __init__(self, allocator: BlockAllocator, block_size,
                 snapshots: SnapshotEntries = None,
                 window: WindowTables = None):
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
        #: the windowed group, for a layout with one (None otherwise);
        #: `_wblocks` maps a key to the window-group block of the same
        #: positions, least recently used first
        self.window = window
        self._wblocks = collections.OrderedDict()
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

    def chain(self):
        """A fresh `_Chain` for one sequence (`insert(chain=...)`)."""
        return _Chain()

    def _extend(self, chain, ids, n_blocks):
        """Grow `chain.keys` to the first `n_blocks` blocks of `ids`:
        each block's bytes are hashed once, and key `k` is
        `_digest(ids[:(k + 1) * block_size])`."""
        bs = self.block_size
        have = len(chain.keys)
        if have >= n_blocks:
            return
        data = np.ascontiguousarray(ids[have * bs:n_blocks * bs], np.int32)
        for k in range(n_blocks - have):
            chain._hasher.update(data[k * bs:(k + 1) * bs].tobytes())
            chain.keys.append(chain._hasher.copy().digest())

    def _walk(self, ids, limit):
        """Keys and blocks of the longest indexed chain of whole
        blocks under ``ids[:limit]``."""
        bs = self.block_size
        chain, blocks = _Chain(), []
        ids = np.asarray(ids)
        while (len(blocks) + 1) * bs <= limit:
            self._extend(chain, ids, len(blocks) + 1)
            bid = self._blocks.get(chain.keys[-1])
            if bid is None:
                chain.keys.pop()
                break
            blocks.append(bid)
        return chain.keys, blocks

    def _touch(self, key):
        self._clock += 1
        self._lru[key] = self._clock

    def _diverging_child(self, parent, want):
        """Among `parent`'s children, the one whose chunk shares the
        longest proper prefix with `want`: ``(key, n_rows)`` or None."""
        want = np.asarray(want, np.int32)
        best_key, best_c = None, 0
        if want.size:
            for child in self._children.get(parent, ()):
                chunk = self._chunks[child]
                m = min(chunk.size, want.size)
                neq = np.nonzero(chunk[:m] != want[:m])[0]
                c = int(neq[0]) if neq.size else m
                if c > best_c:
                    best_key, best_c = child, c
        if best_key is None or best_c >= self.block_size:
            return None
        return best_key, best_c

    def match(self, ids, limit):
        """Longest indexed prefix of ``ids[:limit]``.

        Returns ``(blocks, n_tokens, cow)``: the shared full blocks (in
        table order, NOT yet increfed — the caller increfs one ref per
        slot), the token count they cover, and an optional
        ``(src_block, n_rows)`` copy-on-write candidate when a cached
        block matches only the first `n_rows` of the next chunk (the
        divergence point lies inside it)."""
        keys, blocks = self._walk(ids, limit)
        for key in keys:
            self._touch(key)
        n = len(blocks) * self.block_size
        cow = None
        found = self._diverging_child(keys[-1] if keys else _ROOT,
                                      ids[n:limit])
        if found is not None:
            cow = (self._blocks[found[0]], found[1])
            self._touch(found[0])
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
        keys, blocks = self._walk(ids, limit)
        deep = max((i for i, key in enumerate(keys) if key in self._snap),
                   default=-1) + 1
        for key in keys[:deep]:
            self._touch(key)
        entry = self._snap[keys[deep - 1]] if deep else None
        return blocks[:deep], deep * self.block_size, entry, \
            len(blocks) * self.block_size

    def match_window(self, ids, limit):
        """`match` for a layout with a windowed group: the longest
        indexed prefix of ``ids[:limit]``, cut at the deepest depth at
        which the window group still holds every block of the `window`
        tokens before it (a query that resumes there reads them).

        Returns ``(blocks, n_tokens, cow, held, n_matched)``: the
        full group's shared blocks and the tokens they cover; a
        copy-on-write candidate ``(src_block, n_rows, src_window_block)``
        when the chain was not cut and a cached block of BOTH groups
        matches the first `n_rows` of the next chunk; `held`, the
        window-group blocks to resume over as ``{block index: block
        id}`` (NOT yet increfed); and how many tokens' blocks the full
        chain matched in all: ``n_matched - n_tokens`` of them are
        lost to the window and computed again."""
        bs, w = self.block_size, self.window
        keys, blocks = self._walk(ids, limit)
        # run[i]: how many consecutive keys up to and with `i` have a
        # window-group block
        run, deep = [], len(keys)
        for key in keys:
            run.append((run[-1] if run else 0) + 1
                       if key in self._wblocks else 0)
        while deep and run[deep - 1] < deep - w.first_block(deep * bs):
            deep -= 1
        n, cow = deep * bs, None
        if deep == len(keys):
            found = self._diverging_child(keys[-1] if keys else _ROOT,
                                          ids[n:limit])
            if found is not None and found[0] in self._wblocks:
                cow = (self._blocks[found[0]], found[1],
                       self._wblocks[found[0]])
                self._touch(found[0])
                self._touch_window(found[0])
        for key in keys[:deep]:
            self._touch(key)
        held = {}
        for k in range(w.first_block(n + (cow[1] if cow else 0)), deep):
            held[k] = self._wblocks[keys[k]]
            self._touch_window(keys[k])
        return blocks[:deep], n, cow, held, len(blocks) * bs

    def _touch_window(self, key):
        self._wblocks.move_to_end(key)      # most recent last

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

    def insert(self, tokens, blocks, written, snapshot=None, window=None,
               chain=None, cold_below=0):
        """Index every fully written block of a sequence.

        `tokens` is the full id sequence, `blocks` its physical block
        list (table order), `written` how many positions hold real KV
        (the last sampled token is never written). Newly indexed blocks
        gain one allocator reference (the cache's own); already-indexed
        prefixes are just LRU-refreshed. Returns #new entries.

        `snapshot` ``(entry, depth)`` hands over the snapshot entry that
        holds the sequence's state after exactly `depth` tokens: it is
        recorded on the block that ends there, or freed when no block
        does.

        `window` ``{block index: block id}`` names window-group blocks
        of the same sequence: each that is fully written is recorded
        on its depth's entry (one reference of the window group's
        allocator), unless the entry has one already; one whose index
        lies under `cold_below` is recorded as the LEAST recently used
        (the caller knows no later request is likely to resume there),
        the others as the most. `chain` is the
        sequence's own `_Chain` (`chain()`), for a request that
        indexes its blocks more than once as it goes: blocks it has
        walked before are not walked again."""
        bs = self.block_size
        tokens = np.asarray(tokens, np.int32)
        chain = chain if chain is not None else _Chain()
        if chain.indexed and chain.keys[chain.indexed - 1] \
                not in self._blocks:
            chain.indexed = 0       # the index was cleared meanwhile
        n_blocks = written // bs
        self._extend(chain, tokens, n_blocks)
        keys, added = chain.keys, 0
        parent = keys[chain.indexed - 1] if chain.indexed else _ROOT
        for k in range(chain.indexed, n_blocks):
            key = keys[k]
            if key not in self._blocks:
                bid = blocks[k]
                self._alloc.incref(bid)
                self._blocks[key] = bid
                self._chunks[key] = tokens[k * bs:(k + 1) * bs].copy()
                self._parent[key] = parent
                self._children.setdefault(parent, set()).add(key)
                added += 1
            self._touch(key)
            parent = key
        chain.indexed = max(chain.indexed, n_blocks)
        if snapshot is not None:
            entry, depth = snapshot
            if 0 < depth <= written and depth % bs == 0:
                self._record_snapshot(keys[depth // bs - 1], entry)
            else:
                self.snapshots.free(entry)
        for k, wbid in (window or {}).items():
            if k < n_blocks and keys[k] not in self._wblocks:
                self.window.alloc.incref(wbid)
                self._wblocks[keys[k]] = wbid
                if k < cold_below:
                    self._wblocks.move_to_end(keys[k], last=False)
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
        wbid = self._wblocks.pop(key, None)
        if wbid is not None:
            self.window.alloc.decref(wbid)
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
        free nothing but are dropped last-resort too). Returns #freed.
        An entry goes with the window-group block recorded on it."""
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

    def reclaim_window(self, n_blocks):
        """Free `n_blocks` window-group blocks that only the index
        still holds, least recently used first; their entries stay
        (the full chain then matches deeper than the window group can
        serve, and the rest is computed again). Returns #freed."""
        alloc, freed = self.window.alloc, 0
        for key in list(self._wblocks):
            if freed >= n_blocks:
                break
            if alloc.refcount(self._wblocks[key]) == 1:
                alloc.decref(self._wblocks.pop(key))
                freed += 1
        return freed

    @property
    def window_blocks(self):
        """How many window-group blocks the index names."""
        return len(self._wblocks)

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
