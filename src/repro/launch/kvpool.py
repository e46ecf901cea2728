"""Paged Sidebar KV pool: block-granular cache manager with prefix caching.

``core/sidebar.py`` realizes the paper's scratchpad discipline — explicit
per-location ownership, a recycling free list, protocol errors on
reuse-before-release — for *intra-layer intermediates*. This module lifts
the same discipline up to the serving layer's KV memory: instead of one
max-length cache row per request (slot-granular, PR-3/4), the KV cache is
ONE physical pool of fixed-size **blocks** (``block_size`` token
positions each) and every request owns a *logical block table* mapping
its positions onto pooled blocks.

The allocator mirrors the sidebar protocol deliberately:

  * fixed-size placements recycled through a free list (``SidebarBuffer``
    regions ~ pool blocks);
  * an explicit lifecycle — ``free -> staged -> active`` (+ ``cached``,
    the refcount-0-but-indexed refinement of free) — with
    ``KVPoolError`` raised on any out-of-order transition, the exact
    analogue of ``SidebarProtocolError``'s reuse-before-release;
  * ownership is *refcounted* rather than binary: a block whose content
    is a pure function of a prompt prefix may be owned by several
    requests at once (prefix caching), and a write into shared state
    must copy first (copy-on-write) — the multi-reader generalization
    of the sidebar's single-owner mutex.

Prefix caching is **hash-consed**: a full block whose tokens are
``prompt[: (j+1) * block_size]`` is registered under the byte content of
that whole prefix (a radix-tree path collapsed into its content key —
exact, collision-free, and cheap at serving scale). A later request
whose prompt starts with the same tokens splices the physical block into
its table with a refcount bump instead of recomputing its KV. When the
last owner releases a registered block it becomes ``cached``: still
indexed, evicted LRU only when the free list runs dry.

Device side, the pool is family-agnostic: ``KVPool`` materializes the
model's own ``cache_specs`` with ``batch=num_blocks`` and
``max_len=block_size``, and probes each leaf's batch and length axes
from spec diffs — so GQA 5-D KV, int8 scales, and the MLA latent all
page identically. Attention gathers/scatters through the block table
(``models.attention``); the generic ``gather``/``copy_blocks`` here
serve copy-on-write and test-time reconstruction.

``launch.scheduler.PagedContinuousBatchingServer`` drives this: chunked
prefill-ahead stages pending requests' KV block-by-block between decode
segments, so admission is a block-table splice plus a first decode step
that the following segment program already performs — no synchronous
full-prompt prefill on the admission critical path.
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import hashlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models.attention import SCRATCH_BLOCK

Array = jax.Array


class KVPoolError(RuntimeError):
    """Violation of the block lifecycle / refcount protocol (the serving-
    layer analogue of ``core.sidebar.SidebarProtocolError``)."""


class BlockState(enum.Enum):
    FREE = "free"        # on the free list, content meaningless
    STAGED = "staged"    # allocated; prefill-ahead is writing its KV
    ACTIVE = "active"    # owned (refcount >= 1) by live request(s)
    CACHED = "cached"    # refcount 0 but prefix-indexed; LRU-evictable


def prefix_key(tokens: np.ndarray, end: int) -> bytes:
    """Content key of the prefix ``tokens[:end]`` — the hash-consing key
    a full block is registered under. Byte-exact (no collision risk); a
    radix-tree path collapsed into its content."""
    return np.ascontiguousarray(tokens[:end], dtype=np.int32).tobytes()


def chunk_key(prev: bytes, tokens: np.ndarray) -> bytes:
    """Chained chunk-boundary key: ``H(prev_key || block_tokens)``.

    Semantically IDENTICAL to the whole-prefix byte key — the chain
    covers the block's content, its offset (the chain depth), and its
    entire preceding context, which is exactly what a transformer
    block's KV is a function of — but O(1) bytes per block instead of
    O(prefix) bytes, so a long RAG prompt's per-boundary keys stay
    cheap to compute, store, and probe fleet-wide. The ``ck:`` prefix
    keeps the namespace disjoint from raw whole-prefix keys; SHA-256
    stands in for byte exactness (collisions are not a serving-scale
    concern)."""
    h = hashlib.sha256(prev)
    h.update(np.ascontiguousarray(tokens, dtype=np.int32).tobytes())
    return b"ck:" + h.digest()


def chunk_keys(tokens: np.ndarray, n_blocks: int,
               block_size: int) -> list[bytes]:
    """Chunk-boundary keys for the first ``n_blocks`` full blocks of
    ``tokens``: ``keys[j]`` addresses the block backing positions
    ``[j*bs, (j+1)*bs)`` *in this exact context* (the chain threads
    every preceding block through the digest)."""
    t = np.asarray(tokens, np.int32).reshape(-1)
    keys: list[bytes] = []
    prev = b""
    for j in range(n_blocks):
        prev = chunk_key(prev, t[j * block_size:(j + 1) * block_size])
        keys.append(prev)
    return keys


@dataclasses.dataclass
class PoolCounters:
    """Allocator-level counters surfaced into ``SchedulerStats``."""

    allocs: int = 0
    evictions: int = 0
    cow_copies: int = 0
    prefix_block_lookups: int = 0
    prefix_block_hits: int = 0
    # full prompt[:-1] blocks that entered begin_request — the honest
    # hit-rate denominator (a lookup walk that stopped early would
    # otherwise undercount misses); with the full interior walk below,
    # lookups == prompt_blocks, but the counter keeps the denominator
    # exact by construction rather than by walk policy
    prompt_blocks: int = 0
    # interior splices: hits at a chunk boundary PAST the first miss —
    # the capability whole-prefix-walk prefix caching did not have
    chunk_interior_hits: int = 0
    in_use_peak: int = 0


class BlockAllocator:
    """Host-side block lifecycle: free list, refcounts, prefix index.

    Pure bookkeeping — no device arrays. ``num_blocks`` includes the
    reserved scratch block 0, which is never allocated.
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (one is the scratch)")
        self.num_blocks = int(num_blocks)
        self._state = [BlockState.FREE] * num_blocks
        self._ref = [0] * num_blocks
        self._state[SCRATCH_BLOCK] = BlockState.ACTIVE  # never handed out
        self._ref[SCRATCH_BLOCK] = 1
        self._free: collections.deque[int] = collections.deque(
            range(1, num_blocks))
        # LRU of cached (refcount-0 but indexed) blocks (an ordered set)
        self._evictable: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict())
        self._index: dict[bytes, int] = {}
        # a block can be addressable under several aliases — its legacy
        # whole-prefix byte key AND its chained chunk-boundary key name
        # the same content — so the reverse map holds every key
        self._keys_of: dict[int, list[bytes]] = {}
        self.counters = PoolCounters()
        # fault-injection hook (launch.faults): consulted at every
        # alloc(); returning True makes the alloc raise KVPoolError —
        # callers' rollback paths must leave state untouched
        self.fault_hook: Callable[[], bool] | None = None

    # -- introspection -----------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable blocks (scratch excluded)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_evictable(self) -> int:
        return len(self._evictable)

    @property
    def in_use(self) -> int:
        """Blocks held by live owners (staged or refcount >= 1)."""
        return self.capacity - self.num_free - self.num_evictable

    @property
    def occupancy(self) -> float:
        return self.in_use / self.capacity

    def state(self, bid: int) -> BlockState:
        return self._state[bid]

    def refcount(self, bid: int) -> int:
        return self._ref[bid]

    def _check(self, bid: int) -> None:
        if not 0 < bid < self.num_blocks:
            raise KVPoolError(f"block id {bid} out of range "
                              f"(1..{self.num_blocks - 1}; 0 is scratch)")

    # -- lifecycle ---------------------------------------------------------
    def can_alloc(self, n: int) -> bool:
        return self.num_free + self.num_evictable >= n

    def alloc(self) -> int:
        """free -> staged. Recycles the free list first; when dry, evicts
        the least-recently-released cached block (dropping its prefix
        index entry). Raises ``KVPoolError`` when nothing is left — the
        scheduler's cue to defer staging until a release frees blocks."""
        if self.fault_hook is not None and self.fault_hook():
            raise KVPoolError(
                "injected allocation failure (fault harness)"
            )
        if self._free:
            bid = self._free.popleft()
        elif self._evictable:
            bid, _ = self._evictable.popitem(last=False)  # LRU
            self._drop_keys(bid)
            self.counters.evictions += 1
        else:
            raise KVPoolError(
                f"KV pool exhausted: all {self.capacity} blocks are "
                "staged or active (no cached block to evict)"
            )
        if self._ref[bid] != 0:
            raise KVPoolError(
                f"block {bid} on the free path with refcount "
                f"{self._ref[bid]} (double allocation)"
            )
        self._state[bid] = BlockState.STAGED
        self._ref[bid] = 1
        self.counters.allocs += 1
        self.counters.in_use_peak = max(self.counters.in_use_peak,
                                        self.in_use)
        return bid

    def activate(self, bid: int) -> None:
        """staged -> active: staging finished, the owning request is
        admitted. Activating a block that was never staged (or is shared)
        is a protocol error."""
        self._check(bid)
        if self._state[bid] is not BlockState.STAGED:
            raise KVPoolError(
                f"activate on block {bid} in state "
                f"{self._state[bid].value!r} (must be staged)"
            )
        self._state[bid] = BlockState.ACTIVE

    def retain(self, bid: int) -> None:
        """Add an owner: a prefix hit on an active or cached block. A
        cached block revives off the eviction list."""
        self._check(bid)
        st = self._state[bid]
        if st is BlockState.CACHED:
            self._evictable.pop(bid)
            self._state[bid] = BlockState.ACTIVE
            self._ref[bid] = 1
            # a revival raises in_use exactly like an allocation does
            self.counters.in_use_peak = max(self.counters.in_use_peak,
                                            self.in_use)
            return
        if st is not BlockState.ACTIVE:
            raise KVPoolError(
                f"retain on block {bid} in state {st.value!r} "
                "(only active/cached blocks can gain owners)"
            )
        self._ref[bid] += 1

    def release(self, bid: int) -> None:
        """Drop one owner. At refcount 0 a prefix-indexed block becomes
        cached (evictable, still addressable by content); an unindexed
        one returns to the free list. Releasing below zero raises."""
        self._check(bid)
        if self._state[bid] is BlockState.FREE or self._ref[bid] < 1:
            raise KVPoolError(
                f"release on block {bid} (state {self._state[bid].value!r}, "
                f"refcount {self._ref[bid]}): refcounts never go negative"
            )
        self._ref[bid] -= 1
        if self._ref[bid] > 0:
            return
        if self._keys_of.get(bid):
            self._state[bid] = BlockState.CACHED
            self._evictable[bid] = None  # most-recently released = MRU
        else:
            self._state[bid] = BlockState.FREE
            self._free.append(bid)

    def evict_cached(self, n: int | None = None) -> int:
        """Force-evict up to ``n`` cached blocks LRU-first (all of them
        when ``n`` is None): drop the prefix-index entries and return
        the blocks to the free list. Live owners are untouched — only
        refcount-0 indexed blocks are evictable, so this can never pull
        a block out from under a request (or a spilled request: spill
        releases every block and restores from host copies). This is
        the eviction-storm injection site and a memory-pressure valve."""
        count = 0
        while self._evictable and (n is None or count < n):
            bid, _ = self._evictable.popitem(last=False)  # LRU
            self._drop_keys(bid)
            self._state[bid] = BlockState.FREE
            self._free.append(bid)
            self.counters.evictions += 1
            count += 1
        return count

    def _drop_keys(self, bid: int) -> None:
        """Eviction half of hash-consing: forget every alias the block
        was addressable under (whole-prefix and chunk keys drop as one
        — they name the same content, so a partial drop could never be
        coherent)."""
        for key in self._keys_of.pop(bid, []):
            del self._index[key]

    # -- prefix index (hash-consing) ---------------------------------------
    def lookup(self, key: bytes) -> int | None:
        self.counters.prefix_block_lookups += 1
        bid = self._index.get(key)
        if bid is not None:
            self.counters.prefix_block_hits += 1
        return bid

    def lookup_any(self, keys) -> int | None:
        """One COUNTED lookup across alias keys naming the same content
        (chunk-boundary key first, whole-prefix key as the fallback).
        However many aliases are probed, the stats see one block-level
        lookup and at most one hit — the hit rate measures content
        reuse, not key-scheme redundancy."""
        self.counters.prefix_block_lookups += 1
        for key in keys:
            bid = self._index.get(key)
            if bid is not None:
                self.counters.prefix_block_hits += 1
                return bid
        return None

    def peek(self, key: bytes) -> int | None:
        """Side-effect-free index probe: no counters, no LRU touch. The
        replica router calls this across the whole fleet per request —
        counting those probes would drown the real hit-rate stats."""
        return self._index.get(key)

    def is_registered(self, bid: int) -> bool:
        """Is the block addressable by content (under any alias)?"""
        return bool(self._keys_of.get(bid))

    def register(self, key: bytes, bid: int) -> int:
        """Hash-cons: publish ``bid`` as THE block for ``key``. If the
        key is already taken (a concurrent request staged the same
        content), the existing block wins and ``bid`` stays a private
        unshared copy — returns the canonical id either way. A block
        may register under several keys (whole-prefix + chunk-boundary
        aliases of the same content); all of them drop together at
        eviction."""
        self._check(bid)
        if self._state[bid] is not BlockState.ACTIVE:
            raise KVPoolError(
                f"register on block {bid} in state "
                f"{self._state[bid].value!r} (must be active: blocks are "
                "published at admission, after staging completes)"
            )
        existing = self._index.get(key)
        if existing is not None:
            return existing
        self._index[key] = bid
        self._keys_of.setdefault(bid, []).append(key)
        return bid


# ---------------------------------------------------------------------------
# Device pool: the model's own cache specs at (batch=num_blocks,
# max_len=block_size), with per-leaf axes probed from spec diffs.
# ---------------------------------------------------------------------------


def _diff_axis(a, b) -> int:
    for i, (x, y) in enumerate(zip(a.shape, b.shape)):
        if x != y:
            return i
    raise ValueError(
        f"cache leaf {a.shape} has no differing axis between probes; "
        "the paged pool cannot address it"
    )


def probe_batch_axes(api, cfg, minfo, max_len: int):
    """Which axis of each cache leaf is the batch (slot/block) axis?
    Diff the spec shapes for batch=2 vs batch=3."""
    s2 = api.cache_specs(cfg, minfo, 2, max_len)
    s3 = api.cache_specs(cfg, minfo, 3, max_len)
    return jax.tree.map(_diff_axis, s2, s3, is_leaf=L.is_spec)


def probe_length_axes(api, cfg, minfo, batch: int):
    """Which axis of each cache leaf is the sequence-length axis? Diff
    the spec shapes for max_len=16 vs max_len=32. Together with the
    batch axis this fully describes how a leaf pages: pool leaves carry
    blocks on the batch axis and ``block_size`` positions on the length
    axis, whatever the family's layout (GQA 5-D KV, int8 scales, MLA
    latent)."""
    s16 = api.cache_specs(cfg, minfo, batch, 16)
    s32 = api.cache_specs(cfg, minfo, batch, 32)
    return jax.tree.map(_diff_axis, s16, s32, is_leaf=L.is_spec)


class KVPool:
    """The physical pooled cache plus generic block-granular device ops.

    ``cache`` is a normal model cache pytree whose probed batch axis has
    ``num_blocks`` entries and probed length axis ``block_size``
    positions — the scheduler hands it (plus block tables) straight to
    the model's decode/prefill steps, where attention scatters/gathers
    through the tables. The helpers here are the *generic* paths used
    off the hot loop: copy-on-write block copies and dense
    reconstruction for tests.
    """

    def __init__(self, api, cfg, minfo, *, num_blocks: int,
                 block_size: int, shardings=None) -> None:
        self.cfg = cfg
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.batch_axes = probe_batch_axes(api, cfg, minfo, block_size)
        self.length_axes = probe_length_axes(api, cfg, minfo, num_blocks)
        # made on the device in one program; under tensor-parallel
        # serving ``shardings`` puts the KV-head axes on the mesh's
        # "model" axis from the start, so no chip ever holds the whole
        # pool
        self.cache = jax.jit(
            lambda: api.init_cache(cfg, minfo, num_blocks, block_size),
            out_shardings=shardings)()

    def copy_blocks(self, dst: list[int], src: list[int]) -> None:
        """Device copy pool[src] -> pool[dst] on every leaf (the
        copy-on-write primitive). Eager jnp ops — rare path, smoke-scale
        tensors; the hot paths never copy."""
        if not dst:
            return
        d = jnp.asarray(dst, jnp.int32)
        s = jnp.asarray(src, jnp.int32)
        self.cache = jax.tree.map(
            lambda f, ax: f.at[(slice(None),) * ax + (d,)].set(
                jnp.take(f, s, axis=ax)),
            self.cache, self.batch_axes,
        )

    def gather(self, tables) -> dict:
        """Dense per-request cache reconstruction: block tables ``(B,
        nb)`` -> a cache tree shaped exactly like the slot scheduler's
        slab with ``max_len = nb * block_size`` — the bit-exactness
        bridge the segment programs decode through."""
        return gather_blocks(self.cache, self.batch_axes,
                             self.length_axes, tables)

    def read_blocks(self, bids: list[int]) -> list:
        """Device -> host copy of whole blocks, one pytree of numpy
        leaves per block (dtype-preserving, so a later ``write_blocks``
        round-trip is bit-exact). The preemption spill path — rare by
        construction, so eager per-leaf ``jnp.take`` is fine."""
        if not bids:
            return []
        b = jnp.asarray(bids, jnp.int32)
        batch = jax.tree.map(
            lambda f, ax: np.asarray(jnp.take(f, b, axis=ax)),
            self.cache, self.batch_axes,
        )
        return [
            jax.tree.map(
                lambda leaf, ax: np.take(leaf, j, axis=ax),
                batch, self.batch_axes,
            )
            for j in range(len(bids))
        ]

    def write_blocks(self, bids: list[int], payloads: list) -> None:
        """Host -> device: write spilled block payloads (as produced by
        ``read_blocks``) back into pool blocks ``bids`` — the restore
        half of preemption. Bit-exact: same dtypes, whole-block set."""
        if not bids:
            return
        b = jnp.asarray(bids, jnp.int32)
        stacked = jax.tree.map(
            lambda *leaves: np.stack(leaves), *payloads)
        self.cache = jax.tree.map(
            lambda f, s, ax: f.at[(slice(None),) * ax + (b,)].set(
                jnp.moveaxis(jnp.asarray(s), 0, ax).astype(f.dtype)),
            self.cache, stacked, self.batch_axes,
        )


def gather_blocks(cache, batch_axes, length_axes, tables):
    """Pool -> dense slab view, per leaf, jit-traceable. The paged
    segment program runs this ONCE at entry, decodes every step on the
    dense view with the slab scheduler's own (aligned/ragged) machinery,
    and ``scatter_blocks`` writes the blocks back at exit — block
    bookkeeping costs O(1) gathers per segment, not per token. With the
    paged decode kernel this pair is OFF the hot path entirely (the
    ``kernel="slab"`` reference and COW/tests keep it); the dispatch
    record below is the observable tests assert that on."""
    kops.record_dispatch("gather_blocks", "dma")
    t = jnp.asarray(tables, jnp.int32)

    def leaf(f, ba, la):
        g = jnp.take(f, t, axis=ba)              # axis ba -> (B, nb)
        # merge the (nb, block) pair back into one length axis; the
        # batch axis precedes the length axis in every family layout,
        # so the block axis sits at la + 1 after the take
        g = jnp.moveaxis(g, ba + 1, la)
        shape = list(g.shape)
        merged = shape[la] * shape[la + 1]
        return g.reshape(*shape[:la], merged, *shape[la + 2:])

    return jax.tree.map(leaf, cache, batch_axes, length_axes)


def scatter_blocks(cache, dense, batch_axes, length_axes, tables):
    """Dense slab view -> pool, the inverse of ``gather_blocks``.

    Every table entry is written back wholesale. Blocks shared between
    rows (prefix hits) or with the index receive the values they already
    hold — decode only writes positions inside each row's exclusive
    blocks (the copy-on-write invariant) — and duplicate scratch entries
    receive junk nothing reads, so the scatter is order-independent."""
    kops.record_dispatch("scatter_blocks", "dma")
    t = jnp.asarray(tables, jnp.int32)

    def leaf(f, g, ba, la):
        shape = list(g.shape)
        bs = f.shape[la]
        nb = shape[la] // bs
        g = g.reshape(*shape[:la], nb, bs, *shape[la + 1:])
        g = jnp.moveaxis(g, la, ba + 1)          # (…, B, nb, …, bs, …)
        return f.at[(slice(None),) * ba + (t,)].set(g.astype(f.dtype))

    return jax.tree.map(leaf, cache, dense, batch_axes, length_axes)


def validate_tables(tables, num_blocks: int) -> None:
    """Host-side bounds check on a block-table batch before dispatch.

    The device paths deliberately carry NO bounds machinery: the paged
    gathers declare ``mode="promise_in_bounds"`` and the paged-attention
    kernel's table-indexed DMA would read whatever pool row a corrupt
    entry names. This is the promise's enforcement point — cheap numpy
    on a (B, nb) int table, raising ``KVPoolError`` instead of letting
    a stale/sentinel entry silently alias block 0 (the old ``jnp.take``
    clipping behaviour) or a neighbour's block.
    """
    t = np.asarray(tables)
    if t.size == 0:
        return
    lo, hi = int(t.min()), int(t.max())
    if lo < 0 or hi >= num_blocks:
        raise KVPoolError(
            f"block table entry out of range: min {lo}, max {hi} for a "
            f"pool of {num_blocks} blocks — stale or corrupt table"
        )


# ---------------------------------------------------------------------------
# Request-level orchestration: tables, prefix splicing, COW.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RequestBlocks:
    """One request's logical->physical mapping while it lives in the
    pool: ``bids[j]`` backs positions ``[j*bs, (j+1)*bs)``."""

    bids: list[int]
    prefix_hit_blocks: int      # LEADING bids spliced from the index
    span: int                   # positions covered: len(bids) * bs
    # every spliced block index, interior holes included — a superset
    # of range(prefix_hit_blocks); staging prefills the complement
    hit_idx: tuple[int, ...] = ()

    def table_row(self, width: int) -> np.ndarray:
        row = np.full((width,), SCRATCH_BLOCK, np.int32)
        row[: len(self.bids)] = self.bids
        return row


class PagedKVManager:
    """Allocator + device pool + prefix index, with request-granular ops.

    The scheduler talks to this and to nothing lower: ``begin_request``
    (prefix splice + atomic span allocation, at staging start),
    ``publish_prompt`` (activate + hash-cons full prompt blocks, at
    admission), ``ensure_exclusive`` (copy-on-write before a write into
    a shared block), ``release_request`` (at retirement). Lazy growth
    goes through ``ensure_span`` (the scheduler's ``_grow_active``);
    preemption through ``spill_request``/``restore_request``.

    ``spare_blocks`` appends that many physical rows to the device pool
    WITHOUT registering them with the allocator: their ids are
    ``num_blocks .. num_blocks + spare_blocks - 1`` (``spare_ids``).
    They are scratch — never refcounted, never hash-consed, never
    spilled — and exist for the speculative decoder: drafted tokens
    write into a slot's private spares spliced into its verify table,
    and only ACCEPTED positions are copied into allocator-owned blocks
    (``pool.copy_blocks``). A rejected draft therefore leaves zero
    trace in ``counters`` — not as an accounting convention but because
    the allocator genuinely never saw it.
    """

    def __init__(self, api, cfg, minfo, *, num_blocks: int,
                 block_size: int, shardings=None,
                 spare_blocks: int = 0) -> None:
        self.block_size = int(block_size)
        self.spare_blocks = int(spare_blocks)
        self.alloc = BlockAllocator(num_blocks)
        self.pool = KVPool(api, cfg, minfo,
                           num_blocks=num_blocks + self.spare_blocks,
                           block_size=block_size, shardings=shardings)

    @property
    def spare_ids(self) -> range:
        """Physical ids of the scratch rows past the allocator's reach."""
        return range(self.alloc.num_blocks,
                     self.alloc.num_blocks + self.spare_blocks)

    @property
    def counters(self) -> PoolCounters:
        return self.alloc.counters

    def blocks_needed(self, n_positions: int) -> int:
        return -(-int(n_positions) // self.block_size)

    def _prompt_keys(self, prompt: np.ndarray,
                     n_blocks: int) -> list[tuple[bytes, bytes]]:
        """Per-block alias key pairs (chunk-boundary, whole-prefix) for
        the first ``n_blocks`` full blocks of ``prompt``. Both name the
        same content; publication registers both, probes try both."""
        cks = chunk_keys(prompt, n_blocks, self.block_size)
        return [(cks[j], prefix_key(prompt, (j + 1) * self.block_size))
                for j in range(n_blocks)]

    def _peek_block(self, keys: tuple[bytes, bytes]) -> int | None:
        for key in keys:
            bid = self.alloc.peek(key)
            if bid is not None:
                return bid
        return None

    def prefix_affinity(self, prompt: np.ndarray) -> int:
        """How many leading full ``prompt[:-1]`` blocks this pool already
        holds — the router's steering signal. Pure ``peek``: no counter
        or LRU side effects, so probing every replica per request leaves
        the per-replica prefix stats untouched."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_full = (int(prompt.size) - 1) // self.block_size
        hits = 0
        for keys in self._prompt_keys(prompt, n_full):
            if self._peek_block(keys) is None:
                break
            hits += 1
        return hits

    def chunk_affinity(self, prompt: np.ndarray) -> int:
        """Chunk-granular affinity: how many of the prompt's full
        ``prompt[:-1]`` blocks — interior chunk boundaries INCLUDED,
        not just the leading run — this pool holds. Always >=
        ``prefix_affinity``; the router steers by it so a replica whose
        leading block was evicted but whose retrieved-chunk blocks
        survive still wins the request. Pure ``peek``, like
        ``prefix_affinity``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_full = (int(prompt.size) - 1) // self.block_size
        return sum(1 for keys in self._prompt_keys(prompt, n_full)
                   if self._peek_block(keys) is not None)

    def check_span(self, rb: RequestBlocks, end: int) -> None:
        """Host-side companion to the device write's ``mode="drop"``:
        a decode segment about to write positions up to ``end - 1``
        must stay inside the request's allocated span. The device path
        silently DROPS out-of-table writes (never corrupting a
        neighbour); this check makes the scheduling bug that would have
        produced them loud instead of a token-quality mystery."""
        if end > rb.span:
            raise KVPoolError(
                f"write frontier {end} exceeds the request's allocated "
                f"span {rb.span} ({len(rb.bids)} blocks of "
                f"{self.block_size}) — segment length outran allocation"
            )

    def begin_request(self, prompt: np.ndarray, n_positions: int
                      ) -> RequestBlocks | None:
        """Start staging: splice every full prompt[:-1] block already in
        the index (refcount bump, zero compute), then allocate fresh
        staged blocks for the rest of the request's whole KV span
        (``n_positions`` = prompt + generation - 1 write positions).

        The walk is chunk-granular and does NOT stop at the first miss:
        a block found at an interior chunk boundary (its chained key
        covers content + offset + full preceding context, so the splice
        is bit-exact by construction) is spliced even when an earlier
        block was evicted — staging then prefills only the holes. The
        stats see every full prompt block as one lookup
        (``prompt_blocks`` is the honest hit-rate denominator) and
        interior splices separately (``chunk_interior_hits``).

        Atomic: returns ``None`` without side effects when the pool
        cannot cover the remainder (the scheduler defers staging)."""
        bs = self.block_size
        need = self.blocks_needed(n_positions)
        n_full = (int(prompt.size) - 1) // bs
        n_walk = min(n_full, need)
        hits: list[tuple[int, int]] = []     # (block index j, bid)
        miss_seen = False
        leading = 0
        for j, keys in enumerate(self._prompt_keys(prompt, n_walk)):
            bid = self.alloc.lookup_any(keys)
            if bid is None:
                miss_seen = True
                continue
            hits.append((j, bid))
            if miss_seen:
                self.counters.chunk_interior_hits += 1
            else:
                leading += 1
        self.counters.prompt_blocks += n_walk
        # retain-then-check: reviving a cached hit removes it from the
        # evictable pool, so availability must be measured AFTER the
        # retains — checking can_alloc first would double-count revived
        # hits as still-evictable and let alloc() raise mid-loop.
        for _, bid in hits:
            self.alloc.retain(bid)
        fresh_needed = need - len(hits)
        fresh: list[int] = []
        try:
            if not self.alloc.can_alloc(fresh_needed):
                raise KVPoolError("pool cannot cover the span")
            for _ in range(fresh_needed):
                fresh.append(self.alloc.alloc())
        except KVPoolError:
            # atomic rollback: an alloc CAN raise past the can_alloc
            # check (injected failure) — the splice's refcount bumps and
            # any partially allocated fresh blocks must all unwind, or
            # the hits leak a reference forever
            for bid in fresh:
                self.alloc.release(bid)
            for _, bid in hits:  # revived hits re-cache
                self.alloc.release(bid)
            return None
        # weave spliced and fresh blocks into table order
        by_idx = dict(hits)
        it = iter(fresh)
        bids = [by_idx[j] if j in by_idx else next(it)
                for j in range(need)]
        return RequestBlocks(bids=bids,
                             prefix_hit_blocks=leading,
                             span=need * bs,
                             hit_idx=tuple(sorted(by_idx)))

    def ensure_span(self, rb: RequestBlocks, n_positions: int) -> bool:
        """Lazy growth: extend ``rb`` with fresh exclusive blocks until
        it covers ``n_positions`` write positions. Allocated blocks go
        straight to active (they back this request's own generated
        tokens — never published, never shared). Atomic: on exhaustion
        or injected failure the partial growth unwinds and the request
        keeps its old span — False is the scheduler's preemption cue."""
        need = self.blocks_needed(n_positions)
        if need <= len(rb.bids):
            return True
        got: list[int] = []
        try:
            for _ in range(need - len(rb.bids)):
                bid = self.alloc.alloc()
                self.alloc.activate(bid)
                got.append(bid)
        except KVPoolError:
            for bid in got:
                self.alloc.release(bid)
            return False
        rb.bids.extend(got)
        rb.span = len(rb.bids) * self.block_size
        return True

    def spill_request(self, rb: RequestBlocks, valid_end: int) -> dict:
        """Preemption: copy the blocks holding the request's first
        ``valid_end`` positions of KV to host, then release EVERY block
        the request owns. The victim keeps no pool references at all —
        shared prefix blocks drop to cached (still evictable; restore
        re-splices them if they survive, rewrites them if not), so a
        spilled request can never be the reason an eviction is unsafe.
        Returns the host payload for a ``SidebarSpillRegion`` entry."""
        n = min(self.blocks_needed(valid_end), len(rb.bids))
        blocks = self.pool.read_blocks(rb.bids[:n])
        nbytes = sum(
            leaf.nbytes for payload in blocks
            for leaf in jax.tree.leaves(payload))
        self.release_request(rb)
        return {"blocks": blocks, "n_blocks": n, "nbytes": nbytes}

    def restore_request(self, prompt: np.ndarray, payload: dict,
                        ) -> RequestBlocks | None:
        """Resume a spilled request: re-acquire one pool block per
        spilled block — splicing any full ``prompt[:-1]`` block still in
        the prefix index (bit-identical by hash-consing; prefill KV is a
        pure function of the prefix) and writing the host copy into a
        fresh block otherwise — then re-publish the full prompt blocks.
        Atomic: on failure every acquired block unwinds and the caller
        keeps the payload (the request stays spilled). The write
        frontier resumes at ``valid_end`` inside an exclusive block, so
        the re-spliced prefix blocks are never written (same structural
        invariant as admission, still enforced by ``ensure_exclusive``).
        """
        bs = self.block_size
        n = payload["n_blocks"]
        n_full = (int(prompt.size) - 1) // bs
        n_walk = min(n_full, n)
        keys = self._prompt_keys(prompt, n_walk)
        acquired: list[tuple[int, bool]] = []   # (bid, spliced?)
        try:
            for j in range(n):
                bid = self.alloc.lookup_any(keys[j]) if j < n_walk else None
                if bid is not None:
                    self.alloc.retain(bid)
                    acquired.append((bid, True))
                else:
                    acquired.append((self.alloc.alloc(), False))
        except KVPoolError:
            for bid, _ in acquired:
                self.alloc.release(bid)
            return None
        fresh = [bid for bid, spliced in acquired if not spliced]
        self.pool.write_blocks(
            fresh,
            [payload["blocks"][j] for j, (_, spliced)
             in enumerate(acquired) if not spliced])
        for bid in fresh:
            self.alloc.activate(bid)
        spliced_js = tuple(j for j, (_, spliced) in enumerate(acquired)
                           if spliced)
        leading = 0
        for j in spliced_js:
            if j != leading:
                break
            leading += 1
        rb = RequestBlocks(
            bids=[bid for bid, _ in acquired],
            prefix_hit_blocks=leading,
            span=n * bs,
            hit_idx=spliced_js,
        )
        # re-publish: restored full prompt blocks re-enter the index
        # under both key families so later requests (and a re-preempted
        # restore) splice them whichever way they probe
        for j in range(n_walk):
            bid = rb.bids[j]
            if not self.alloc.is_registered(bid):
                for key in keys[j]:
                    self.alloc.register(key, bid)
        return rb

    def publish_prompt(self, prompt: np.ndarray, rb: RequestBlocks) -> None:
        """At admission: staged blocks go active, and every full
        prompt[:-1] block is hash-consed into the index — under both its
        whole-prefix key and its chunk-boundary key — so later requests
        splice it whichever way they probe. Spliced hit blocks (possibly
        sparse under interior-hole splicing) are already active and
        registered. (Blocks covering generated positions stay private:
        their future content depends on this request's own sampling
        stream, not on any shareable prefix.)"""
        hit = set(rb.hit_idx) if rb.hit_idx else set(
            range(rb.prefix_hit_blocks))
        n_full = (int(prompt.size) - 1) // self.block_size
        n_walk = min(n_full, len(rb.bids))
        keys = self._prompt_keys(prompt, n_walk)
        for j, bid in enumerate(rb.bids):
            if j in hit:
                continue
            self.alloc.activate(bid)
            if j < n_walk:
                for key in keys[j]:
                    self.alloc.register(key, bid)

    def ensure_exclusive(self, rb: RequestBlocks, block_idx: int) -> bool:
        """Copy-on-write: if the block backing ``block_idx`` is shared
        (refcount > 1) or published (another request could splice it
        between now and the write), divert this request onto a private
        copy before it writes. Returns True when a copy happened.

        The scheduler's structural invariant (sharing covers only full
        prompt[:-1] blocks; writes start at position ``S - 1``) makes
        this a no-op on today's paths — it is the protocol's safety net,
        and the property tests exercise it directly."""
        bid = rb.bids[block_idx]
        shared = (self.alloc.refcount(bid) > 1
                  or self.alloc.is_registered(bid))
        if not shared:
            return False
        new = self.alloc.alloc()       # comes out staged
        self.pool.copy_blocks([new], [bid])
        if self.alloc.state(bid) is BlockState.ACTIVE:
            self.alloc.activate(new)   # the copy mirrors the original
        rb.bids[block_idx] = new
        self.alloc.release(bid)
        self.counters.cow_copies += 1
        return True

    def release_request(self, rb: RequestBlocks) -> None:
        """Retirement: drop this request's ownership of every block.
        Published blocks whose refcount reaches zero stay cached
        (evictable) for future prefix hits; private ones free."""
        for bid in rb.bids:
            self.alloc.release(bid)
        rb.bids = []
