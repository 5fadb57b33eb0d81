"""Host-side bookkeeping for the paged KV cache (ISSUE 7).

The device half of paging lives in models/transformer.py (block pool +
block-table gather inside the compiled tick) and serving/engine.py (the
jitted paged tick / chunked prefill). Everything here is pure-Python
state the scheduler mutates between compiled calls:

  * `BlockAllocator` — a refcounted free list over the physical pool.
    Block 0 is reserved as the TRASH block: retired slots' table entries
    (and pad positions of chunked prefills) point at it, so their garbage
    writes can never land in a block another request owns. A block frees
    when its last reference drops — a slot's table entry and a radix-
    cache node each hold one.
  * `SlotPool` — one kind of cache the engine keeps blocks for: its
    allocator, the table the tick reads and each slot's block list. An
    engine holds a list of them: one entry for a model with one pool, a
    second (with a window, whose blocks retire while the stream runs)
    for a model whose layer kinds keep their own. Two numbers and a
    flag say how a pool's blocks follow the stream: how many positions
    one of its rows stands for (`stride`: a summary row a chunk), the
    `window` its layers see, and whether the window slides (a block
    retires once the window has passed it) or tumbles (`tumbling`: all
    of a window's blocks retire when the stream crosses into the next).
  * `RadixPrefixCache` — a block-granularity radix tree over prompt
    token ids (SGLang's RadixAttention at vLLM's block alignment): a
    node caches ONE full block (`block_size` tokens) of K/V under its
    parent's prefix. Admission walks the new prompt's full blocks down
    the tree; every hit is admitted by *reference* (the slot's table
    points at the cached physical block) instead of re-running prefill.
    Only whole blocks are ever shared, and a slot's writes always land
    in blocks it privately owns (its first unmatched block onward), so
    the copy-on-write discipline holds by construction — divergence
    within a block simply misses the cache and prefills a private copy.
    Eviction is LRU over leaf nodes whose block the cache is the sole
    owner of (evicting a block an active slot still reads would free
    nothing and lose reuse).

The leak invariant the engine asserts at teardown:
``free + resident == usable`` — every non-trash block is either on the
free list or accounted to at least one live reference.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools

import numpy as np


def block_hashes(tokens, block_size: int) -> list[str]:
    """Chained per-block content digests of ``tokens``'s full blocks:
    ``h[i] = blake2b(h[i-1] || tokens_of_block_i)``. Each digest names a
    whole PREFIX (not just its last block), so two replicas hold the
    same cached prefix iff they hold the same digest — the fleet prefix
    index's matching unit. blake2b, not Python's ``hash()``: the
    builtin is per-process salted (PYTHONHASHSEED), and these digests
    must agree between the router and its subprocess workers."""
    out: list[str] = []
    prev = b""
    for i in range(len(tokens) // block_size):
        blk = ",".join(
            str(int(t))
            for t in tokens[i * block_size:(i + 1) * block_size])
        prev = hashlib.blake2b(prev + blk.encode(),
                               digest_size=16).digest()
        out.append(prev.hex())
    return out


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` physical KV
    blocks of ``block_size`` tokens. Block 0 is the reserved trash block
    and is never handed out."""

    TRASH = 0

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks {num_blocks} must be >= 2 (block 0 is the "
                f"reserved trash block)")
        if block_size < 1:
            raise ValueError(f"block_size {block_size} must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # pop() hands out low block ids first (1, 2, ...)
        self._free = list(range(num_blocks - 1, 0, -1))
        self._refs: dict[int, int] = {}

    @property
    def usable(self) -> int:
        """Allocatable blocks (the pool minus the trash block)."""
        return self.num_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def resident(self) -> int:
        """Blocks currently referenced by at least one owner."""
        return len(self._refs)

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """n fresh blocks at refcount 1, or None if the free list is
        short (the caller decides: evict prefix cache, preempt, or
        wait)."""
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._refs[b] = 1
        return blocks

    def incref(self, block: int) -> None:
        if block == self.TRASH:
            raise ValueError("cannot reference the trash block")
        if block not in self._refs:
            raise ValueError(f"block {block} is not allocated")
        self._refs[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; returns True when the block actually
        freed back to the pool."""
        rc = self._refs.get(block)
        if rc is None:
            raise ValueError(f"block {block} is not allocated")
        if rc > 1:
            self._refs[block] = rc - 1
            return False
        del self._refs[block]
        self._free.append(block)
        return True

    def check_leaks(self, expected_resident: int = 0) -> None:
        """The teardown invariant: free + resident == usable, and — once
        every owner has released (slots retired, radix cleared) —
        resident is exactly ``expected_resident``."""
        if self.free_count + self.resident != self.usable:
            raise AssertionError(
                f"KV block leak: free {self.free_count} + resident "
                f"{self.resident} != usable {self.usable} "
                f"(held: {sorted(self._refs)})")
        if self.resident != expected_resident:
            raise AssertionError(
                f"KV block leak: {self.resident} blocks still referenced "
                f"at teardown (expected {expected_resident}): "
                f"{sorted(self._refs)}")


@dataclasses.dataclass
class SlotPool:
    """One kind of cache a paged engine keeps blocks for, and which slot
    holds which of them."""

    kind: str | None            # the `pool` id on the engine's spans
    table: str                  # the cache leaf the model reads ids from
    alloc: BlockAllocator
    tables: np.ndarray          # [slots, pages] ids, the tick's view
    blocks: list                # a slot's ids in logical order; 0 = retired
    # positions a query of this pool's layers sees, itself included: a
    # block goes back to `alloc` once the window has passed it. 0: every
    # position (the pool grows with the stream)
    window: int = 0
    # a slot's first logical block not yet retired, so that a sweep
    # never walks the dead prefix again
    first: np.ndarray | None = None
    # positions one row stands for: a block of `block_size` rows backs
    # `block_size * stride` positions
    stride: int = 1
    # a windowed pool's retirement: sliding, or a whole window at a time
    tumbling: bool = False

    @classmethod
    def empty(cls, kind, table, num_blocks, block_size, slots, pages,
              window=0, stride=1, tumbling=False):
        return cls(kind, table, BlockAllocator(num_blocks, block_size),
                   np.zeros((slots, pages), np.int32),
                   [[] for _ in range(slots)], window,
                   np.zeros(slots, np.int64), stride, tumbling)

    @property
    def in_use(self) -> int:
        return self.alloc.usable - self.alloc.free_count

    def block_of(self, position: int) -> int:
        """The logical block that holds `position`'s row."""
        return position // (self.alloc.block_size * self.stride)

    def blocks_for(self, positions: int) -> int:
        """Blocks that back the rows of the first `positions` positions."""
        return -(-positions // (self.alloc.block_size * self.stride))

    def retired_before(self, lo: int) -> int:
        """Leading logical blocks of a windowed pool that no query at or
        after position `lo` can see."""
        if self.tumbling:
            return self.block_of(lo // self.window * self.window)
        return self.block_of(max(0, lo - (self.window - 1)))

    @property
    def ids(self) -> dict:
        """What this pool's spans carry: its kind, where an engine keeps
        several to tell apart."""
        return {"pool": self.kind} if self.kind else {}


class _RadixNode:
    __slots__ = ("children", "parent", "key", "block", "last_use",
                 "hash", "remote")

    def __init__(self, parent, key, block, hash="", remote=False):
        self.children: dict[tuple, _RadixNode] = {}
        self.parent = parent
        self.key = key
        self.block = block
        self.last_use = 0
        # the node's chained prefix digest (block_hashes) — what the
        # replica publishes in its health frontier
        self.hash = hash
        # True when the block's K/V arrived over the fleet KV stream
        # (import_prefix_blocks) instead of local prefill — hits through
        # it are STEERED hits, counted separately from local ones
        self.remote = remote


class RadixPrefixCache:
    """Block-granularity radix tree mapping full-block token prefixes to
    the physical pool blocks holding their K/V. Each node owns one
    allocator reference on its block, so cached prefixes survive the
    admitting request's retirement and free only on eviction."""

    def __init__(self, allocator: BlockAllocator):
        self.alloc = allocator
        self._root = _RadixNode(None, None, None)
        self._clock = itertools.count(1)
        self._nodes = 0
        # admission-level counters the engine folds into its summary.
        # hit_tokens counts LOCAL hits only; steered hits (through
        # remote-imported blocks) land in remote_hit_tokens — keeping
        # hit_rate/token_hit_rate comparable to the pre-fleet stamps
        self.lookups = 0
        self.hits = 0
        self.lookup_tokens = 0
        self.hit_tokens = 0
        self.remote_hits = 0
        self.remote_hit_tokens = 0
        self.evictions = 0

    @property
    def block_count(self) -> int:
        """Blocks the cache currently holds a reference on."""
        return self._nodes

    def _keys(self, tokens) -> list[tuple]:
        bs = self.alloc.block_size
        return [tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                for i in range(len(tokens) // bs)]

    def match(self, tokens) -> list[int]:
        """Physical blocks backing the longest cached full-block prefix
        of ``tokens`` (possibly empty). Does NOT take references — the
        caller increfs the blocks it actually admits — and does NOT
        count toward the hit-rate stats (a pool-starved admission
        re-matches every retry; the engine records ONE
        ``record_admission`` when the admission actually lands).
        Touches the walked nodes' LRU clocks."""
        return [n.block for n in self.match_nodes(tokens)]

    def match_nodes(self, tokens) -> list:
        """Like match(), but returns the NODES — callers that need the
        remote flag (steered-hit accounting) or the prefix digests read
        them off the chain."""
        node, out = self._root, []
        for key in self._keys(tokens):
            child = node.children.get(key)
            if child is None:
                break
            child.last_use = next(self._clock)
            out.append(child)
            node = child
        return out

    def record_admission(self, matched_blocks: int, lookup_tokens: int,
                         remote_blocks: int = 0) -> None:
        """Fold one LANDED admission into the hit-rate counters.
        ``remote_blocks`` (of the matched) came from fleet-shipped
        prefix imports — they count as STEERED hits, kept out of the
        local hit_rate so it stays comparable across fleet topologies."""
        self.lookups += 1
        self.lookup_tokens += lookup_tokens
        local = matched_blocks - remote_blocks
        if local:
            self.hits += 1
            self.hit_tokens += local * self.alloc.block_size
        if remote_blocks:
            self.remote_hits += 1
            self.remote_hit_tokens += remote_blocks * self.alloc.block_size

    def insert(self, tokens, blocks, remote: bool = False) -> int:
        """Register ``blocks`` as the cache entries for the full-block
        prefix of ``tokens`` (``len(blocks)`` blocks' worth). Prefix
        nodes that already exist keep their block (the caller was
        admitted THROUGH them, so blocks[i] is the same physical id);
        new nodes take one allocator reference each and are stamped
        ``remote`` when their K/V arrived over the fleet KV stream.
        Returns how many new blocks were cached."""
        hashes = block_hashes(tokens, self.alloc.block_size)
        node, added = self._root, 0
        for key, block, hsh in zip(self._keys(tokens), blocks, hashes):
            child = node.children.get(key)
            if child is None:
                self.alloc.incref(block)
                child = _RadixNode(node, key, block, hash=hsh,
                                   remote=remote)
                node.children[key] = child
                self._nodes += 1
                added += 1
            child.last_use = next(self._clock)
            node = child
        return added

    def frontier(self, limit: int = 64) -> list[str]:
        """The most-recently-used ``limit`` cached prefix digests — what
        health() publishes for the router's FleetPrefixIndex. Every
        cached node's digest is a candidate (an internal node is a
        valid shorter match for a prompt that diverges below it);
        recency-bounded so a subprocess replica's health row stays a
        small JSON line, and hot prefixes (the ones worth steering
        toward) survive the bound."""
        nodes: list[_RadixNode] = []
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            nodes.append(n)
            stack.extend(n.children.values())
        nodes.sort(key=lambda n: n.last_use, reverse=True)
        return [n.hash for n in nodes[:limit]]

    def _evictable_leaves(self) -> list[_RadixNode]:
        out = []
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif self.alloc.refcount(n.block) == 1:
                # the cache is the sole owner: evicting actually frees
                out.append(n)
        return out

    def evictable_count(self) -> int:
        """How many blocks cascading leaf eviction could actually free:
        sole-owner nodes whose entire subtree is sole-owner too (a
        shared descendant pins its whole ancestor chain, since only
        leaves ever drop). Lets the engine check feasibility BEFORE
        destroying reusable prefixes on a reclaim that cannot cover the
        allocation anyway."""
        # iterative post-order (a full-length cached prompt is a chain
        # max_seq_len/block_size deep — don't lean on the recursion
        # limit): freeable(node) = all children freeable AND sole-owner
        order: list[_RadixNode] = []
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            order.append(n)
            stack.extend(n.children.values())
        total = 0
        freeable: dict[int, bool] = {}
        for n in reversed(order):  # children before parents
            ok = (all(freeable[id(c)] for c in n.children.values())
                  and self.alloc.refcount(n.block) == 1)
            freeable[id(n)] = ok
            total += ok
        return total

    def reclaim(self, n: int) -> int:
        """Evict LRU sole-owner leaves until ``n`` blocks have freed (or
        nothing evictable remains). Returns blocks actually freed."""
        freed = 0
        while freed < n:
            leaves = self._evictable_leaves()
            if not leaves:
                break
            for leaf in sorted(leaves, key=lambda x: x.last_use):
                if freed >= n:
                    break
                self._drop(leaf)
                freed += 1
        return freed

    def _drop(self, node: _RadixNode) -> None:
        del node.parent.children[node.key]
        self.alloc.decref(node.block)
        self._nodes -= 1
        self.evictions += 1

    def clear(self) -> int:
        """Release every cached block (teardown / post-warmup flush)."""
        freed = 0
        stack = list(self._root.children.values())
        order = []
        while stack:
            n = stack.pop()
            order.append(n)
            stack.extend(n.children.values())
        for n in reversed(order):  # children before parents
            self._drop(n)
            freed += 1
        return freed

    def reset_stats(self) -> None:
        """Zero the hit-rate counters (post-warmup flush) — cached
        content and LRU state are untouched."""
        self.lookups = self.hits = 0
        self.lookup_tokens = self.hit_tokens = 0
        self.remote_hits = self.remote_hit_tokens = 0
        self.evictions = 0

    def stats(self) -> dict:
        return {
            "lookups": self.lookups,
            "hits": self.hits,
            "hit_rate": (round(self.hits / self.lookups, 4)
                         if self.lookups else None),
            "hit_tokens": self.hit_tokens,
            "lookup_tokens": self.lookup_tokens,
            "token_hit_rate": (
                round(self.hit_tokens / self.lookup_tokens, 4)
                if self.lookup_tokens else None),
            # steered hits (fleet-shipped prefix blocks) — split out so
            # hit_rate above stays the LOCAL rate, comparable to the
            # per-engine stamps from before the fleet index existed
            "remote_hits": self.remote_hits,
            "remote_hit_tokens": self.remote_hit_tokens,
            "remote_token_hit_rate": (
                round(self.remote_hit_tokens / self.lookup_tokens, 4)
                if self.lookup_tokens else None),
            "cached_blocks": self._nodes,
            "evictions": self.evictions,
        }


class FleetPrefixIndex:
    """The router-owned fleet-wide view of every replica's radix
    frontier (the tentpole's cross-replica half): each replica publishes
    its cached prefix digests (``RadixPrefixCache.frontier()``) through
    ``health()`` snapshots; the dispatcher asks this index which replica
    holds the LONGEST cached prefix of an incoming prompt's digest chain
    (``block_hashes``) and steers the request there — or, when the owner
    can't take it, ships the matched blocks over the KV stream so a hot
    system prompt is prefilled once per fleet, not once per replica.
    Pure host state; refreshed (not accumulated) per snapshot, so a
    replica's evictions and deaths age out of the index naturally."""

    def __init__(self):
        self._frontiers: dict[int, set[str]] = {}

    def update(self, replica: int, hashes) -> None:
        """Replace ``replica``'s published frontier with this snapshot's."""
        self._frontiers[replica] = set(hashes or ())

    def add(self, replica: int, hashes) -> None:
        """Extend ``replica``'s frontier in place — the router's
        optimistic bookkeeping right after a prefix ship, so a burst of
        same-prefix arrivals doesn't re-ship the same blocks every
        dispatch until the next health snapshot replaces the set."""
        self._frontiers.setdefault(replica, set()).update(hashes or ())

    def remove(self, replica: int) -> None:
        self._frontiers.pop(replica, None)

    def match_depth(self, replica: int, hash_chain) -> int:
        """Longest prefix (in blocks) of ``hash_chain`` this replica
        published. Digests are chained, so membership of ``chain[i]``
        alone proves the whole i+1-block prefix is cached there."""
        have = self._frontiers.get(replica)
        if not have:
            return 0
        depth = 0
        for h in hash_chain:
            if h not in have:
                break
            depth += 1
        return depth

    def best_match(self, hash_chain, eligible=None) -> tuple[int | None,
                                                             int]:
        """(replica, depth) of the deepest published match — the
        steering target. ``eligible`` restricts candidates; ties break
        toward the lowest replica index (deterministic). (None, 0) when
        nobody holds any prefix of the chain."""
        best, best_depth = None, 0
        for rep in sorted(self._frontiers):
            if eligible is not None and rep not in eligible:
                continue
            d = self.match_depth(rep, hash_chain)
            if d > best_depth:
                best, best_depth = rep, d
        return best, best_depth

    def replicas(self) -> list[int]:
        return sorted(self._frontiers)


class FleetSessionIndex:
    """FleetPrefixIndex's sibling for persistent sessions (ISSUE 18):
    the router-owned map of which replica holds a session RESIDENT in
    its HBM tier (blocks parked after stream close). Replicas publish
    their resident session ids through ``health()`` snapshots
    (``session_frontier``); the dispatcher steers a reattaching
    ``submit(session_id=...)`` to the owner — a zero-copy radix
    re-seed there — before falling back to the router's host-DRAM/disk
    ``SessionStore`` tiers. Pure host state; refreshed (not
    accumulated) per snapshot, so demotions, evictions and replica
    deaths age out naturally."""

    def __init__(self):
        self._resident: dict[int, set[str]] = {}

    def update(self, replica: int, session_ids) -> None:
        """Replace ``replica``'s published resident set."""
        self._resident[replica] = set(session_ids or ())

    def add(self, replica: int, session_id: str) -> None:
        """Optimistic bookkeeping right after a steered reattach or a
        finished session stream — the owner answers for the session
        before the next health snapshot confirms it."""
        self._resident.setdefault(replica, set()).add(session_id)

    def discard(self, session_id: str) -> None:
        """Forget a session fleet-wide (demoted into the store, or
        dropped)."""
        for have in self._resident.values():
            have.discard(session_id)

    def remove(self, replica: int) -> None:
        self._resident.pop(replica, None)

    def owner(self, session_id: str, eligible=None) -> int | None:
        """The replica holding ``session_id`` resident, or None. Ties
        (stale overlapping snapshots) break toward the lowest index —
        deterministic steering, exactly like best_match."""
        for rep in sorted(self._resident):
            if eligible is not None and rep not in eligible:
                continue
            if session_id in self._resident[rep]:
                return rep
        return None

    def sessions(self, replica: int) -> set[str]:
        return set(self._resident.get(replica, ()))
