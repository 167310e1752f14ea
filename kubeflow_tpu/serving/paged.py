"""Paged KV-cache bookkeeping: block pool + radix prefix cache.

This module is pure host-side Python — no jax. The device arrays (the
block pool itself, `[L, num_blocks, block_size, *cell]`) live inside
`ContinuousEngine`'s `SlotState`; here we only track which physical
blocks are free, which are owned by an in-flight request, and which are
retained by the radix tree for cross-request prefix reuse.

Conventions
-----------
- Block 0 is the reserved *trash* block. Unallocated block-table entries
  point at it, and writes from retired-but-not-yet-reset slots land
  there harmlessly. It is never handed out by the pool.
- The radix tree has one node per *full* block: an edge is exactly
  `block_size` tokens. Partial-block prefixes are matched by comparing
  against a child's key and are handled by the caller as copy-on-write
  (the matched block seeds the prefill state; the new request writes its
  own fresh block, so the shared one is never mutated).
- `refs` on a node counts *active requests whose block table points at
  that physical block*. Only refcount-0 nodes may be evicted, and only
  leaves (evicting an interior node would orphan its children's token
  paths).

Write disjointness
------------------
The fused prefill/append kernel (`ops/pallas/prefill_append.py`)
rewrites every block it visits *in full* — including the cells below
each row's cursor, which it writes back as the content it read. That
is only safe under the invariant this module maintains by
construction: **a row's write range `[q_start, q_start + q_lens)`
lies in blocks no OTHER row's block table references.**

Concretely:

- New cells land only in *fresh* blocks the pool just allocated to
  exactly one request (`BlockPool` hands a block to one owner; the
  `_free_set` mirror makes double-allocation impossible).
- Radix-shared blocks sit strictly *below* every sharer's cursor:
  the tree only indexes full blocks of already-written prompt prefix,
  and a partial-block match is copy-on-write (the new request copies
  the cells into its own fresh block rather than appending into the
  shared one). A visited shared block is therefore read-only for all
  sharers, and the kernel's full-block rewrite reproduces its
  contents bit-for-bit.
- Concurrent rows in one fused dispatch come from different slots,
  whose table tails are disjoint fresh chains — so no two rows'
  write ranges can alias.

`tests/test_prefill_append_kernel.py` pins the consequences (shared
block survives both sharers' visits byte-identically; unvisited
blocks untouched) but the invariant itself is a *precondition* the
engine guarantees, not a behavior the kernel checks at runtime.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable

from kubeflow_tpu.obs.cachestats import canonical_prefix
from kubeflow_tpu.obs.cardinality import LabelGuard

__all__ = ["BlockPool", "HostSpillTier", "RadixPrefixCache",
           "TRASH_BLOCK"]

TRASH_BLOCK = 0


class BlockPool:
    """Free-list allocator over physical KV block ids `[1, num_blocks)`.

    Block 0 (trash) is reserved and never allocated. The pool knows
    nothing about the radix tree; blocks held by the tree are simply
    "in use" until `RadixPrefixCache.evict` returns them.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 trash + 1 usable), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # LIFO off the tail; initialised so the first allocs are 1, 2, ...
        self._free = list(range(num_blocks - 1, 0, -1))
        # Membership mirror of _free: free() must reject a block that is
        # already free (double-free would hand the same physical block to
        # two owners and silently corrupt both sequences' KV).
        self._free_set = set(self._free)
        # Optional obs.CacheLedger: when attached, every alloc/free is
        # booked (frees to a CAUSE), giving the eviction-forensics
        # metrics their conservation guarantee at the only chokepoint
        # blocks actually pass through.
        self.ledger = None

    def attach_ledger(self, ledger) -> None:
        """Attach a lifecycle ledger. Must happen before the first
        alloc, or the ledger's birth count can't reconcile against
        `in_use` (the conservation invariant CI asserts)."""
        if self.in_use:
            raise ValueError(
                f"ledger attached with {self.in_use} blocks already live")
        self.ledger = ledger

    @property
    def capacity(self) -> int:
        """Usable blocks (excludes the trash block)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Take `n` blocks, or None (and take nothing) if fewer are free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        if self.ledger is not None:
            self.ledger.note_alloc(out)
        return out

    def free(self, blocks, *, cause: str | None = None) -> None:
        """Return `blocks` to the pool. `cause` books the deaths in the
        attached ledger (see obs.EVICTION_CAUSES); a None cause lands in
        the ledger's `unattributed` bucket, which CI pins at zero — so
        every call site must say WHY the blocks died."""
        blocks = list(blocks)
        seen: set[int] = set()
        for b in blocks:
            if not (0 < b < self.num_blocks):
                raise ValueError(f"freeing out-of-range block {b}")
            if b in self._free_set or b in seen:
                raise ValueError(f"double-free of block {b}")
            seen.add(b)
        for b in blocks:
            self._free.append(b)
            self._free_set.add(b)
        if self.ledger is not None:
            self.ledger.note_free(blocks, cause)


class HostSpillTier:
    """Bytes-budgeted host-RAM LRU store for demoted KV block contents
    (the fleet cache tier's middle rung, PR 19).

    Entries are keyed by `(ns, token_path)` where `token_path` is the
    FULL token prefix ending at the block — content is a pure function
    of the token prefix by the insert-time canonical-form invariant,
    so the key alone names the payload and a restore is token-identical
    by construction. Payloads are opaque to this module (the batcher
    stores host-numpy `(k, v)` copies); this class only does the
    budget/LRU bookkeeping, so it stays jax-free like the rest of the
    file. `put` returns the keys the budget pushed out (oldest first)
    so the caller can book them as content deaths
    (`CacheLedger.note_spill_drop`)."""

    def __init__(self, budget_bytes: int, block_bytes: int):
        if budget_bytes < 0:
            raise ValueError(f"budget_bytes must be >= 0, got "
                             f"{budget_bytes}")
        if block_bytes < 1:
            raise ValueError(f"block_bytes must be >= 1, got "
                             f"{block_bytes}")
        self.budget_bytes = int(budget_bytes)
        self.block_bytes = int(block_bytes)
        # (ns, token_path tuple) -> payload; insertion order == LRU
        # order (move_to_end on every touch)
        self._entries: OrderedDict[tuple, object] = OrderedDict()

    @property
    def capacity_blocks(self) -> int:
        return self.budget_bytes // self.block_bytes

    @property
    def spilled_blocks(self) -> int:
        return len(self._entries)

    @property
    def spilled_bytes(self) -> int:
        return len(self._entries) * self.block_bytes

    def _key(self, ns: str, path) -> tuple:
        return (ns, tuple(int(t) for t in path))

    def contains(self, ns: str, path) -> bool:
        """Presence probe WITHOUT an LRU touch — planning peeks, only
        an actual demote/restore moves the clock."""
        return self._key(ns, path) in self._entries

    def put(self, ns: str, path, payload) -> list[tuple]:
        """Park one block's content; returns the `(ns, token_path)`
        keys the byte budget evicted to make room (possibly including
        this very entry when the budget can't hold even one block)."""
        key = self._key(ns, path)
        self._entries[key] = payload
        self._entries.move_to_end(key)
        dropped: list[tuple] = []
        while len(self._entries) * self.block_bytes > self.budget_bytes:
            victim, _ = self._entries.popitem(last=False)
            dropped.append(victim)
        return dropped

    def pop(self, ns: str, path):
        """Take one block's content out (a restore owns it now), or
        None if the budget already dropped it."""
        return self._entries.pop(self._key(ns, path), None)

    def clear(self) -> int:
        n = len(self._entries)
        self._entries.clear()
        return n


class _Node:
    __slots__ = ("key", "block", "children", "refs", "last_use", "parent")

    def __init__(self, key, block, parent):
        self.key = key          # tuple of block_size token ids (None at root)
        self.block = block      # physical block id (None at root)
        self.children = {}      # key tuple -> _Node
        self.refs = 0           # active requests pointing at self.block
        self.last_use = 0       # logical clock, for LRU eviction
        self.parent = parent


class RadixPrefixCache:
    """Token-prefix index over full KV blocks, with ref-counted sharing.

    `match` walks full-block edges and additionally reports a *partial*
    match inside the next edge (for copy-on-write seeding). `insert`
    adopts caller-owned blocks into the tree; blocks whose token path
    already exists are left with the caller (duplicates — free them).
    `evict` pops refcount-0 leaves in LRU order back to the pool.
    """

    def __init__(self, pool: BlockPool, *, heat_half_life: int = 64,
                 heat_max_entries: int = 512):
        self.pool = pool
        self.block_size = pool.block_size
        self.root = _Node(None, None, None)
        # Namespaced roots (tenant prefix isolation): ns "" is the
        # shared default tree (`self.root`, kept as an attribute for
        # back-compat); any other ns gets its own root on first use, so
        # two namespaces can never match each other's entries — not
        # even the timing side channel of a shared-prefix hit.
        self._roots: dict[str, _Node] = {"": self.root}
        self._clock = 0
        self.cached_blocks = 0  # blocks currently owned by the tree
        # Decayed per-prefix heat: (ns, first-block key) -> [score,
        # last-bump clock]. A prefix is named by its FIRST full block —
        # the same token slice the router's rendezvous affinity key
        # hashes, so replica digests join against routing keys. Scores
        # halve every `heat_half_life` radix-clock ticks (accesses),
        # and the table is pruned to its hottest half past
        # `heat_max_entries`, so memory is bounded regardless of
        # prompt diversity.
        self.heat_half_life = max(1, int(heat_half_life))
        self.heat_max_entries = max(2, int(heat_max_entries))
        self._heat: dict[tuple[str, tuple], list] = {}
        # hashed-mode guard: digests export prefixes as 16-hex blake2b
        # names, never raw tokens — bounded label cardinality by
        # construction
        self.heat_guard = LabelGuard(hashed=True)
        # Optional host-RAM spill tier (PR 19): when attached (with a
        # device-block reader), evict() demotes victim contents to the
        # tier instead of discarding them. The reader is best-effort —
        # any failure degrades that eviction to a plain discard.
        self.spill: HostSpillTier | None = None
        self.spill_reader: Callable[[int], object] | None = None

    def attach_spill(self, tier: HostSpillTier,
                     reader: Callable[[int], object]) -> None:
        """Attach a `HostSpillTier` plus a `reader(block_id) ->
        payload | None` that snapshots one device block's contents to
        host memory (the batcher closes it over the engine's
        `export_blocks`). From then on eviction demotes instead of
        discarding, booked as cause `spill`; a None/raising reader
        falls back to the old `lru` discard, so spill can never make
        eviction less correct — only cheaper to undo."""
        self.spill = tier
        self.spill_reader = reader

    # -- internals ---------------------------------------------------------

    def _root_for(self, ns: str) -> _Node:
        root = self._roots.get(ns)
        if root is None:
            root = self._roots[ns] = _Node(None, None, None)
        return root

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _touch(self, node: _Node) -> None:
        t = self._tick()
        # roots (any namespace) are the only nodes with key None
        while node is not None and node.key is not None:
            node.last_use = t
            node = node.parent

    def _decayed(self, ent: list, t: int) -> float:
        return ent[0] * 0.5 ** ((t - ent[1]) / self.heat_half_life)

    def _heat_bump(self, ns: str, key: tuple) -> None:
        t = self._clock
        ent = self._heat.get((ns, key))
        if ent is None:
            if len(self._heat) >= self.heat_max_entries:
                self._heat_prune(t)
            self._heat[(ns, key)] = [1.0, t]
        else:
            ent[0] = self._decayed(ent, t) + 1.0
            ent[1] = t

    def _heat_prune(self, t: int) -> None:
        """Keep only the hottest half (by decayed score) — amortized
        O(n log n) once per max_entries/2 novel prefixes."""
        ranked = sorted(self._heat.items(),
                        key=lambda kv: self._decayed(kv[1], t),
                        reverse=True)
        self._heat = dict(ranked[: self.heat_max_entries // 2])

    # -- queries -----------------------------------------------------------

    def match(self, tokens, *,
              ns: str = "") -> tuple[list["_Node"], "_Node | None", int]:
        """Longest cached prefix of `tokens` within namespace `ns`.

        Returns `(nodes, partial_node, partial_len)`: `nodes` are the
        fully-matched block edges in order; `partial_node` (if any) is a
        child whose key shares `partial_len in [1, block_size)` leading
        tokens with the remainder. Does NOT take refs — callers decide
        which nodes they depend on and `ref` those.
        """
        bs = self.block_size
        nodes: list[_Node] = []
        node = self._root_for(ns)
        i = 0
        while i + bs <= len(tokens):
            child = node.children.get(tuple(tokens[i : i + bs]))
            if child is None:
                break
            nodes.append(child)
            node = child
            i += bs
        partial_node, partial_len = None, 0
        rest = tuple(tokens[i : i + bs])
        if rest:
            for key, child in node.children.items():
                n = 0
                for a, b in zip(rest, key):
                    if a != b:
                        break
                    n += 1
                if n > partial_len:
                    partial_node, partial_len = child, n
        if nodes:
            self._touch(nodes[-1])
            self._heat_bump(ns, nodes[0].key)
        if partial_node is not None:
            self._touch(partial_node)
        return nodes, partial_node, partial_len

    # -- ref management ----------------------------------------------------

    def ref(self, nodes) -> None:
        for n in nodes:
            n.refs += 1
        if nodes:
            self._touch(nodes[-1])

    def unref(self, nodes) -> None:
        for n in nodes:
            n.refs -= 1
            assert n.refs >= 0, "refcount underflow"

    # -- growth ------------------------------------------------------------

    def insert(self, tokens, blocks: dict[int, int], *,
               hold: bool = False, ns: str = ""):
        """Index `tokens` (length must be a multiple of block_size) into
        namespace `ns` of the tree. `blocks[i]` is the caller-owned
        physical block holding tokens `[i*bs, (i+1)*bs)`; only consulted
        for edges that don't exist yet. Returns `(adopted, held_nodes)`
        where `adopted` is the set of block indices the tree took
        ownership of, and `held_nodes` the nodes created with an initial
        ref for the caller (only when `hold=True` — the caller's block
        table points at those blocks, so they must not be evicted
        underneath it).
        """
        bs = self.block_size
        assert len(tokens) % bs == 0, len(tokens)
        adopted: set[int] = set()
        held: list[_Node] = []
        node = self._root_for(ns)
        for i in range(len(tokens) // bs):
            key = tuple(tokens[i * bs : (i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                phys = blocks.get(i)
                if phys is None:
                    break  # caller had nothing for this edge; stop here
                child = _Node(key, phys, node)
                node.children[key] = child
                adopted.add(i)
                self.cached_blocks += 1
                if i == 0:
                    # a prefix's first cached appearance is its first
                    # heat point (later hits bump via match())
                    self._heat_bump(ns, key)
                if hold:
                    child.refs = 1
                    held.append(child)
            node = child
        if node.key is not None:
            self._touch(node)
        return adopted, held

    # -- shrink ------------------------------------------------------------

    def _path_tokens(self, node: _Node) -> tuple:
        """Full token prefix ending at `node`'s block, reconstructed
        by walking parent edges to the namespace root — the spill
        tier's key (content is a pure function of this path by the
        canonical-form invariant)."""
        keys = []
        while node is not None and node.key is not None:
            keys.append(node.key)
            node = node.parent
        out: list[int] = []
        for key in reversed(keys):
            out.extend(key)
        return tuple(out)

    def _demote(self, ns: str, victim: _Node) -> bool:
        """Try to park `victim`'s block content in the spill tier.
        Returns True when the content survives on the host (the free
        books as `spill`), False for a plain discard (`lru`). Reader
        failures — including a concurrently-donated device state —
        degrade to discard: spill is an optimization, never a new
        failure mode."""
        if self.spill is None or self.spill_reader is None:
            return False
        try:
            payload = self.spill_reader(victim.block)
        except Exception:  # noqa: BLE001 — best-effort device read
            payload = None
        if payload is None:
            return False
        dropped = self.spill.put(ns, self._path_tokens(victim), payload)
        if dropped and self.pool.ledger is not None:
            self.pool.ledger.note_spill_drop(len(dropped))
        return True

    def evict(self, need: int) -> int:
        """Free refcount-0 LRU leaves back to the pool until `need`
        blocks have been released (or no candidates remain). Returns
        how many were actually freed. With a spill tier attached each
        victim's content is demoted to host RAM first (death cause
        `spill` instead of `lru`), so a later request for the same
        prefix restores it with a host-to-device copy instead of
        recomputing the prefill."""
        freed = 0
        while freed < need:
            victim = None
            victim_ns = ""
            # evict across namespaces
            stack = [(ns, root) for ns, root in self._roots.items()]
            while stack:
                ns, n = stack.pop()
                stack.extend((ns, c) for c in n.children.values())
                if n.key is None or n.children or n.refs > 0:
                    continue
                if victim is None or n.last_use < victim.last_use:
                    victim, victim_ns = n, ns
            if victim is None:
                break
            spilled = self._demote(victim_ns, victim)
            del victim.parent.children[victim.key]
            self.pool.free([victim.block],
                           cause="spill" if spilled else "lru")
            self.cached_blocks -= 1
            freed += 1
        return freed

    def clear(self, *, cause: str = "refdrop") -> None:
        """Drop the whole tree, returning every cached block to the pool.

        Must be called whenever the device-side pool array is discarded
        (e.g. after a failed dispatch poisons the state): the tree's
        blocks describe content that no longer exists. That is a
        reference drop (the content died with the device state), not an
        LRU decision — hence the default cause.
        """
        blocks = []
        for root in self._roots.values():
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                blocks.append(n.block)
                stack.extend(n.children.values())
            root.children.clear()
        if blocks:
            self.pool.free(blocks, cause=cause)
        self.cached_blocks = 0

    # -- heat export -------------------------------------------------------

    def heat_digest(self, k: int = 16) -> list[dict]:
        """Top-`k` hottest prefixes by decayed score, exported as
        16-hex hashed names (via the hashed LabelGuard) — safe to put
        on heartbeats and `/v1/models` without leaking prompt tokens,
        and joinable against the router's `prefix_hash` of the same
        first-block token slice."""
        t = self._clock
        ranked = sorted(
            ((self._decayed(ent, t), ns, key)
             for (ns, key), ent in self._heat.items()),
            key=lambda x: x[0], reverse=True)
        return [
            {"prefix": self.heat_guard.admit(canonical_prefix(key, ns)),
             "score": round(score, 4)}
            for score, ns, key in ranked[: max(0, int(k))]
            if score > 1e-9
        ]
