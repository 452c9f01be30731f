"""Radix shared-prefix KV cache: prefill each course context once.

Students in one course ask against the same assignment/material context,
yet every request used to prefill its full prompt from scratch — with
the megastep having taken the host out of the decode loop (PR 9),
prefill became the dominant per-request device cost under same-course
traffic. This module is the sharing machinery: a radix tree over
token-id sequences whose nodes own immutable, device-resident KV block
runs, so a prompt whose prefix was prefilled by an earlier request
splices those blocks into its slot and prefills
only the uncached suffix (the RadixAttention idea from SGLang, over
vLLM-style fixed-size KV blocks, mapped onto the paged engine's
contiguous right-padded slot layout).

Design facts, each load-bearing:

- **Block granularity.** A cache symbol is a block of `block_tokens`
  consecutive token ids, and a block is the unit of everything the tree
  DECIDES: matching, `plan_staged`'s cut, splitting, pins, the budget's
  count, eviction, where a snapshot stands. What a node HOLDS for a block
  is either a `KVBlock` of the block's own arrays ([L, 1, H, B, Dh] per
  plane, plus int8 scale planes when kv-quant) or a `RunBlock`: its place
  in a STORED RUN, one array a plane for `run_blocks` consecutive blocks
  ([L, 1, H, R*B, Dh]), which a publish cuts out of the slot with one
  launch and a hit splices with one launch and one array a plane. A
  publish stores the new blocks of an edge as runs, counted from the
  edge's own first new block, for as many whole runs as it adds; what is
  left over (and every short prompt) rests as blocks. Both are
  block-aligned, which keeps the device programs' shapes static: the
  engine's `_stage_block`/`_export_block`/`_export_run` programs compile
  once per cache width, never per prefix length.
- **Immutability.** Tree-owned arrays are never donated and never
  written: the splice (`dynamic_update_slice` into the slot's pages of
  the live cache) READS them, the publish slices fresh copies OUT
  of a flipped slot's pages. The donation-safety and pspec-flow
  lint rules sweep this module with the rest of `engine/`;
  `tests/test_lint_clean.py` pins that donating a shared block plane
  fails lint.
- **Right-padded absolute positions.** A slot's layout puts prompt
  token j at cache slot j (position id j), so a cached block's KV is
  valid for ANY request whose prompt starts with the same tokens — no
  per-request position remapping, which is what makes byte-identical
  reuse possible (`tests/test_prefix_cache.py` pins cache-hit == cold
  generation token for token, megastep/spec/kv-quant included).
- **Ref-count + LRU eviction.** Admission pins the matched node
  (`acquire`) until the request completes; eviction under the
  configurable block budget removes least-recently-used *leaf* nodes
  with zero pins only (interior nodes are protected by having
  children, pinned leaves by their refcount), so a block a live slot
  still references is never freed — the budget may transiently overrun
  instead (pinned-overrun is observable via `blocks_used`).

- **State snapshots.** A family whose cache carries a recurrent state
  (`ModelFamily.recurrent_state`: `KVCache.ssm`, `.conv`) cannot reuse a
  prefix by its keys and values alone: the state at the end of the prefix
  is needed too, and a state has no positions to slice. So a node also
  holds `StateSnapshot`s, each the state a prefill from position 0 leaves
  after a whole number of its edge's blocks; a hit is then only as long
  as the deepest snapshot on the matched path (`deepest_snapshot`), the
  engine splices the blocks up to it and restores it, and prefills the
  rest. Snapshots are few and large (one is every state-space layer's
  state of one sequence), so they stand where the engine asks
  (`engine/paged.py` `_snapshot_point`), count their bytes
  (`snapshot_bytes`), are bounded in number (`max_snapshots`, the least
  recently used dropped first) and leave with their nodes.

Concurrency: host-side only, single-threaded by contract — the paged
engine's host API is single-threaded and the serving queue drives it
from one runner coroutine, so there is no lock here by design.
"""

from __future__ import annotations

import dataclasses
import time
from typing import (
    Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union)

import jax

# Tokens per cache block: the tree's matching granularity and the static
# width of the engine's block splice/export programs. 16 matches the
# default device chunk; tests shrink it to exercise multi-block paths
# with tiny prompts.
BLOCK_TOKENS = 16


class KVBlock(NamedTuple):
    """One immutable device-resident KV block: `block_tokens` consecutive
    positions of a single sequence ([L, 1, H, B, Dh] per plane, each by
    its own H and Dh, `v` None for a family whose cache is one plane; int8
    scale planes [L, 1, H, B] ride along for a quantized cache). Only
    planes WITH a positions axis can be cut into blocks: a recurrent
    family's `ssm` and `conv` planes have none, so a block holds its
    attention layers' keys and values alone and the state at a block
    boundary is a `StateSnapshot` beside it. Shared structure: never
    donated, never written in place — the lint sweep and the reversion
    pin in tests/test_lint_clean.py enforce it."""

    k: jax.Array
    v: Optional[jax.Array]
    ks: Optional[jax.Array] = None
    vs: Optional[jax.Array] = None
    # A family that selects what it reads (models/minicpm_sala.py): the
    # pooled keys of the block's own positions, [L, 1, H, B / stride, Dh].
    pool: Optional[jax.Array] = None


class RunBlock(NamedTuple):
    """Block `index` of a STORED RUN: `run` is a `KVBlock` whose planes hold
    `run_blocks` consecutive blocks of one sequence in one array a plane
    ([L, 1, H, R*B, Dh]; the pooled plane R*B / stride entries), immutable
    and shared like any block. The R entries of a run all point at the one
    `run`, so a `_split` inside it leaves both nodes reading the same
    arrays, and evicting one of them frees nothing the other needs: the
    arrays go when the last entry that points at them goes. The tree still
    counts, splits and evicts by entries, a block each."""

    run: KVBlock
    index: int


def splice_pieces(blocks: Sequence) -> List[Tuple[int, int, Optional[KVBlock]]]:
    """A matched path's blocks (`Match.blocks()`, or a head of it) as the
    stretches a splice takes them in: (first block, blocks, stored run) for
    entries 0 .. n-1 of one stored run in their order, which one launch
    writes from the run's arrays with the count of tokens that are to be
    kept, and (first block, blocks, None) for a stretch up to the next
    run's first entry. On a path from the root a run is entered at its
    first block (a split keeps the head above the tail, and a leaf goes
    before its parent), so such a stretch is blocks that rest on their
    own."""

    def opens_run(b) -> bool:
        return isinstance(b, RunBlock) and b.index == 0

    out: List[Tuple[int, int, Optional[KVBlock]]] = []
    i = 0
    while i < len(blocks):
        b, n = blocks[i], 1
        if opens_run(b):
            while (i + n < len(blocks) and isinstance(blocks[i + n], RunBlock)
                   and blocks[i + n].run is b.run
                   and blocks[i + n].index == n):
                n += 1
        else:
            while i + n < len(blocks) and not opens_run(blocks[i + n]):
                n += 1
        out.append((i, n, b.run if opens_run(b) else None))
        i += n
    return out


class StateSnapshot(NamedTuple):
    """The recurrent state of ONE sequence after a prefix of whole blocks,
    immutable and device-resident like a `KVBlock`: `ssm` [Lm, 1, H, P, N]
    float32 and `conv` [Lm, 1, K-1, C] (models/mamba2.py), planes without
    a positions axis; either may be the whole of it. Never donated, never
    written in place."""

    ssm: Optional[jax.Array]   # None: a state that is its window alone
    conv: Optional[jax.Array]  # None: a state without a convolution

    @property
    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in (self.ssm, self.conv)
                   if x is not None)


@dataclasses.dataclass
class _Node:
    """One radix-tree node: an edge of consecutive blocks plus the KV
    that backs them. `edge[i]` is the tuple of token ids block i of
    this edge covers; `blocks[i]` its KV, a `KVBlock` of its own arrays or
    a `RunBlock`, its place in a stored run. Children key on their edge's
    first block tuple."""

    edge: List[Tuple[int, ...]]
    blocks: List[Union[KVBlock, RunBlock]]
    parent: Optional["_Node"]
    children: Dict[Tuple[int, ...], "_Node"] = dataclasses.field(
        default_factory=dict
    )
    refs: int = 0
    last_used: int = 0
    # blocks of this edge a snapshot stands AFTER (1 .. len(edge)) ->
    # [the snapshot, the clock when a lookup last chose it].
    snapshots: Dict[int, list] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Match:
    """A longest-prefix lookup result: the matched path (deepest node
    last) with how many of each node's blocks matched, and the matched
    token count. `nodes`/`used` are parallel; only the deepest node may
    be partially used (matching stops at the first divergence)."""

    nodes: Tuple[_Node, ...]
    used: Tuple[int, ...]
    tokens: int

    def blocks(self) -> List[Union[KVBlock, RunBlock]]:
        out: List[Union[KVBlock, RunBlock]] = []
        for node, n in zip(self.nodes, self.used):
            out.extend(node.blocks[:n])
        return out


def plan_staged(hit_tokens: int, true_len: int, block_tokens: int) -> int:
    """Fit a cache hit into staged admission: returns the prefix
    length to splice (a multiple of `block_tokens`; 0 = cold staging).

    The uncached suffix is chunked through the megastep scan at any
    length, so the only constraints are block alignment and
    the >= 1 recomputed token rule (the last prompt position's logits
    seed the first sampled token; the cache does not store them). The
    spliced prefix simply moves the staged cursor forward: fewer prefill
    chunks, identical flip contract.
    """
    p = min(hit_tokens, true_len - 1)
    return p - p % block_tokens


class PrefixCache:
    """Host-side radix tree over block-granular token prefixes.

    The engine owns the device programs — and the hit/prompt-token
    accounting (it counts the USED prefix after bucket fitting, which
    the raw radix match overstates); this class owns structure and
    policy: longest-prefix lookup, insert-with-split, ref-count pins,
    and LRU leaf eviction under `max_blocks`. `blocks_used` is the live
    level the budget is enforced on; `evicted_blocks` the cumulative
    eviction count.
    """

    def __init__(self, block_tokens: int = BLOCK_TOKENS,
                 max_blocks: int = 512, max_snapshots: int = 0,
                 run_blocks: int = 0):
        if block_tokens < 1 or max_blocks < 1:
            raise ValueError("prefix cache needs block_tokens/max_blocks >= 1")
        self.block_tokens = block_tokens
        self.max_blocks = max_blocks
        # Blocks in a stored run (module docstring, "Block granularity");
        # 0: every block rests on its own.
        self.run_blocks = run_blocks
        # State snapshots (module docstring): how many the tree may hold,
        # how many it holds and their bytes.
        self.max_snapshots = max_snapshots
        self.snapshots = 0
        self.snapshot_bytes = 0
        self._root = _Node(edge=[], blocks=[], parent=None)
        self._clock = 0
        self.blocks_used = 0
        self.evicted_blocks = 0   # cumulative, pop'd by the engine stats
        # Multi-turn session pins: session_id -> (deepest pinned node,
        # monotonic expiry). A session pin is SOFT — it protects a
        # transcript path from LRU eviction until its TTL lapses or the
        # session releases it, but under budget pressure with nothing
        # unpinned left it is force-released in soonest-expiry order
        # (the eviction-under-live-session-pin policy). Request refcount
        # pins (`refs`, live slots) remain hard: never evicted.
        self._session_pins: Dict[str, Tuple[_Node, float]] = {}

    # ------------------------------------------------------------- lookup

    def _block_keys(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        b = self.block_tokens
        return [
            tuple(tokens[i: i + b])
            for i in range(0, len(tokens) - b + 1, b)
        ]

    def _walk(
        self, keys: Sequence[Tuple[int, ...]]
    ) -> Tuple[List[_Node], List[int], int]:
        """Longest shared prefix walk: (path nodes, blocks used per node,
        total blocks matched)."""
        nodes: List[_Node] = []
        used: List[int] = []
        cur = self._root
        i = 0
        while i < len(keys):
            child = cur.children.get(keys[i])
            if child is None:
                break
            j = 0
            while (j < len(child.edge) and i + j < len(keys)
                   and child.edge[j] == keys[i + j]):
                j += 1
            nodes.append(child)
            used.append(j)
            i += j
            if j < len(child.edge):
                break
            cur = child
        return nodes, used, i

    def lookup(self, tokens: Sequence[int]) -> Match:
        """Longest cached prefix of `tokens`, at block granularity,
        usable-capped at `len(tokens) - 1` (the last prompt position is
        always recomputed — its logits seed the first sampled token).
        Touches the matched path for LRU."""
        usable = max(0, (len(tokens) - 1) // self.block_tokens)
        keys = self._block_keys(tokens)[:usable]
        nodes, used, matched = self._walk(keys)
        self._clock += 1
        for node in nodes:
            node.last_used = self._clock
        return Match(nodes=tuple(nodes), used=tuple(used),
                     tokens=matched * self.block_tokens)

    # ----------------------------------------------------------- pinning

    def acquire(self, match: Match) -> None:
        """Pin the matched path for a live slot: the deepest node's
        refcount protects it from eviction, its ancestors are protected
        structurally (they have children). Balanced by `release` when
        the request completes (or the engine resets)."""
        if match.nodes:
            match.nodes[-1].refs += 1

    def release(self, match: Match) -> None:
        if match.nodes:
            match.nodes[-1].refs = max(0, match.nodes[-1].refs - 1)

    # ----------------------------------------------------- session pins

    def pin_session(self, session_id: str, tokens: Sequence[int],
                    ttl_s: float, now: Optional[float] = None) -> int:
        """Pin the cached path covering `tokens` for a tutoring session:
        turn N's published transcript stays resident so turn N+1 splices
        it as a shared prefix. Re-pinning the same session moves its pin
        to the new (longer) transcript path and refreshes the TTL.
        Returns the number of blocks the pinned path covers (0 = nothing
        cached to pin)."""
        now = time.monotonic() if now is None else now
        keys = self._block_keys(tokens)
        nodes, _used, matched = self._walk(keys)
        if not nodes or matched == 0:
            self._session_pins.pop(session_id, None)
            return 0
        self._session_pins[session_id] = (nodes[-1], now + ttl_s)
        self._clock += 1
        for node in nodes:
            node.last_used = self._clock
        return matched

    def release_session(self, session_id: str) -> bool:
        """Explicit release (session closed): the path becomes ordinary
        LRU-evictable content immediately."""
        return self._session_pins.pop(session_id, None) is not None

    def expire_sessions(self, now: Optional[float] = None) -> int:
        """Release pins whose TTL lapsed. Returns sessions released."""
        now = time.monotonic() if now is None else now
        dead = [sid for sid, (_, exp) in self._session_pins.items()
                if exp <= now]
        for sid in dead:
            del self._session_pins[sid]
        return len(dead)

    def _session_nodes(self) -> Dict[int, float]:
        """id(node) -> soonest expiry among the sessions pinning it."""
        out: Dict[int, float] = {}
        for node, exp in self._session_pins.values():
            key = id(node)
            out[key] = min(out.get(key, exp), exp)
        return out

    @property
    def session_count(self) -> int:
        return len(self._session_pins)

    def session_pinned_blocks(self) -> int:
        """Blocks held resident by session pins: the union of root->pin
        paths (the `session_pinned_blocks` gauge)."""
        seen: Dict[int, int] = {}
        for node, _exp in self._session_pins.values():
            cur: Optional[_Node] = node
            while cur is not None and cur.parent is not None:
                if id(cur) in seen:
                    break
                seen[id(cur)] = len(cur.blocks)
                cur = cur.parent
        return sum(seen.values())

    # ------------------------------------------------------------ insert

    def _split(self, node: _Node, j: int) -> _Node:
        """Split `node` after its first `j` blocks; returns the new
        upper node. The tail keeps the original node object so existing
        pins (refcounts) stay attached to the blocks they protect —
        ancestors are protected by having children."""
        assert node.parent is not None and 0 < j < len(node.edge)
        # A `j` inside a stored run leaves both nodes its entries: the one
        # array a plane is shared, nothing is cut or copied.
        top = _Node(edge=node.edge[:j], blocks=node.blocks[:j],
                    parent=node.parent, last_used=node.last_used,
                    snapshots={n: e for n, e in node.snapshots.items()
                               if n <= j})
        node.parent.children[top.edge[0]] = top
        node.edge = node.edge[j:]
        node.blocks = node.blocks[j:]
        node.snapshots = {n - j: e for n, e in node.snapshots.items()
                          if n > j}
        top.children[node.edge[0]] = node
        node.parent = top
        return top

    def insert(
        self,
        tokens: Sequence[int],
        make_block: Callable[[int], KVBlock],
        make_run: Optional[Callable[[int], KVBlock]] = None,
    ) -> int:
        """Publish `tokens`' uncached full blocks into the tree.
        `make_block(i)` materializes block i's KV (the engine slices it
        out of the completed prefill's cache — called only for blocks
        the tree does not already hold); `make_run(i)`, where the caller
        has one, the `run_blocks` blocks from block i as ONE stored run,
        and then the new blocks rest as whole runs counted from the first
        new block, and what is left over as blocks. Returns blocks added
        (a stored run counts `run_blocks`). Does NOT
        evict; the engine calls `evict_to_budget` after (so a publish
        can never evict blocks its own admission still references)."""
        keys = self._block_keys(tokens)
        nodes, used, matched = self._walk(keys)
        if matched >= len(keys):
            return 0
        cur = self._root if not nodes else nodes[-1]
        if nodes and used[-1] < len(nodes[-1].edge):
            # Divergence inside an edge: split so the shared head is a
            # real node the new tail can branch from.
            cur = self._split(nodes[-1], used[-1])
        fresh: List[Union[KVBlock, RunBlock]] = []
        at, r = matched, self.run_blocks
        while make_run is not None and 0 < r <= len(keys) - at:
            run = make_run(at)
            fresh.extend(RunBlock(run, j) for j in range(r))
            at += r
        fresh.extend(make_block(i) for i in range(at, len(keys)))
        self._clock += 1
        node = _Node(edge=list(keys[matched:]), blocks=fresh, parent=cur,
                     last_used=self._clock)
        cur.children[node.edge[0]] = node
        self.blocks_used += len(fresh)
        return len(fresh)

    # --------------------------------------------------- state snapshots

    def deepest_snapshot(
        self, match: Match, limit: int
    ) -> Tuple[int, Optional[StateSnapshot]]:
        """(tokens, snapshot) of the deepest state snapshot on `match`'s
        path that stands at or before `limit` tokens; (0, None) where
        there is none. Touches it for the snapshots' LRU."""
        best, at, entry = 0, 0, None
        for node, used in zip(match.nodes, match.used):
            for n, e in node.snapshots.items():
                tokens = (at + n) * self.block_tokens
                if n <= used and best < tokens <= limit:
                    best, entry = tokens, e
            at += used
        if entry is None:
            return 0, None
        entry[1] = self._clock
        return best, entry[0]

    def _snapshot_home(self, tokens: Sequence[int], position: int):
        """(node, blocks into its edge) of the block boundary `position`
        tokens into `tokens`, or None where the tree does not hold the
        path that far."""
        want = position // self.block_tokens
        nodes, used, matched = self._walk(self._block_keys(tokens[:position]))
        if matched < want or not nodes:
            return None
        return nodes[-1], used[-1]

    def has_snapshot(self, tokens: Sequence[int], position: int) -> bool:
        home = self._snapshot_home(tokens, position)
        return home is not None and home[1] in home[0].snapshots

    def attach_snapshot(self, tokens: Sequence[int], position: int,
                        snap: StateSnapshot) -> bool:
        """Hold `snap`, the state after the first `position` tokens of
        `tokens` (a whole number of blocks the tree already holds), with
        the node that holds the block before the boundary. Then drop the
        least recently used snapshots down to `max_snapshots`. False where
        the tree lacks the path, has one there already, or holds none."""
        home = self._snapshot_home(tokens, position)
        if home is None or home[1] in home[0].snapshots \
                or self.max_snapshots < 1:
            return False
        self._clock += 1
        home[0].snapshots[home[1]] = [snap, self._clock]
        self.snapshots += 1
        self.snapshot_bytes += snap.nbytes
        while self.snapshots > self.max_snapshots:
            node, n = min(
                ((node, n) for node in self._iter_nodes()
                 for n in node.snapshots),
                key=lambda at: at[0].snapshots[at[1]][1])
            self._forget(node.snapshots.pop(n)[0])
        return True

    def _forget(self, snap: StateSnapshot) -> None:
        self.snapshots -= 1
        self.snapshot_bytes -= snap.nbytes

    # ---------------------------------------------------------- eviction

    def _leaves(self) -> List[_Node]:
        out: List[_Node] = []
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    def evict_to_budget(self, now: Optional[float] = None) -> int:
        """Evict least-recently-used unpinned leaf nodes until
        `blocks_used <= max_blocks` or nothing evictable remains.

        Session-pin policy (ordered, each tier exhausted before the
        next):

        1. TTL-expired session pins are released first — an expired
           session's transcript is ordinary LRU-evictable content.
        2. Leaves with zero refs and no live session pin evict in LRU
           order (the pre-session behavior).
        3. Still over budget: live session pins are force-released in
           soonest-expiry order (the session closest to lapsing loses
           its residency guarantee), freeing their leaves for tier 2.
        4. Leaves pinned by a live REQUEST (refs > 0) are never evicted:
           the budget transiently overruns instead — a slot is actively
           reading those blocks.

        Returns blocks freed."""
        now = time.monotonic() if now is None else now
        self.expire_sessions(now)
        freed = 0
        while self.blocks_used > self.max_blocks:
            protected = self._session_nodes()
            victims = [n for n in self._leaves()
                       if n.refs == 0 and id(n) not in protected]
            if not victims:
                # Everything evictable is session-pinned: force-release
                # the pin nearest its TTL and retry; if only request
                # pins remain, overrun.
                if not self._session_pins:
                    break
                sid = min(self._session_pins,
                          key=lambda s: self._session_pins[s][1])
                del self._session_pins[sid]
                continue
            victim = min(victims, key=lambda n: n.last_used)
            assert victim.parent is not None
            del victim.parent.children[victim.edge[0]]
            self.blocks_used -= len(victim.blocks)
            for snap, _ in victim.snapshots.values():
                self._forget(snap)
            freed += len(victim.blocks)
        self.evicted_blocks += freed
        return freed

    # ------------------------------------------------------------- admin

    def clear(self) -> None:
        """Drop every cached block (warmup hygiene: ghost prompts must
        not seed the live tree). Pins are owned by the engine, which
        clears its own pin table alongside; session pins die with the
        tree they pointed into."""
        self._root = _Node(edge=[], blocks=[], parent=None)
        self.blocks_used = 0
        self.snapshots = self.snapshot_bytes = 0
        self._session_pins = {}

    @property
    def node_count(self) -> int:
        return sum(1 for _ in self._iter_nodes()) - 1  # minus root

    def _iter_nodes(self):
        stack = [self._root]
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())
