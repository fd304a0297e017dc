"""The KV manager: what a sequence holds in the pools, and who may hit it.

Three boxes, the arrows one way: the scheduler (``engine.py``) asks this
module; this module asks the pools' mechanics (``allocator.py``) and the
host tier (``offload.py``). Nothing here imports the scheduler, JAX or a
model: it takes the few numbers it needs of ``EngineConfig`` and
``ModelConfig`` and answers in numpy, so a unit test runs it with no
device (tests/test_kv_manager.py).

A sequence's ``Hold`` is its blocks of the full pool (a paged cache of
whole histories under one block table: every model's) and, where the
model has them, FURTHER PARTS: the window pool's blocks
(``ModelConfig.window_kv_pool``) and a row of the state's snapshot pool
(``state_layers``: a conv or linear-attention layer's state).

The prefix rule, once: a prompt hits up to THE LONGEST BLOCK BOUNDARY
EVERY PART OF THE HOLD CAN SERVE. The full pool matches the prompt's
chained block hashes; each further part then trims the hit (``_trims``).
What is claimed on the way is in the hold from the first moment, so ONE
``release`` is a failed reservation's rollback, a finish, a preemption
and an abort alike.

The refusal, once: the KV tiers, the wire, the mesh, the mirror, the
verify forward, adapters and the int8 planes carry one paged cache under
one table; a model whose holds have a further part refuses them by name
(``refuse_options``, ``refuse_transfer``). The hooks that move ONE
cache's blocks by index address ``KvManager.allocator`` directly.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Iterable, Optional

import numpy as np

from .allocator import Block, BlockAllocator, WindowPool, sequence_block_hashes

#: ``KvManager.stats`` keys of a model with a window pool (absent
#: otherwise), exported as engine_<key>_total: prompt tokens the full
#: pool matched and those of them the window pool could not back; booked
#: once a decode or mixed step over the live rows, the tokens of
#: window-layer KV a row holds against its context's length, and the
#: pages the window layers' kernels walk against those its context spans
WINDOW_COUNTERS = ("prefix_matched_tokens", "prefix_window_missed_tokens",
                   "kv_window_resident_tokens", "kv_window_context_tokens",
                   "attn_window_pages", "attn_window_context_pages")
#: the same for a model with state layers: matched tokens, those a hit
#: was cut short by for want of a snapshot, snapshots taken, and
#: snapshots that lost their row to a newer one (the second and the last
#: stay 0 for a state that is snapshotted a row a block)
SNAPSHOT_COUNTERS = ("prefix_matched_tokens", "prefix_unsnapshotted_tokens",
                     "state_snapshots", "state_snapshot_evictions")


def window_pool_blocks(model, max_batch: int, block_size: int,
                       mixed_budget: int, prefill_chunk: int,
                       asked: int = 0) -> int:
    """The window pool's size (0: the model has no window pool). Derived:
    what every decode slot holds at most while a mixed step's chunk
    advances it (window + chunk, and a block of slack) and one lone
    prefill's chunk on top (the FLOOR: under it a sequence could find no
    block for its window), a window a slot of cached tails (what a
    prefix hit needs of a context that nobody holds any more), plus page
    0. ``asked`` (``EngineConfig.window_blocks``) replaces the derived
    size and may not lie under the floor."""
    if not model.window_kv_pool:
        return 0
    blocks = lambda tokens: -(-tokens // block_size)  # noqa: E731
    floor = (max_batch * (blocks(model.kv_window + mixed_budget) + 1)
             + blocks(prefill_chunk) + 1)
    if asked and asked < floor:
        raise ValueError(
            f"window_blocks={asked} is under the {floor} blocks that "
            f"{max_batch} slots' windows and chunks can hold at once")
    return asked or floor + max_batch * blocks(model.kv_window)


class SnapshotPool:
    """Which KV block's state snapshot lies in which row of the state's
    snapshot pool (``llama.init_state``'s ``snap`` arrays): the host
    side of ONE mechanism for every kind of state.

    WHERE a snapshot can be taken is the kind of state's rule
    (``llama.StateTrack``). A state the programs can write at every
    block end (``at_block_ends``: a conv layer's window of rows) has a
    row a block, which it is small enough for: the pool is ``dense``,
    the row IS the block id and every committed block has its snapshot.
    A state that exists at a chunk's end only (a recurrent matrix) is a
    map with LRU reuse, whatever the pool's size: a row is taken
    (``take``) for the block at which a prefill chunk ends, the program
    writes the chunk's end state there, and a block whose row went to a
    newer snapshot has none any more. An entry carries the block's
    chained hash, so a block id the allocator recycled for other content
    does not answer for the old one. A prefix hit counts up to the last
    matched block for which ``row_of`` answers."""

    def __init__(self, rows: int, num_blocks: int, at_block_ends: bool = False):
        if at_block_ends and rows < num_blocks:
            raise ValueError(
                f"a state snapshotted at every block end needs a row a "
                f"block: {rows} rows for {num_blocks} blocks")
        self.rows = rows
        self.dense = at_block_ends
        self._by_block: OrderedDict = OrderedDict()  # idx -> [row, hash]
        self._free = list(range(rows - 1, -1, -1))
        self._pins: dict[int, int] = {}
        self.evictions = 0

    def row_of(self, block: Block) -> int:
        """The row that holds ``block``'s snapshot, or -1."""
        if self.dense:
            return block.idx
        e = self._by_block.get(block.idx)
        if e is None or e[1] is None or e[1] != block.seq_hash:
            return -1
        self._by_block.move_to_end(block.idx)
        return e[0]

    def take(self, block: Block) -> int:
        """A row for the snapshot about to be written for ``block``: the
        one it has, a free one, or the least recently used unpinned one
        (its block loses its snapshot); -1 if every row is pinned."""
        e = self._by_block.pop(block.idx, None)
        if e is not None:
            row = e[0]
        elif self._free:
            row = self._free.pop()
        else:
            victim = next((b for b, (r, _h) in self._by_block.items()
                           if r not in self._pins), None)
            if victim is None:
                return -1
            row = self._by_block.pop(victim)[0]
            self.evictions += 1
        self._by_block[block.idx] = [row, block.seq_hash]
        return row

    def bind(self, block: Block) -> None:
        """``block`` was committed: its pending snapshot (taken before
        the block had a hash) answers for this content from now on."""
        e = self._by_block.get(block.idx)
        if e is not None and e[1] is None:
            e[1] = block.seq_hash

    def pin(self, row: int) -> None:
        """An admission will restore from ``row``: it is not reused
        until ``unpin``."""
        self._pins[row] = self._pins.get(row, 0) + 1

    def unpin(self, row: int) -> None:
        n = self._pins.get(row, 0) - 1
        if n > 0:
            self._pins[row] = n
        else:
            self._pins.pop(row, None)


@dataclass
class Hold:
    """What ONE sequence holds in the pools. The manager writes it; the
    scheduler reads ``blocks`` (where a transfer hook gathers or lands
    pages by index) and ``committed`` (where such a hook reports it)."""

    #: the full pool's blocks by position, of which the first
    #: ``committed`` are full and hashed; the hash chain's end
    blocks: list = field(default_factory=list)
    committed: int = 0
    parent_hash: Optional[int] = None
    #: the window pool's blocks by position (None: released behind the
    #: window), the first entry that may hold one, the entries that go
    #: back cold (``cold_entries``), the full-pool blocks a hit was cut by
    wblocks: list = field(default_factory=list)
    wfloor: int = 0
    wcold: range = range(0)
    window_cut: int = 0
    #: the token counts at which a prefill chunk has to end and leave a
    #: snapshot (a sparse pool), and the pinned row the first chunk
    #: restores (-1: starts from zeros, or handed over)
    snap_points: tuple = ()
    restore_row: int = -1


class KvManager:
    """The pools' bookkeeping, built once an engine from its
    ``EngineConfig`` (read: ``model``, ``num_blocks``, ``block_size``,
    ``max_batch_size``, ``max_blocks_per_seq``, ``prefill_chunk``,
    ``mixed_step_budget``, ``window_blocks``). ``snapshot_rows``: the
    rows of the state's snapshot pool (``llama.state_snapshot_rows``;
    read only for a model with state layers)."""

    def __init__(self, cfg, snapshot_rows: int = 0, mirror=None):
        m = cfg.model
        #: what this model's sequences hold beyond one paged cache, as a
        #: refusal names it
        self.beyond = " and ".join(
            [f"{kind} layers ({m.state_layers} of {m.num_layers} here: a "
             "per-sequence state beside keys and values)"
             for kind, n in (("conv", m.conv_layers),
                             ("linear-attention", m.linear_layers)) if n]
            + ["window and full attention layers in two KV pools "
               f"({m.kv_pool_layers[1]} window layers of {m.num_layers} here)"
               ] * bool(m.window_kv_pool))
        self.refuse_options(cfg, mirror)
        self.block_size = bs = cfg.block_size
        self.max_blocks = cfg.max_blocks_per_seq
        #: the full pool: every model's, and the ONE cache the tier,
        #: fleet-prefix and disaggregation hooks address
        self.allocator = BlockAllocator(cfg.num_blocks, bs)
        self._pools = [("full", self.allocator)]  # by name, for what is said
        self._failed = self._pools[0]  # the pool that last had no block
        self.offload = None
        self.stats: dict = {}
        #: the prefix rule's further parts, in the order they trim a hit
        self._trims = []
        self.window: Optional[WindowPool] = None
        self.window_blocks = window_pool_blocks(
            m, cfg.max_batch_size, bs, cfg.mixed_step_budget,
            cfg.prefill_chunk, cfg.window_blocks)
        if self.window_blocks:
            self.window = WindowPool(self.window_blocks, bs, m.kv_window)
            self._pools.append(("window", self.window.allocator))
            # the tokens a prompt's first dispatch can write at most
            self._first_chunk = max(cfg.prefill_chunk, cfg.mixed_step_budget)
            self._released_seen = 0
            self._trims.append(self._trim_window)
            self.stats.update(dict.fromkeys(WINDOW_COUNTERS, 0))
        self.snapshots: Optional[SnapshotPool] = None
        if m.state_layers:
            self.snapshots = SnapshotPool(
                snapshot_rows, cfg.num_blocks, at_block_ends=m.conv_layers > 0)
            self._trims.append(self._trim_snapshots)
            self.stats.update(dict.fromkeys(SNAPSHOT_COUNTERS, 0))

    def attach_offload(self, offload) -> None:
        """The host tier (``OffloadManager``; built after the caches
        whose size this manager decides): evicted blocks park there, and
        a reservation probes it for the chain's continuation."""
        self.offload = offload
        self.allocator.on_evict = lambda h, b: offload.on_evict(h, b.idx)
        # a stale lower-tier copy aging out must not un-index a
        # device-resident block (offload.flush_dropped asks first)
        offload.device_has = self.allocator.has_hash

    # ---- the refusal ----

    def refuse_options(self, cfg, mirror) -> None:
        """What moves, shards, re-encodes or re-runs ONE paged cache
        under ONE table refuses, by name, a model whose sequences hold
        more than that; none of it is bypassed in silence."""
        if not self.beyond:
            return
        asked = [name for name, on in (
            ("spec_gamma (the verify forward)", cfg.spec_gamma > 0),
            ("ring_prefill_threshold (ring prefill)",
             cfg.ring_prefill_threshold > 0),
            ("mesh (tp / ep / pp / sp sharding)", cfg.mesh is not None),
            ("the multi-host mirror", mirror is not None),
            ("host_cache_blocks / disk_cache_blocks (the KV tiers)",
             cfg.host_cache_blocks > 0 or cfg.disk_cache_blocks > 0),
            ("adapters", bool(cfg.adapters)),
            ("kv_cache_dtype=int8 (the scale planes)",
             cfg.kv_cache_dtype == "int8"),
        ) if on]
        if asked:
            raise ValueError(
                f"{', '.join(asked)}: not supported for a model with "
                f"{self.beyond}. These carry ONE paged cache under ONE "
                "block table (and the verify forward cannot roll a state "
                "back); what this model's sequences hold beyond that rides "
                "the scheduler, the allocators and the prefix cache only")

    def refuse_transfer(self, what: str) -> None:
        """The disaggregation, fleet-prefix and resharding hooks move one
        cache's blocks between engines: no lane for a further part."""
        if self.beyond:
            raise ValueError(
                f"{what}: not supported for a model with {self.beyond} "
                "(the KV wire carries one cache under one block table and "
                "no per-sequence state)")

    # ---- reserve / release ----

    def blocks_for(self, n_tokens: int) -> int:
        """The full-pool blocks a sequence of ``n_tokens`` is admitted
        with: its tokens', and a block of decode headroom."""
        bs = self.block_size
        return min((n_tokens + bs) // bs + 1, self.max_blocks)

    def reserve(self, tokens, model_salt: Optional[int] = None, hashes=None,
                probe_host: bool = False):
        """The one allocation protocol shared by local prefill, remote
        prefill (worker side) and remote decode (decode side): match the
        prompt's full blocks by the prefix rule (the final token is
        always recomputed, so prefill yields fresh last-position logits),
        optionally probe the host tier for the chain's continuation (its
        h2d upload starts HERE, so it has usually landed by the time the
        prefill chunk needs the pages), then allocate fresh blocks for
        prompt + decode headroom. Returns (hold, history, upload or
        None), ``history`` the tokens that need no prefill; or None, with
        every claim given back. ``hashes``: the prompt's chain, where the
        caller computed it already. ``model_salt`` (``model_hash_salt``)
        roots the chain: the same prompt under two models hashes to
        disjoint chains on every plane that speaks these hashes."""
        bs, n, off = self.block_size, len(tokens), self.offload
        if hashes is None:
            hashes = sequence_block_hashes(tokens[: n - 1], bs, model_salt)
        hold = Hold(blocks=self.allocator.match_prefix((), hashes=hashes))
        matched = hit = len(hold.blocks)
        for trim in self._trims:
            hit = trim(hold, hashes, hit, n)
        kept = len(hold.blocks)
        if off is not None and kept:
            # blocks a router prefetch hint brought to the device tier,
            # now claimed: the hint saved this request a cold host
            # restore. The hashes ride along so peer-pulled blocks count
            # toward peer_pull_hidden_frac
            hinted = [b for b in hold.blocks if b.prefetched]
            for b in hinted:
                b.prefetched = False
            if hinted:
                off.note_prefetch_hits(
                    len(hinted), hashes=[b.seq_hash for b in hinted])
        # the chain's continuation past the device match; reserving takes
        # it out of the host pool, so it can't be LRU'd before the restore
        r_hashes, r_data = [], []
        if probe_host and off is not None:
            r_hashes, r_data = off.reserve_chain(
                [s for _l, s in hashes[kept:]])
        history = (hit + len(r_hashes)) * bs
        fresh = self.allocator.allocate(max(0, self.blocks_for(n) - kept))
        ok = fresh is not None
        if ok:
            hold.committed = kept
            # (no device match: the chain starts from its model's root)
            hold.parent_hash = hold.blocks[-1].seq_hash if kept else model_salt
            hold.blocks.extend(fresh)
            # the first chunk's blocks in the window pool, taken here so
            # that a pool that is full holds the prompt back like the
            # full pool does (later chunks give back what they take)
            ok = self.window is None or self._grow_window(
                hold, history, min(n, history + self._first_chunk))
        else:
            self._failed = self._pools[0]
        if not ok:
            self.release(hold)
            if r_hashes:
                off.unreserve(r_hashes, r_data)
            return None
        # what the hashes matched, counted where a prompt is admitted with
        # it; what a prefill skips of it is the caller's to say
        if self._trims:
            self.stats["prefix_matched_tokens"] += matched * bs
        if self.window is not None:
            self.stats["prefix_window_missed_tokens"] += hold.window_cut * bs
        if self.snapshots is not None:
            self.stats["prefix_unsnapshotted_tokens"] += (kept - hit) * bs
        upload = off.begin_upload(
            r_hashes, r_data, [b.idx for b in fresh[: len(r_hashes)]]
        ) if r_hashes else None
        return hold, history, upload

    def _trim_window(self, hold: Hold, hashes, hit: int, n_tokens: int) -> int:
        """The window pool's half of the prefix rule: the hit ends at the
        longest boundary p whose tail [p - window, p) is resident there
        (``WindowPool.match_tail`` claims it). The full pool's blocks
        beyond p go back: their tokens are computed again."""
        p, tail = self.window.match_tail(hashes, hit)
        self.allocator.free(hold.blocks[p:])
        del hold.blocks[p:]
        hold.window_cut = hit - p
        # (the claimed tail, behind Nones: nothing in front of it is held)
        hold.wblocks = tail
        hold.wfloor = len(tail) - sum(b is not None for b in tail)
        hold.wcold = self.window.cold_entries(len(tail), n_tokens)
        return p

    def _trim_snapshots(self, hold: Hold, hashes, hit: int,
                        n_tokens: int) -> int:
        """The state's half: the hit ends at the last matched block that
        holds a snapshot (pinned until ``restore_from`` or ``release``).
        The tokens behind it are computed again, into the matched blocks
        they already lie in and with the same values, and the chunk that
        reaches the match's end leaves the snapshot the next asker
        restores (a dense pool cuts nothing)."""
        pool, bs, n = self.snapshots, self.block_size, hit
        while n and (row := pool.row_of(hold.blocks[n - 1])) < 0:
            n -= 1
        if n:
            hold.restore_row = row
            pool.pin(row)
        if not pool.dense:
            points = {hit * bs} if n < hit else set()
            # the prompt's last full block: what a repeat or an extension
            # of it will match. Not under two blocks: the cut is one more
            # dispatch, more than what a repeat would skip costs (and a
            # 2-block prompt stays in the bucket a harness warms with it)
            if n_tokens >= 2 * bs:
                points.add(n_tokens // bs * bs)
            hold.snap_points = tuple(sorted(p for p in points if p > n * bs))
        return n

    def release(self, hold: Hold) -> None:
        """Everything ``hold`` holds goes back (committed blocks stay
        hittable in their pools' reuse lists) and the hold is empty: a
        finish, a preemption, an abort and a reservation's rollback."""
        self.allocator.free(hold.blocks)
        if self.window is not None:
            self.window.free(hold.wblocks)
        if hold.restore_row >= 0:  # never restored: its first chunk never ran
            self.snapshots.unpin(hold.restore_row)
        vars(hold).update(vars(Hold()))

    # ---- provisioning ----

    def short(self, hold: Hold, upto: int) -> bool:
        """Does ``hold`` lack a block, in any pool, for a dispatch that
        writes up to token ``upto`` (exclusive)?"""
        bs = self.block_size
        return upto > len(hold.blocks) * bs or (
            self.window is not None and upto > len(hold.wblocks) * bs)

    def at_limit(self, hold: Hold) -> bool:
        """Does ``hold`` have every block a block table can name?"""
        return len(hold.blocks) >= self.max_blocks

    def grow(self, hold: Hold, pos: int, upto: int) -> bool:
        """Provision ``hold`` for a dispatch whose first query stands at
        ``pos`` and that writes up to token ``upto`` (exclusive): the
        full pool's blocks, then the window pool's, where what lies
        behind ``pos``'s window goes back FIRST (it may be what the pool
        gives out next). False, ``why()`` naming the pool, when one has
        no block to give. A table row is stale after a True."""
        need = -(-upto // self.block_size) - len(hold.blocks)
        if need > 0:
            fresh = self.allocator.allocate(need)
            if fresh is None:
                self._failed = self._pools[0]
                return False
            hold.blocks.extend(fresh)
        return self.window is None or self._grow_window(hold, pos, upto)

    def _grow_window(self, hold: Hold, pos: int, upto: int) -> bool:
        wp = self.window
        hold.wfloor = wp.release_behind(
            hold.wblocks, hold.wfloor, pos, hold.committed, hold.wcold)
        if wp.grow(hold.wblocks, upto):
            return True
        self._failed = self._pools[1]
        return False

    def why(self) -> str:
        """The last failed ``grow`` or ``reserve``, by its pool."""
        name, alloc = self._failed
        return f"{name} pool exhausted ({alloc.state_counts()})"

    def commit(self, hold: Hold, tokens, written_len: int,
               chunk: bool = False) -> None:
        """Content-address the blocks that just became full AND fully
        written, in every pool. ``written_len``: the positions whose KV
        is in the device cache. After a decode window (and a remote
        prefill's first-token emit) the last sampled token is in
        ``tokens`` but its KV is written by the NEXT dispatch: callers
        there pass ``len(tokens) - 1``, so a block whose last row is
        pending is never exposed to a prefix match (a concurrent hit
        would attend garbage). ``chunk``: a prompt with chunks to go;
        only a hold with a window part commits then (only a committed
        block can go back from behind the window, so a long prompt holds
        a window and a chunk there, not itself)."""
        if chunk and self.window is None:
            return
        bs = self.block_size
        full = min(written_len // bs, len(hold.blocks))
        while hold.committed < full:
            i = hold.committed
            parent = hold.parent_hash
            hold.parent_hash = self.allocator.commit_full_block(
                hold.blocks[i], tokens[i * bs: (i + 1) * bs], parent)
            if i < len(hold.wblocks):  # the same tokens' window-layer KV
                self.window.commit(hold.wblocks[i], hold.blocks[i], parent)
            hold.committed += 1
            if self.snapshots is None:
                continue
            if self.snapshots.dense:
                # the program that filled the block left the state at
                # its last token under its id (llama.StateTrack)
                self.stats["state_snapshots"] += 1
            else:  # a snapshot taken for it answers for this content
                self.snapshots.bind(hold.blocks[i])

    # ---- tables ----

    def table_rows(self, hold: Hold) -> tuple:
        """``hold``'s block-table row in each pool (one or two:
        ``StepState.set_tables``'s arguments). Page 0, the trash block,
        pads a row and stands where the window pool's holds no block."""
        t = np.zeros(self.max_blocks, np.int32)
        idxs = [b.idx for b in hold.blocks[: self.max_blocks]]
        t[: len(idxs)] = idxs
        if self.window is None:
            return (t,)
        w = np.zeros(self.max_blocks, np.int32)
        lo, hi = hold.wfloor, min(len(hold.wblocks), self.max_blocks)
        w[lo:hi] = [b.idx for b in hold.wblocks[lo:hi]]
        return t, w

    def tables(self, hold: Hold):
        """A step program's block-table argument for one sequence: an
        array, or for a model with a window pool the pair."""
        rows = self.table_rows(hold)
        return rows[0] if len(rows) == 1 else rows

    def stack_tables(self, holds: Iterable[Hold], rows: int):
        """The same for a mixed step's segments: ``[rows, width]`` (the
        rows behind ``holds``' are dead segments': all page 0), or the
        pair of them."""
        out = []
        for pool in zip(*map(self.table_rows, holds)):
            out.append(np.zeros((rows, self.max_blocks), np.int32))
            out[-1][: len(pool)] = pool
        return out[0] if len(out) == 1 else tuple(out)

    # ---- the snapshot side ----

    def clip_take(self, hold: Hold, pos: int, take: int) -> int:
        """``take`` tokens of the prompt from ``pos``, cut so that the
        chunk ends where the hold wants a snapshot of its state
        (``Hold.snap_points``: a state that exists at a chunk's end
        only)."""
        for p in hold.snap_points:
            if pos < p < pos + take:
                return p - pos
        return take

    def snap_rows(self, ends, rows: int = 1) -> Optional[np.ndarray]:
        """``[rows]``: the snapshot row of each prefill segment of a
        dispatch, ``ends`` their (hold, tokens its chunk ends after): a
        row of the pool where the hold wants a snapshot there, else (a
        dead segment too) an index past the pool, which the program
        drops. None: a state snapshotted a row a block names no rows."""
        pool = self.snapshots
        if pool.dense:
            return None
        out = np.full(rows, pool.rows, np.int32)
        for i, (hold, end) in enumerate(ends):
            if end not in hold.snap_points:
                continue
            row = pool.take(hold.blocks[end // self.block_size - 1])
            if row >= 0:
                self.stats["state_snapshots"] += 1
                self.stats["state_snapshot_evictions"] = pool.evictions
                out[i] = row
        return out

    def restore_from(self, hold: Hold) -> int:
        """The snapshot row ``hold``'s first prefill chunk starts its
        state from (-1: zeros), handed over ONCE and unpinned: the
        program that reads it is enqueued before any that writes one."""
        row, hold.restore_row = hold.restore_row, -1
        if row >= 0:
            self.snapshots.unpin(row)
        return row

    # ---- what is measured ----

    def usage(self) -> tuple:
        """(the fuller pool's share in use: what admission is held to;
        blocks held by sequences and blocks in all, over the pools)."""
        pools = [a for _name, a in self._pools]
        return (max(a.usage() for a in pools),
                sum(a.used_count for a in pools),
                sum(a.num_blocks - 1 for a in pools))

    def gauges(self) -> dict:
        """``engine_kv_pool_blocks{pool,state}`` (a model with more than
        one pool only)."""
        return {
            f'engine_kv_pool_blocks{{pool="{pool}",state="{state}"}}': v
            for pool, a in (self._pools if len(self._pools) > 1 else ())
            for state, v in a.state_counts().items()}

    def note_work(self, n: int, rows: Iterable, segs=()) -> None:
        """The window pool's work counters of one decode or mixed
        dispatch of ``n`` steps (no device read; nothing without one):
        over the live ``rows``, as (hold, context length), the window
        layers' KV a row holds against its context's length, and the
        pages their kernels walk for it (from the window's floor up)
        against those its context spans, at the first step; ``segs``: a
        mixed step's prefill segments, as (history, real tokens)."""
        wp = self.window
        if wp is None:
            return
        st, bs = self.stats, self.block_size
        for hold, ctx in rows:
            pages = -(-ctx // bs)
            st["kv_window_resident_tokens"] += (
                len(hold.wblocks) - hold.wfloor) * bs * n
            st["kv_window_context_tokens"] += ctx * n
            st["attn_window_pages"] += (pages - wp.first_seen(ctx - 1)) * n
            st["attn_window_context_pages"] += pages * n
        for hist, real in segs:
            pages = -(-(hist + real) // bs)
            st["attn_window_pages"] += pages - wp.first_seen(hist)
            st["attn_window_context_pages"] += pages

    def step_attrs(self) -> dict:
        """``engine.step``'s ``window_released``: the blocks released
        behind their sequences' windows since the last step closed."""
        if self.window is None:
            return {}
        was, self._released_seen = self._released_seen, self.window.released
        return {"window_released": self._released_seen - was}

    def prefill_attrs(self, hold: Hold, cached_prefix: int) -> dict:
        """``engine.prefill``'s attributes from the hold: ``cut_by`` (the
        pool whose match ended the hit) and ``window_cut`` (the tokens
        the full pool matched beyond it); ``restored`` (the prompt tokens
        whose state came from a snapshot)."""
        out = {}
        if self.window is not None:
            out.update(cut_by="window" if hold.window_cut else "full",
                       window_cut=hold.window_cut * self.block_size)
        if self.snapshots is not None:
            out["restored"] = cached_prefix
        return out
