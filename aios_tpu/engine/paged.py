"""Host-side page-table management for the paged KV cache.

The device holds a fixed page pool and reads it through per-slot page
tables; THIS module owns the mapping. The pool has ONE stored layout,
[L, N, P, KH*D] per k/v (layers, physical pages, rows of a page, and a
row's kv heads side by side: head h is [h*D, (h+1)*D) of the last axis, for
any head size). A latent-attention (MLA) model's pool is the same pair of
arrays holding LATENT rows, not keys and values: in k's place the compressed
latents [L, N, P, kv_lora_rank] (512), in v's the rotary key parts
[L, N, P, 128] with a row's qk_rope_head_dim (64) values in lanes [0, 64) and
zeros above — two arrays and not one padded 576 -> 640 row, because a page is
then two whole-tile DMAs (a 64-lane row is a lane-padded tile in HBM either
way and Mosaic refuses a page DMA narrower than a tile) and the latent part
feeds the value product without a lane slice. 640 values a row are stored
for the published 576 (ModelConfig.kv_row_dims); allocator, tables, prefix
index and page accounting are the same, a page being P rows of either. The paged decode kernel takes that array whole with the
layer's index and DMAs the pages it needs from where they lie
(ops/paged_attention.py); every other reader and writer indexes it by
(layer, page, row) and reshapes only what is small — new rows on the way
in (ops.merge_heads; a prompt's or a chunk's rows go in by whole pages,
ops.write_rows), gathered pages on the way out (ops.gather_pages: a chunk's
attention one key tile at a time up to the chunk's last row, the verify
step and the CPU reference a slot's table). Nothing slices, reshapes or
copies a layer of it. An
int8 pool keeps its per-(row, kv head) scales beside it as [L, N, P, KH].

A stack that mixes WINDOW and FULL attention layers (ModelConfig.layer_types)
has pages by kind, in ONE array pair with two page ranges a period:
[L / period, N_period, P, KH*D], a period's pages being the full kind's range
for each of its full layers and then the window kind's range for each of its
window layers (PoolLayout.bases; each range's first page is that range's
sacrificial page). A slot has a table a kind, side by side in one
[S, 2 * MAX_BLOCKS] int32 array (full, then window), and each kind its free
list, refcounts and residency rule (KindPageAllocator): the full kind holds a
slot's every row, the window kind the rows its window can still reach, the
pages below it released as the slot advances and reused by any slot. A window
layer's trimmed table entries keep their stale page ids (trim_below_window's
rule): the decode kernel and a chunk's gather start at the window's first
block, which no trim has passed, and the two readers that gather a slot's
whole table (the CPU reference, the verify step) mask every row below the
window, so a stale id is at most a masked read of a page another slot now
owns. (Rewriting a dead entry to the sacrificial page instead would race a
dispatch still in flight: on the CPU backend the device's table IS the host
array.) A layer reads page table[kind] + base of
period l // period, so nothing a graph makes is as large as a layer of the pool
and no layer holds pages it cannot read. PREFIX SHARING there: the index
holds the full kind's pages as for any model; beside it WindowPrefixPages
keeps, for each registered block, the window-kind page the registering slot
still held (its last window and what straddles it), and a hit at m rows is
served only where every window-kind block that holds a row of (m - window, m)
is held too: else the longest shorter hit that is, else none
(``prefix_hits_refused_window`` counts the hits cut short or refused).

A THIRD kind of per-slot memory holds no rows at all: the STATE KIND of a
stack whose layers are mostly linear-attention (``kda``) layers
(ModelConfig.state_kind; engine/kda.py) or in which state-space (``mamba2``)
layers stand beside grouped-query ones (engine/mamba2.py: there the pool is
the GROUPED-QUERY pool, and only the stack's ``full`` layers have a layer of
it). Such a layer keeps, a slot, one float32 recurrent state ([heads, key,
value]; for mamba2 [heads, channels, state values]: ``ModelConfig.state_shapes``)
and the last taps - 1 rows of its
convolution's input, which do not grow with the context and are overwritten
by every token. They live in ONE array pair a model, indexed by SLOT and not
through a table: states [L_kda, S + 1, heads, key, value] float32 and tails
[L_kda, taps - 1, S', width] bfloat16 (``SlotStates``; slot S is the
scratch slot a decode step hands its dead slots, as page 0 is the pools'; the
tails' slots lie beside the width, S' = S + 1 rounded up to whole bfloat16
tiles of 16 rows, because the TPU compiler relaid a [.., S + 1, taps - 1,
width] array, and one of 49 slots, out into whole tiles and back in every
decode program: 58 MB copied twice, tests/test_mosaic_aot.py -k state_kind).
There are no pages to take or hand back: ``ensure`` and a slot's row budget
count the latent kind's rows alone (only the stack's ``mla`` layers have a
layer of the pool), a slot's state is RESET by its first chunk (a chunk that
starts at row 0 reads zeros, whatever the last tenant left: nothing is zeroed
at release), and ``free_slot`` has nothing to return. PREFIX SHARING there: a
hit is REFUSED (``prefix_hits_refused_state``, with the rows it would have
served in ``prefix_rows_refused_state``) and nothing is registered: the index
would hold the latent rows of a prefix and not the state at its end, and rows
served without the state are wrong, not slow. (A snapshot of the state at a
block boundary, kept beside the index, is what would serve such a hit; there
is none yet.)

Allocation is a free-list pop, release a push — O(1), no compaction, no
device traffic beyond the [S, MAX_BLOCKS] int32 table that rides along with
each dispatch (a few hundred bytes). The scheduler's admission/retire cycle calls
`ensure`/`free_slot`; a pool that can't back a grow request raises
`PoolExhausted` so the batcher can retire a victim request instead of
corrupting anyone's cache.

Page 0 is reserved as the *sacrificial page*: never allocated, mapped by
every unbacked table entry, and the write target for inactive slots — the
paged twin of the dense engine's sacrificial last cache row.

Reference equivalence: llama.cpp's per-sequence KV cells behind
llama-server (SURVEY.md section 2.3); redesigned as vLLM/JetStream-style
paging because HBM reservation, not compute, is what caps co-resident
slots x context on a TPU chip (SURVEY.md section 7.2, hard part no. 1).
"""

from __future__ import annotations

import logging
import struct
import zlib
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .. import faults
from ..analysis.locks import make_lock
from .config import LAYER_KINDS as KINDS  # the two tables' order in a slot's row

log = logging.getLogger("aios.paged")

SACRIFICIAL_PAGE = 0

# How the serving router scores prefix rows that are only host-resident:
# a restorable prefix saves the prefill compute but still pays alloc +
# device_put + scatter, so it is worth less than true HBM residency —
# routing prefers the replica with the pages already on chip and falls
# back to the one that can at least restore them.
HOST_OVERLAP_DISCOUNT = 0.5


class PoolExhausted(RuntimeError):
    """No free pages left to back a prefill/decode grow request.
    ``replica`` identifies the starved replica of a dp-partitioned pool
    (0 for the unreplicated pool) so the batcher can evict a request that
    actually frees pages there."""

    def __init__(self, needed: int, free: int, replica: int = 0,
                 kind: str = ""):
        super().__init__(
            f"KV page pool exhausted: need {needed} page(s), {free} free "
            f"(replica {replica})" + (f" of the {kind} kind" if kind else "")
        )
        self.needed = needed
        self.free = free
        self.replica = replica
        # which kind's pages ran out, in a pool by kind ("" otherwise): the
        # batcher's victim is the slot that holds most pages of THAT kind
        self.kind = kind


class PageAllocator:
    """Free-list allocator over ``num_pages`` physical pages of ``page_size``
    rows, mapping ``num_slots`` slots x ``max_blocks`` logical blocks.

    ``replicas`` partitions the pool for a dp-replicated serving plan:
    the physical page axis shards over dp, so each replica owns a
    contiguous range of ``num_pages / replicas`` pages and table entries
    hold REPLICA-LOCAL ids (each device reads only its own slots' tables
    under shard_map, so local ids need no translation on device). Every
    replica's local page 0 is sacrificial. Slots map to replicas in
    contiguous blocks — the same split GSPMD applies to the slot axis."""

    def __init__(self, num_pages: int, page_size: int, num_slots: int,
                 max_blocks: int, replicas: int = 1, kind: str = "") -> None:
        self.kind = kind  # named by PoolExhausted in a pool by kind
        if replicas < 1 or num_pages % replicas:
            raise ValueError(
                f"num_pages {num_pages} must divide into {replicas} replicas"
            )
        if num_slots % replicas:
            raise ValueError(
                f"num_slots {num_slots} must divide into {replicas} replicas"
            )
        if num_pages // replicas < 2:
            raise ValueError("need at least 2 pages/replica (one sacrificial)")
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_slots = num_slots
        self.max_blocks = max_blocks
        self.replicas = replicas
        self.local_pages = num_pages // replicas
        # local page 0 of every replica is sacrificial — never on a free list
        self._free: List[List[int]] = [
            list(range(self.local_pages - 1, 0, -1)) for _ in range(replicas)
        ]
        # host copy of the device tables; unbacked entries map page 0
        self.tables = np.full((num_slots, max_blocks), SACRIFICIAL_PAGE,
                              dtype=np.int32)
        self._blocks_used = np.zeros(num_slots, dtype=np.int64)
        # leading blocks already released by sliding-window trimming; their
        # table entries are stale-but-unread until the slot frees
        self._trimmed = np.zeros(num_slots, dtype=np.int64)
        # window+sink KV compression (prune_range): blocks
        # [_pruned_lo, _pruned_hi) of a slot were released mid-sequence —
        # their table entries map the sacrificial page and free_slot must
        # not decref them again. _pruned_lo is the sink boundary (fixed
        # once pruning starts), _pruned_hi only moves forward.
        self._pruned_lo = np.zeros(num_slots, dtype=np.int64)
        self._pruned_hi = np.zeros(num_slots, dtype=np.int64)
        # pages mapped by more than one owner (prefix sharing) carry a
        # refcount; rc 0 means free
        self._rc = np.zeros((replicas, self.local_pages), dtype=np.int64)
        # called with the shortfall when the free list runs dry; returns
        # how many pages it reclaimed (PrefixIndex.reclaim plugs in here)
        self.reclaimer: Optional[Callable[[int], int]] = None
        # pages ``ensure`` ever handed out, and pages trim_below_window
        # released: what kv.window_trim_share_pct divides (pool.stats())
        self.pages_allocated = 0
        self.pages_trimmed = 0

    def replica_of(self, slot: int) -> int:
        return slot * self.replicas // self.num_slots

    @property
    def free_pages(self) -> int:
        return sum(len(f) for f in self._free)

    def free_pages_for(self, slot: int) -> int:
        """Free pages in the replica that backs ``slot`` — the number
        ``ensure`` can actually draw from (``free_pages`` sums across
        replicas and overstates capacity when replicas > 1)."""
        return len(self._free[self.replica_of(slot)])

    def capacity_blocks(self) -> int:
        """Most blocks ONE slot can ever hold: its replica's page count
        minus the sacrificial page (== num_pages - 1 when unreplicated)."""
        return self.local_pages - 1

    def pages_in_use(self) -> int:
        return (self.num_pages - self.replicas) - self.free_pages

    def blocks_for(self, rows: int) -> int:
        return -(-rows // self.page_size)  # ceil

    def can_hold(self, rows: int, window_rows: int) -> bool:
        """Whether a slot of ``rows`` rows, ``window_rows`` of them within
        reach of its window at once (all of them without one), can ever be
        backed: one kind of page holds what the window reaches."""
        return self.blocks_for(window_rows) <= self.capacity_blocks()

    def _take(self, grow: int, replica: int = 0) -> None:
        act = faults.point("allocator.pressure")
        if act is not None:
            # chaos: synthetic pool pressure — rides the real
            # PoolExhausted recovery (victim eviction at decode grow /
            # prefill, restore fallback at alloc_pages)
            raise PoolExhausted(
                grow, len(self._free[replica]), replica, self.kind
            )
        free = self._free[replica]
        if grow > len(free) and self.reclaimer is not None:
            self.reclaimer(grow - len(free))
        if grow > len(free):
            raise PoolExhausted(grow, len(free), replica, self.kind)

    def ensure(self, slot: int, rows: int) -> bool:
        """Back slot ``slot`` for ``rows`` logical rows; allocates any
        missing pages (rc 1). Returns True iff the table changed. Raises
        PoolExhausted (leaving existing pages intact) if the free list —
        after asking the reclaimer to drop cold prefix pages — can't cover
        the growth."""
        need = min(self.blocks_for(rows), self.max_blocks)
        have = int(self._blocks_used[slot])
        if need <= have:
            return False
        r = self.replica_of(slot)
        self._take(need - have, r)
        self.pages_allocated += need - have
        for b in range(have, need):
            page = self._free[r].pop()
            self._rc[r, page] = 1
            self.tables[slot, b] = page
        self._blocks_used[slot] = need
        return True

    def map_shared(self, slot: int, pages: Sequence[int],
                   first: int = 0) -> None:
        """Map already-resident pages (a matched prefix) as slot ``slot``'s
        blocks from ``first`` on, taking a reference on each. The slot must
        be empty (fresh admission). ``first`` > 0 is the window kind's case:
        the blocks below it count as trimmed (never held, never read)."""
        assert int(self._blocks_used[slot]) == 0, "slot must be empty"
        r = self.replica_of(slot)
        for b, page in enumerate(pages, start=first):
            self._rc[r, page] += 1
            self.tables[slot, b] = page
        self._blocks_used[slot] = first + len(pages)
        self._trimmed[slot] = first

    def alloc_pages(self, n: int, replica: int = 0) -> List[int]:
        """Pop ``n`` fresh pages (refcount 1 each) WITHOUT mapping them to
        a slot — the host-tier restore path allocates its landing pages
        here, scatters the stored KV in, then maps them via
        ``append_owned``. Raises PoolExhausted (after asking the
        reclaimer) with nothing allocated."""
        self._take(n, replica)
        out: List[int] = []
        for _ in range(n):
            page = self._free[replica].pop()
            self._rc[replica, page] = 1
            out.append(page)
        return out

    def append_owned(self, slot: int, pages: Sequence[int]) -> None:
        """Map already-allocated pages (references taken by
        ``alloc_pages``) as ``slot``'s next logical blocks — they extend a
        ``map_shared`` prefix, so no extra reference is taken here."""
        start = int(self._blocks_used[slot])
        for b, page in enumerate(pages, start=start):
            self.tables[slot, b] = page
        self._blocks_used[slot] = start + len(pages)

    def refcount(self, page: int, replica: int = 0) -> int:
        """Public read of a page's reference count (0 = on the free
        list) — the supported accessor for policy code like
        ``PrefixIndex.reclaim`` that must know whether a page is held
        only by the index."""
        return int(self._rc[replica, page])

    def refcounts(self, pages, replica: int = 0) -> np.ndarray:
        """Vectorized :meth:`refcount` over an array of page ids — one
        numpy gather instead of a Python loop of scalar reads, for policy
        code that scans many pages under a lock (``PrefixIndex.
        reclaimable``)."""
        return self._rc[replica, np.asarray(pages, dtype=np.int64)]

    def incref(self, page: int, replica: int = 0) -> None:
        self._rc[replica, page] += 1

    def decref(self, page: int, replica: int = 0) -> None:
        self._rc[replica, page] -= 1
        if self._rc[replica, page] == 0:
            self._free[replica].append(page)
        assert self._rc[replica, page] >= 0, \
            f"page {page} (replica {replica}) refcount underflow"

    def free_slot(self, slot: int) -> None:
        """Drop the slot's reference on each of its pages; pages whose
        refcount hits zero return to the free list (shared prefix pages
        survive under their other owners / the prefix index). Blocks
        released earlier by window trimming or window+sink pruning were
        already decref'd and are skipped."""
        used = int(self._blocks_used[slot])
        r = self.replica_of(slot)
        plo, phi = int(self._pruned_lo[slot]), int(self._pruned_hi[slot])
        for b in range(self._trimmed[slot], used):
            if plo <= b < phi:
                continue  # pruned: reference already dropped
            self.decref(int(self.tables[slot, b]), r)
        # trimmed/pruned entries were already decref'd — just restore the
        # "unbacked maps page 0" invariant for the whole row
        self.tables[slot, :used] = SACRIFICIAL_PAGE
        self._blocks_used[slot] = 0
        self._trimmed[slot] = 0
        self._pruned_lo[slot] = 0
        self._pruned_hi[slot] = 0

    def trim_below_window(self, slot: int, length: int, window: int) -> int:
        """Release the slot's leading blocks that sliding-window attention
        can never read again: block b is dead once its last row
        ``(b+1)*P - 1`` falls below ``length - window`` (window starts only
        move forward, so this is monotone-safe — the reader masks/skips
        those blocks already; ops/paged_attention.py start_blk). The table
        entries keep their stale page ids, which is fine: they are never
        read and ``ensure`` never rewinds. Returns blocks freed now."""
        used = int(self._blocks_used[slot])
        r = self.replica_of(slot)
        dead_rows = max(length - window, 0)
        dead = min(dead_rows // self.page_size, used)
        freed = 0
        for b in range(self._trimmed[slot], dead):
            self.decref(int(self.tables[slot, b]), r)
            freed += 1
        if dead > self._trimmed[slot]:
            self._trimmed[slot] = dead
        self.pages_trimmed += freed
        return freed

    def prune_range(self, slot: int, lo: int, hi: int) -> int:
        """Window+sink KV compression: release the slot's logical blocks
        [lo, hi) — the dead middle between the attention-sink pages
        ([0, lo)) and the sliding window's tail. Each released page drops
        this slot's reference (pages shared with the prefix index or
        other slots survive under their other owners) and its table entry
        is remapped to the sacrificial page, so a stale read is
        deterministic garbage the pruned attention mask never exposes.
        The range only grows forward: repeated calls release
        [max(lo, previous hi), hi). Returns blocks released now.
        Caller (the engine, under its lock) guarantees the mask stops
        attending these rows before the next dispatch."""
        used = int(self._blocks_used[slot])
        hi = min(hi, used)
        prev_hi = int(self._pruned_hi[slot])
        start = max(lo, prev_hi)
        if hi <= start:
            return 0
        r = self.replica_of(slot)
        freed = 0
        for b in range(start, hi):
            self.decref(int(self.tables[slot, b]), r)
            self.tables[slot, b] = SACRIFICIAL_PAGE
            freed += 1
        if prev_hi == 0:
            self._pruned_lo[slot] = lo
        self._pruned_hi[slot] = hi
        return freed

    def pruned_blocks(self, slot: int) -> int:
        """Blocks of ``slot`` released by :meth:`prune_range` so far."""
        return int(self._pruned_hi[slot] - self._pruned_lo[slot]) \
            if self._pruned_hi[slot] else 0

    def slot_pages_resident(self, slot: int) -> int:
        """Pages the slot currently references (mapped blocks minus
        window-trimmed and pruned ones) — what the compressed-slot
        residency gauge reports."""
        return max(
            int(self._blocks_used[slot]) - int(self._trimmed[slot])
            - self.pruned_blocks(slot),
            0,
        )

    def slot_rows_backed(self, slot: int) -> int:
        return int(self._blocks_used[slot]) * self.page_size


class SlotStates:
    """The host's account of the state kind (the module's header): the two
    arrays' shapes and which slots' states are live. The arrays themselves
    ride the engine's donated state beside the pools."""

    def __init__(self, layers: int, num_slots: int, state_shape: Tuple[int, ...],
                 tail_shape: Tuple[int, ...]) -> None:
        self.layers = layers
        self.num_slots = num_slots
        # row num_slots: the scratch slot of a decode step's dead slots
        self.state_shape = (layers, num_slots + 1, *state_shape)
        taps, width = tail_shape
        # (slots rounded up to whole bfloat16 tiles of 16 rows: the header)
        self.tail_shape = (layers, taps, -(-(num_slots + 1) // 16) * 16, width)
        self.live = np.zeros(num_slots, dtype=bool)

    @classmethod
    def of(cls, cfg, num_slots: int) -> "SlotStates":
        """The account of ``cfg``'s state kind over ``num_slots`` slots."""
        return cls(cfg.layers_of(cfg.state_kind), num_slots, *cfg.state_shapes)

    @property
    def slot_bytes(self) -> int:
        """One slot's states and tails over all the kda layers."""
        state = int(np.prod(self.state_shape[2:])) * 4
        tail = self.tail_shape[1] * self.tail_shape[3] * 2
        return self.layers * (state + tail)

    def take(self, slot: int) -> None:
        self.live[slot] = True

    def free_slot(self, slot: int) -> None:
        self.live[slot] = False

    def stats(self) -> Dict[str, int]:
        return {
            "kv_state_slots": int(self.live.sum()),
            "kv_state_bytes": self.slot_bytes * (self.num_slots + 1),
            "kv_state_bytes_live": self.slot_bytes * int(self.live.sum()),
        }


class SeenPrefixes:
    """The block hashes of the prompts admitted, and no pages: what a model
    with a state kind keeps in the prefix index's place, to count the hits
    it refuses (the module's header). Bounded like the index, by the pool's
    pages, oldest out first."""

    def __init__(self, max_blocks: int) -> None:
        self.max_blocks = max_blocks
        self._seen: "OrderedDict[bytes, None]" = OrderedDict()

    def match(self, hashes: Sequence[bytes]) -> int:
        """How many leading blocks of the chain an earlier prompt had."""
        n = 0
        for h in hashes:
            if h not in self._seen:
                break
            self._seen.move_to_end(h)
            n += 1
        return n

    def put(self, hashes: Sequence[bytes]) -> None:
        for h in hashes:
            self._seen[h] = None
            self._seen.move_to_end(h)
        while len(self._seen) > self.max_blocks:
            self._seen.popitem(last=False)


class PoolLayout(NamedTuple):
    """Where the pages of a pool by kind lie (paged.py header): constants
    of a graph. ``kinds[i]`` / ``bases[i]`` are the kind of layer i of a
    period and the first page of its range among the period's ``pages``."""

    max_blocks: int
    kinds: Tuple[str, ...]
    bases: Tuple[int, ...]
    pages: int

    def table_of(self, tables, kind: str):
        """The ``kind`` table(s) of the side-by-side array [.., 2 * MB]."""
        at = KINDS.index(kind) * self.max_blocks
        return tables[..., at : at + self.max_blocks]


class KindPageAllocator:
    """The pages of a stack of window and full layers: a PageAllocator a
    kind (``full``, ``window``) behind the interface the engine and the
    batcher use, their tables two views of one [S, 2 * MAX_BLOCKS] array.

    ``ensure`` backs a slot's rows in both kinds or in neither;
    ``trim_below_window`` releases the window kind's pages only (the full
    kind reads every row); ``free_slot`` frees both. ``window_rows`` is what
    ONE slot's window kind holds at most: the window, one chunk in flight
    and a page of straddle, in whole pages."""

    replicas = 1

    def __init__(self, full_pages: int, window_pages: int, page_size: int,
                 num_slots: int, max_blocks: int,
                 period_kinds: Sequence[str]) -> None:
        self.page_size = page_size
        self.num_slots = num_slots
        self.max_blocks = max_blocks
        self.full = PageAllocator(
            full_pages, page_size, num_slots, max_blocks, kind="full"
        )
        self.window = PageAllocator(
            window_pages, page_size, num_slots, max_blocks, kind="window"
        )
        self.by_kind = {"full": self.full, "window": self.window}
        self.tables = np.full(
            (num_slots, 2 * max_blocks), SACRIFICIAL_PAGE, dtype=np.int32
        )
        for i, kind in enumerate(KINDS):
            self.by_kind[kind].tables = self.tables[
                :, i * max_blocks : (i + 1) * max_blocks
            ]
        bases, at = {}, 0
        for kind in KINDS:
            for i, k in enumerate(period_kinds):
                if k == kind:
                    bases[i] = at
                    at += self.by_kind[kind].num_pages
        self.layout = PoolLayout(
            max_blocks, tuple(period_kinds),
            tuple(bases[i] for i in range(len(period_kinds))), at,
        )

    # -- what the engine and the batcher ask of any allocator ---------------

    def replica_of(self, slot: int) -> int:
        return 0

    @property
    def free_pages(self) -> int:
        return self.full.free_pages + self.window.free_pages

    def free_pages_for(self, slot: int) -> int:
        return self.full.free_pages_for(slot)

    def pages_in_use(self) -> int:
        return self.full.pages_in_use() + self.window.pages_in_use()

    def capacity_blocks(self) -> int:
        """Most blocks one slot can ever hold: the FULL kind's (a slot's
        window kind holds ``can_hold``'s bounded rows)."""
        return self.full.capacity_blocks()

    def blocks_for(self, rows: int) -> int:
        return self.full.blocks_for(rows)

    def can_hold(self, rows: int, window_rows: int) -> bool:
        """Whether a slot of ``rows`` rows, ``window_rows`` of them within
        reach of a window layer at once, can ever be backed."""
        return (
            self.full.blocks_for(rows) <= self.full.capacity_blocks()
            and self.window.blocks_for(window_rows)
            <= self.window.capacity_blocks()
        )

    def ensure(self, slot: int, rows: int) -> bool:
        """Back ``rows`` logical rows of ``slot`` in BOTH kinds, or raise
        PoolExhausted (naming the kind) with neither grown."""
        need = min(self.blocks_for(rows), self.max_blocks)
        for alloc in (self.window, self.full):
            grow = need - int(alloc._blocks_used[slot])
            if grow > 0:
                alloc._take(grow)  # reclaims, or raises before any pop
        changed = self.full.ensure(slot, rows)
        return self.window.ensure(slot, rows) or changed

    def trim_below_window(self, slot: int, length: int, window: int) -> int:
        return self.window.trim_below_window(slot, length, window)

    def free_slot(self, slot: int) -> None:
        self.full.free_slot(slot)
        self.window.free_slot(slot)

    def slot_pages_resident(self, slot: int, kind: str = "") -> int:
        if kind:
            return self.by_kind[kind].slot_pages_resident(slot)
        return sum(a.slot_pages_resident(slot) for a in self.by_kind.values())

    def slot_rows_backed(self, slot: int) -> int:
        return self.full.slot_rows_backed(slot)

    def stats(self) -> Dict[str, int]:
        """The counters by kind (pool.stats()). ``in_use`` counts what the
        prefix index keeps of finished requests too (it stands near the pool
        whenever sharing is on); ``kv_full_pages_live`` is the demand: the
        full-kind pages the slots map now, a page two slots share once a
        slot."""
        return {
            "kv_full_pages_in_use": self.full.pages_in_use(),
            "kv_full_pages_live": sum(
                self.full.slot_pages_resident(s)
                for s in range(self.tables.shape[0])
            ),
            "kv_full_pages": self.full.num_pages - 1,
            "kv_window_pages_in_use": self.window.pages_in_use(),
            "kv_window_pages": self.window.num_pages - 1,
            "kv_window_pages_allocated": self.window.pages_allocated,
            "kv_window_pages_trimmed": self.window.pages_trimmed,
        }


class WindowPrefixPages:
    """The window kind's side of prefix sharing (paged.py header): for a
    registered block's chain hash, the window-kind page the registering slot
    still held, one reference each. LRU; the window allocator's reclaimer
    drops the coldest entries no slot shares when its free list runs dry."""

    def __init__(self, allocator: PageAllocator) -> None:
        self.alloc = allocator
        self._pages: "OrderedDict[bytes, int]" = OrderedDict()
        allocator.reclaimer = self.reclaim

    def __len__(self) -> int:
        return len(self._pages)

    def put(self, hashes: Sequence[bytes], pages: Sequence[int]) -> None:
        for h, page in zip(hashes, pages):
            if h in self._pages:
                self._pages.move_to_end(h)
                continue
            self.alloc.incref(page)
            self._pages[h] = page

    def servable(self, hashes: Sequence[bytes], window: int) -> Tuple[int, List[int]]:
        """The longest n <= len(hashes) such that a hit at n blocks has every
        window-kind block that holds a row of (n * P - window, n * P): (n,
        those blocks' pages, the first of them being block n - len(pages))."""
        P = self.alloc.page_size
        for n in range(len(hashes), 0, -1):
            first = max(n * P - window + 1, 0) // P
            pages = [self._pages.get(h) for h in hashes[first:n]]
            if None not in pages:
                for h in hashes[first:n]:
                    self._pages.move_to_end(h)
                return n, pages
        return 0, []

    def reclaim(self, n: int) -> int:
        dropped = 0
        for h in list(self._pages):
            if dropped >= n:
                break
            page = self._pages[h]
            if self.alloc.refcount(page) == 1:
                del self._pages[h]
                self.alloc.decref(page)
                dropped += 1
        return dropped

    def clear(self) -> None:
        while self._pages:
            _, page = self._pages.popitem(last=False)
            self.alloc.decref(page)


def chain_hashes(
    token_ids: Sequence[int], page_size: int, num_blocks: int
) -> List[bytes]:
    """Content hash per full prompt block, chained so a block's hash
    commits to everything before it — matching block b therefore matches
    the entire prefix [0, (b+1)*P), which is exactly the K/V-equivalence
    condition (K/V of a row depends on all rows before it).

    sha256 over the token bytes, NOT Python's ``hash()``: the index key
    decides whose K/V a request attends over, so a collision is silent
    cross-request cache poisoning — and tuple ``hash()`` is analyzable
    enough to craft collisions in a multi-tenant deployment."""
    import hashlib

    ids = np.asarray(token_ids[: num_blocks * page_size], np.int32)
    hashes: List[bytes] = []
    h = b""
    for b in range(num_blocks):
        block = ids[b * page_size : (b + 1) * page_size]
        h = hashlib.sha256(h + block.tobytes()).digest()
        hashes.append(h)
    return hashes


class PromptHashes(NamedTuple):
    """A prompt's chain hashes with what they were computed over, so that
    whoever is handed them (the batcher from the pool, the engine from the
    batcher) can tell its own truncation's from another's: the prompt's
    last ``rows`` ids, in blocks of ``page_size``
    (``TPUEngine.prompt_hashes`` is the rule)."""

    rows: int
    page_size: int
    hashes: List[bytes]


# -- host-tier wire format (fleet KV transfer, aios_tpu/fleet/kvx.py) -------

# One HostPageStore entry <-> self-describing bytes: magic, tensor count,
# then per tensor key / dtype string / shape / raw buffer. The crc32 rides
# the RPC envelope separately (fleet.proto PageEntry.crc32), computed by
# HostPageStore._entry_crc over the ARRAYS — so the receiver re-derives it
# from the unpacked entry and a flipped bit anywhere in transit (or in the
# sender's host RAM) fails verification, never scatters into live KV.
_WIRE_MAGIC = b"KVX1"


class LatentEntryUnsupported(ValueError):
    """A page of a latent (MLA) pool has no entry kind yet: the host spill
    tier and the fleet transfer plane carry K/V pages only."""


def refuse_latent_entry(entry: Dict[str, np.ndarray]) -> None:
    """Raise for a page entry cut from a latent pool. A K/V page's two
    arrays have one shape; a latent page's (latents, padded rotary parts)
    do not, and a receiver would scatter them as keys and values."""
    k, v = entry.get("k"), entry.get("v")
    if k is not None and v is not None and k.shape != v.shape:
        raise LatentEntryUnsupported(
            f"page entry with k {k.shape} and v {v.shape} is a latent "
            "(MLA) page: the host spill tier and KVX have no entry kind "
            "for it yet"
        )


def pack_entry(entry: Dict[str, np.ndarray]) -> bytes:
    """Serialize one page-KV entry for the transfer plane (sorted keys,
    so the byte stream — like the crc — is order-independent). Refuses a
    latent page (``LatentEntryUnsupported``)."""
    refuse_latent_entry(entry)
    parts = [_WIRE_MAGIC, struct.pack("<B", len(entry))]
    for key in sorted(entry):
        a = np.ascontiguousarray(entry[key])
        kb = key.encode("utf-8")
        db = a.dtype.str.encode("ascii")
        parts.append(struct.pack("<B", len(kb)))
        parts.append(kb)
        parts.append(struct.pack("<B", len(db)))
        parts.append(db)
        parts.append(struct.pack("<B", a.ndim))
        parts.append(struct.pack(f"<{a.ndim}I", *a.shape))
        parts.append(struct.pack("<Q", a.nbytes))
        parts.append(a.tobytes())
    return b"".join(parts)


def unpack_entry(data: bytes) -> Dict[str, np.ndarray]:
    """Inverse of :func:`pack_entry`. Raises ``ValueError`` on any
    malformed framing — the transfer plane counts that as a
    ``decode_error`` and falls back to local prefill, exactly like a
    failed host-tier restore. Arrays are COPIES (writable): store
    entries must be mutable for the ``host_store.corrupt`` fault
    point and immutable-by-convention everywhere else."""
    if data[:4] != _WIRE_MAGIC:
        raise ValueError("bad page-entry magic")
    off = 4
    try:
        (n,) = struct.unpack_from("<B", data, off)
        off += 1
        entry: Dict[str, np.ndarray] = {}
        for _ in range(n):
            (klen,) = struct.unpack_from("<B", data, off)
            off += 1
            key = data[off : off + klen].decode("utf-8")
            off += klen
            (dlen,) = struct.unpack_from("<B", data, off)
            off += 1
            dtype = np.dtype(data[off : off + dlen].decode("ascii"))
            off += dlen
            (ndim,) = struct.unpack_from("<B", data, off)
            off += 1
            shape = struct.unpack_from(f"<{ndim}I", data, off)
            off += 4 * ndim
            (nbytes,) = struct.unpack_from("<Q", data, off)
            off += 8
            if off + nbytes > len(data):
                raise ValueError("page-entry payload truncated")
            a = np.frombuffer(
                data[off : off + nbytes], dtype=dtype
            ).reshape(shape).copy()
            off += nbytes
            entry[key] = a
    except struct.error as exc:
        raise ValueError(f"bad page-entry framing: {exc}") from exc
    if off != len(data):
        raise ValueError("trailing bytes after page-entry payload")
    return entry


class HostPageStore:
    """Host-RAM spill tier behind the prefix cache (hash -> page KV bytes).

    Every HBM eviction from the :class:`PrefixIndex` — LRU past
    ``max_pages`` or the allocator's ``reclaim()`` under pool pressure —
    used to throw the computed KV away; with a store configured
    (``AIOS_TPU_PREFIX_HOST_BYTES`` / ``ModelConfig.prefix_host_bytes``)
    the page's contents are copied device->host here instead, and a later
    prompt whose hash chain misses HBM but hits this tier restores them
    with a ``device_put`` + scatter instead of a prefill forward pass.
    Host RAM is orders of magnitude larger than the HBM slack the index
    can hold, so this multiplies effective prefix capacity (RTP-LLM's
    multi-tier KV cache, PAPERS.md).

    Entries are numpy arrays keyed by the same chain hash the index uses;
    the byte budget is enforced by LRU eviction. The store has its own
    lock: the spill worker writes from its background thread, the engine
    reads under its dispatch lock, and the serving router peeks without
    either.

    Integrity: every entry carries a crc32 computed at spill time and
    verified at restore-probe time — host RAM sits outside the device's
    ECC domain and an entry may be days old, so a flipped byte would
    otherwise scatter silently into live KV and poison every request
    sharing the prefix. A mismatch drops the entry (counted by
    ``corruptions`` / ``aios_tpu_prefix_host_corrupt_total``) and the
    chain truncates there: the caller recomputes instead of restoring
    garbage. The ``host_store.corrupt`` fault point (docs/FAULTS.md)
    flips a byte of a matched entry to drive this path on demand."""

    def __init__(self, max_bytes: int) -> None:
        self.max_bytes = int(max_bytes)
        #: guarded_by _lock
        self._entries: "OrderedDict[bytes, Dict[str, np.ndarray]]" = (
            OrderedDict()
        )
        #: guarded_by _lock
        self._crcs: Dict[bytes, int] = {}
        self.bytes_resident = 0  #: guarded_by _lock
        self.spills = 0  # entries accepted from HBM evictions
        self.restores = 0  # entries promoted back into pool pages
        self.hits = 0  # restore probes that found >= 1 entry
        self.misses = 0
        self.corruptions = 0  # entries dropped on crc32 mismatch
        self._lock = make_lock("host_store")

    @staticmethod
    def _entry_bytes(entry: Dict[str, np.ndarray]) -> int:
        return sum(int(a.nbytes) for a in entry.values())

    @staticmethod
    def _entry_crc(entry: Dict[str, np.ndarray]) -> int:
        crc = 0
        for key in sorted(entry):
            a = entry[key]
            if not a.flags["C_CONTIGUOUS"]:
                a = np.ascontiguousarray(a)
            # checksum the array's buffer directly — tobytes() would
            # copy every page just to feed the crc, doubling memory
            # traffic on each spill and restore probe
            crc = zlib.crc32(a, crc)
        return crc

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def put(self, h: bytes, entry: Dict[str, np.ndarray]) -> None:
        """Insert a spilled page (the newest entry; LRU evicts past the
        byte budget). An entry bigger than the whole budget is dropped.
        The crc32 is computed OUTSIDE the lock (spill-worker thread CPU
        time; the engine's restore probe shares this lock). Refuses a
        latent page (``LatentEntryUnsupported``)."""
        refuse_latent_entry(entry)
        nb = self._entry_bytes(entry)
        if nb > self.max_bytes:
            return
        crc = self._entry_crc(entry)
        with self._lock:
            old = self._entries.pop(h, None)
            if old is not None:
                self.bytes_resident -= self._entry_bytes(old)
            self._entries[h] = entry
            self._crcs[h] = crc
            self.bytes_resident += nb
            self.spills += 1
            while self.bytes_resident > self.max_bytes and self._entries:
                dropped_h, dropped = self._entries.popitem(last=False)
                self._crcs.pop(dropped_h, None)
                self.bytes_resident -= self._entry_bytes(dropped)

    def match_chain(
        self, hashes: Sequence[bytes]
    ) -> List[Tuple[bytes, Dict[str, np.ndarray]]]:
        """Longest stored prefix of ``hashes`` (LRU refreshed, hit/miss
        counted once per probe). Entries stay resident until the caller
        confirms the restore with ``discard`` — a failed restore (pool
        exhausted mid-allocation) must not lose the spilled KV.

        Every matched entry's crc32 is verified before it is handed out;
        a mismatch drops the entry and truncates the chain there (the
        caller recomputes the tail — restoring a corrupt page would
        poison every request sharing the prefix). The crc pass runs
        OUTSIDE the lock (put()'s rationale, mirrored: the spill worker
        and concurrent probes must not stall behind checksum CPU time);
        entries are immutable once stored, and the drop re-checks
        identity under the lock in case a concurrent put replaced the
        hash meanwhile."""
        candidates: List[Tuple[bytes, Dict[str, np.ndarray], int]] = []
        with self._lock:
            for h in hashes:
                e = self._entries.get(h)
                if e is None:
                    break
                self._entries.move_to_end(h)
                candidates.append((h, e, self._crcs.get(h)))
        if candidates:
            # chaos (docs/FAULTS.md): fired only when the probe actually
            # matched — flipping nothing on a miss would count an
            # injected fault whose recovery path never ran
            act = faults.point("host_store.corrupt")
            if act is not None:
                a = next(iter(candidates[0][1].values()))
                a.flat[0] = -a.flat[0] if a.flat[0] else 1
        out: List[Tuple[bytes, Dict[str, np.ndarray]]] = []
        bad: Optional[Tuple[bytes, Dict[str, np.ndarray]]] = None
        for h, e, crc in candidates:
            if crc != self._entry_crc(e):
                bad = (h, e)
                break
            out.append((h, e))
        with self._lock:
            if bad is not None and self._entries.get(bad[0]) is bad[1]:
                self._entries.pop(bad[0], None)
                self._crcs.pop(bad[0], None)
                self.bytes_resident -= self._entry_bytes(bad[1])
                self.corruptions += 1
                log.error(
                    "host-tier page failed crc32 verification; "
                    "dropped (chain truncated at %d of %d)",
                    len(out), len(hashes),
                )
            if out:
                self.hits += 1
            else:
                self.misses += 1
        return out

    def note_failed_restore(self) -> None:
        """A probe hit but the restore itself failed (scatter error or an
        injected ``host_store.restore_fail``): count it as a miss too —
        the request paid a full recompute, which is what the hit/miss
        ratio is supposed to predict."""
        with self._lock:
            self.misses += 1

    def peek_chain(self, hashes: Sequence[bytes]) -> int:
        """Length of the longest stored prefix WITHOUT touching LRU order
        or the hit/miss counters — the serving router's read-only overlap
        probe (same contract as ``PrefixIndex.peek``)."""
        n = 0
        with self._lock:
            for h in hashes:
                if h not in self._entries:
                    break
                n += 1
        return n

    def export_chain(
        self, hashes: Sequence[bytes], budget_bytes: int = 0
    ) -> List[Tuple[bytes, int, Dict[str, np.ndarray]]]:
        """Longest stored prefix of ``hashes`` as wire-ready
        ``(hash, crc32, entry)`` triples for the fleet transfer plane —
        no LRU refresh and no hit/miss movement (exporting to a peer is
        not a local restore probe). ``budget_bytes`` > 0 truncates the
        chain once the cumulative entry size would exceed it.

        The sender-side half of the verified-at-both-ends contract:
        every entry's stored crc32 is recomputed here before it ships; a
        mismatch (host-RAM rot since the spill) drops the entry, counts
        a corruption, and truncates the chain — shipping a rotten page
        would just move the receiver's crc failure one hop later."""
        candidates: List[Tuple[bytes, Dict[str, np.ndarray], int]] = []
        total = 0
        with self._lock:
            for h in hashes:
                e = self._entries.get(h)
                if e is None:
                    break
                total += self._entry_bytes(e)
                if budget_bytes and total > budget_bytes and candidates:
                    break
                candidates.append((h, e, self._crcs.get(h)))
        out: List[Tuple[bytes, int, Dict[str, np.ndarray]]] = []
        bad: Optional[Tuple[bytes, Dict[str, np.ndarray]]] = None
        for h, e, crc in candidates:
            if crc != self._entry_crc(e):
                bad = (h, e)
                break
            out.append((h, crc, e))
        if bad is not None:
            with self._lock:
                if self._entries.get(bad[0]) is bad[1]:
                    self._entries.pop(bad[0], None)
                    self._crcs.pop(bad[0], None)
                    self.bytes_resident -= self._entry_bytes(bad[1])
                    self.corruptions += 1
            log.error(
                "host-tier page failed crc32 at export; dropped "
                "(chain truncated at %d of %d)", len(out), len(hashes),
            )
        return out

    def stored_hashes(self, limit: int) -> List[bytes]:
        """Up to ``limit`` most-recently-used entry hashes — the host
        tier's contribution to the gossiped prefix digest. Read-only
        (no LRU refresh, no counters)."""
        with self._lock:
            keys = list(self._entries.keys())
        return keys[-limit:] if limit else []

    def discard(self, hashes: Sequence[bytes], *, restored: bool = False
                ) -> None:
        """Drop entries (restore promotion, or invalidation). With
        ``restored`` the restore counter moves."""
        with self._lock:
            for h in hashes:
                e = self._entries.pop(h, None)
                self._crcs.pop(h, None)
                if e is not None:
                    self.bytes_resident -= self._entry_bytes(e)
                    if restored:
                        self.restores += 1

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._crcs.clear()
            self.bytes_resident = 0


class _PrefixIndexBase:
    """Shared plumbing of the prefix-page indexes: the allocator hookup
    (``reclaimer``), hit/miss counters, the host-tier ``spill`` hook and
    the lock discipline (the index carries its OWN lock — the serving
    router peeks it per incoming request, and a probe that had to wait
    for an in-flight decode dispatch or a multi-second XLA compile would
    stall pool-wide admission behind one replica's graph build).

    Two implementations share the contract: the legacy flat hash-chain
    map (:class:`PrefixIndex`, the ``AIOS_TPU_PREFIX_RADIX=0`` escape
    hatch) and the refcounted radix tree (:class:`RadixPrefixIndex`, the
    default — SGLang-style cross-request sharing with leaf-LRU
    eviction)."""

    def __init__(self, allocator: PageAllocator, max_pages: int) -> None:
        if allocator.replicas != 1:
            # prefix pages are replica-local under a dp-partitioned pool;
            # cross-replica sharing is impossible, so the engine disables
            # the index rather than serve replica-0-only hits
            raise ValueError(
                "prefix indexes require an unreplicated pool (replicas=1)"
            )
        self.alloc = allocator
        self.max_pages = max_pages
        self.hits = 0
        self.misses = 0
        # host-tier demotion hook: called with evicted (hash, page) pairs
        # before their references drop (see PrefixIndex docstring); None
        # keeps the pre-host-tier behavior (evictions just free the pages)
        self.spill: Optional[
            Callable[[List[Tuple[bytes, int]]], None]
        ] = None
        self._lock = make_lock("prefix_index")
        allocator.reclaimer = self.reclaim

    def reclaim(self, n: int) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def _drop(self, evicted: List[Tuple[bytes, int]]) -> None:
        """Spill evicted entries (hook set), then release their page
        references. Runs OUTSIDE the index lock — the spill hook enqueues
        device reads and the router's ``peek`` must not wait on them; the
        allocator mutation is safe because both eviction paths are
        reached from engine-lock-holding callers. References drop only
        AFTER the spill captured the contents, so a freed page can't be
        reallocated and overwritten mid-copy."""
        if not evicted:
            return
        try:
            if self.spill is not None:
                try:
                    self.spill(evicted)
                except Exception:  # noqa: BLE001 - degrade to plain evict
                    log.exception(
                        "host-tier spill failed; dropping %d page(s)",
                        len(evicted),
                    )
        finally:
            # the references drop even if the spill dies with a
            # BaseException (KeyboardInterrupt mid-gather): these entries
            # are already out of the index, so skipping the decref would
            # leak their pages for the process lifetime
            for _, page in evicted:
                self.alloc.decref(page)


class PrefixIndex(_PrefixIndexBase):
    """Content-addressed cache of prompt-prefix pages (hash -> page).

    Agent workloads resend the same system/task preamble constantly
    (SURVEY.md section 3.1: every reasoning round rebuilds the prompt from
    the same context); matching a prompt's leading full blocks against this
    index turns their prefill into a table update — zero forward-pass
    compute and zero new pages. The index holds one reference per cached
    page, so pages survive their originating request; LRU eviction (and the
    allocator's reclaimer hook, under pool pressure) drops the coldest
    entries. Shared pages are read-only BY CONSTRUCTION: matches are capped
    at the prompt's last full block minus one row, so every write a slot
    performs (tail prefill, decode) lands at rows past the shared region.

    Hashes are the ``bytes`` sha256 digests of :func:`chain_hashes`,
    end-to-end — the engine's ``_match_prefix``/``prefix_hashes`` and the
    serving router's overlap probes all trade in the same digest chain.

    ``spill`` (set by the engine when a :class:`HostPageStore` is
    configured) is called with the evicted ``[(hash, page), ...]`` pairs
    BEFORE their index references drop, outside the index lock — the
    engine captures the pages' device contents there, so an eviction
    becomes a host-tier demotion instead of a loss. The hook runs under
    the engine dispatch lock (both eviction paths are reached from
    lock-holding callers), which is what keeps the page contents stable
    until the capture is enqueued.
    """

    def __init__(self, allocator: PageAllocator, max_pages: int) -> None:
        super().__init__(allocator, max_pages)
        self._index: "OrderedDict[bytes, int]" = OrderedDict()  # hash -> page

    def snapshot(self) -> Dict[bytes, int]:
        """Point-in-time hash -> page mapping of every cached block
        (tests/diagnostics; both index implementations provide it)."""
        with self._lock:
            return dict(self._index)

    def digest(self, limit: int) -> List[Tuple[bytes, int]]:
        """Up to ``limit`` hottest ``(chain hash, depth-in-blocks)``
        pairs for the gossiped fleet prefix digest. The flat map does
        not track chain depth, so it advertises 0 (membership is what
        remote overlap scoring consumes; depth is advisory). Read-only —
        no LRU refresh, no counters."""
        with self._lock:
            keys = list(self._index.keys())
        return [(h, 0) for h in keys[-limit:]] if limit else []

    def match(self, hashes: Sequence[bytes]) -> List[int]:
        """Longest indexed prefix of ``hashes``; returns its pages (LRU
        positions refreshed). No references are taken — the caller maps
        them via ``PageAllocator.map_shared`` under the engine lock."""
        pages: List[int] = []
        with self._lock:
            for h in hashes:
                page = self._index.get(h)
                if page is None:
                    break
                self._index.move_to_end(h)
                pages.append(page)
            if pages:
                self.hits += 1
            else:
                self.misses += 1
        return pages

    def peek(self, hashes: Sequence[bytes]) -> int:
        """Length of the longest indexed prefix of ``hashes`` WITHOUT
        touching hit/miss counters or LRU order — the serving router's
        read-only overlap probe (scoring N replicas per request must not
        skew the cache statistics or keep cold entries artificially
        warm)."""
        n = 0
        with self._lock:
            for h in hashes:
                if h not in self._index:
                    break
                n += 1
        return n

    def put(self, hashes: Sequence[bytes], pages: Sequence[int]) -> None:
        """Register freshly computed (or host-restored) prefix blocks, one
        index reference each; LRU entries past ``max_pages`` are evicted —
        spilled to the host tier first when a ``spill`` hook is set."""
        evicted: List[Tuple[bytes, int]] = []
        with self._lock:
            for h, page in zip(hashes, pages):
                if h in self._index:
                    self._index.move_to_end(h)
                    continue
                self.alloc.incref(page)
                self._index[h] = page
            while len(self._index) > self.max_pages:
                evicted.append(self._index.popitem(last=False))
        self._drop(evicted)

    def clear(self) -> None:
        """Drop every entry (and its page reference) WITHOUT spilling —
        the warmup/shutdown path, where the cached blocks are synthetic
        junk that must not pollute the host tier."""
        with self._lock:
            while self._index:
                _, page = self._index.popitem(last=False)
                self.alloc.decref(page)

    def reclaimable(self) -> int:
        """How many entries ``reclaim`` could free right now (pages held
        ONLY by the index, refcount 1). The restore path pre-clamps its
        chain to free + reclaimable so a chain the pool can't back
        doesn't evict cold HBM entries just to fail anyway."""
        with self._lock:
            if not self._index:
                return 0
            pages = np.fromiter(
                self._index.values(), dtype=np.int64, count=len(self._index)
            )
            return int(np.count_nonzero(self.alloc.refcounts(pages) == 1))

    def reclaim(self, n: int) -> int:
        """Drop up to ``n`` cold entries whose pages are held ONLY by the
        index (refcount 1) — called by the allocator when the free list
        runs dry. Entries still shared by live slots are left alone.
        Dropped pages spill to the host tier (hook set) before they free,
        so pool pressure demotes the cold prefix KV instead of burning
        it."""
        evicted: List[Tuple[bytes, int]] = []
        with self._lock:
            for h in list(self._index):
                if len(evicted) >= n:
                    break
                page = self._index[h]
                if self.alloc.refcount(page) == 1:
                    del self._index[h]
                    evicted.append((h, page))
        self._drop(evicted)
        return len(evicted)


class _RadixNode:
    """One path-compressed radix-tree node: a run of consecutive prefix
    blocks (``entries`` = aligned (chain hash, page) pairs) plus children
    keyed by the FIRST hash of each child's run. ``stamp`` is the LRU
    clock at the node's last traversal."""

    __slots__ = ("entries", "children", "parent", "stamp")

    def __init__(self, parent: Optional["_RadixNode"]) -> None:
        self.entries: List[Tuple[bytes, int]] = []
        self.children: Dict[bytes, "_RadixNode"] = {}
        self.parent = parent
        self.stamp = 0


class RadixPrefixIndex(_PrefixIndexBase):
    """Refcounted radix tree over prompt-prefix token blocks (SGLang-style,
    arXiv:2312.07104) — the default prefix index.

    Same digest currency as :class:`PrefixIndex` (the ``bytes`` sha256
    chain of :func:`chain_hashes`; a block's hash commits to everything
    before it, so a prompt's hash chain IS its tree path), but the tree
    structure buys what the flat LRU map cannot:

      * **sharing by construction** — eviction is leaf-LRU, bottom-up, so
        a cached chain's prefix is always cached too. The flat map could
        evict block 0 of a chain while deeper blocks survived as
        unreachable garbage, pinning their pages until a pool-pressure
        reclaim; here that state is unrepresentable.
      * **divergence-aware structure** — two prompts sharing K leading
        blocks share one K-entry path and branch below it (path
        compression splits a node at the divergence point), so the shared
        preamble's recency is maintained once, by every user, while each
        cold divergent tail ages out on its own.
      * **partial-node overlap credit** — ``peek`` counts a match that
        ends mid-node (a prompt diverging inside another prompt's cached
        run), so the serving router's overlap score sees the true
        shareable row count, not floor-to-node granularity.

    Eviction (LRU past ``max_pages``) and pool-pressure ``reclaim`` both
    pop entries from leaf TAILS (deepest blocks of the coldest chains
    first) and hand the evicted (hash, page) pairs to the PR 4 ``spill``
    hook before the references drop — the host-tier demotion contract is
    unchanged. ``put`` accepts chains whose leading blocks are already
    cached (the host-tier restore re-inserts a restored segment by
    passing its lead context), traversing the cached part and grafting
    only the new suffix."""

    def __init__(self, allocator: PageAllocator, max_pages: int) -> None:
        super().__init__(allocator, max_pages)
        self._root = _RadixNode(None)
        self._size = 0  # total entries (== pages referenced by the tree)
        self._clock = 0

    # -- internal helpers (caller holds self._lock) -------------------------

    def _split(self, node: _RadixNode, j: int) -> None:
        """Path-compression split: ``entries[:j]`` stay on ``node``; the
        suffix moves to a new child that inherits node's children (and
        node's pre-touch stamp, so the unshared tail ages on its own)."""
        suffix = node.entries[j:]
        child = _RadixNode(node)
        child.entries = suffix
        child.children = node.children
        child.stamp = node.stamp
        for c in child.children.values():
            c.parent = child
        node.entries = node.entries[:j]
        node.children = {suffix[0][0]: child}

    def _leaves(self):
        stack = [self._root]
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif n is not self._root:
                yield n

    def _detach(self, node: _RadixNode) -> None:
        parent = node.parent
        if parent is None:
            return
        for key, child in list(parent.children.items()):
            if child is node:
                del parent.children[key]
                break

    def _evict_overflow(self, evicted: List[Tuple[bytes, int]]) -> None:
        """Pop deepest blocks of least-recently-used chains until the
        size fits ``max_pages``. One leaf DFS per VICTIM LEAF, not per
        entry: the coldest leaf stays the minimum-stamp leaf until it
        drains, so its whole tail pops under one scan — a bulk overflow
        (a long prompt registering many blocks at once) holds the index
        lock for O(overflow + leaves), not O(overflow x tree)."""
        while self._size > self.max_pages:
            best = None
            for leaf in self._leaves():
                if leaf.entries and (
                    best is None or leaf.stamp < best.stamp
                ):
                    best = leaf
            if best is None:
                return
            while best.entries and self._size > self.max_pages:
                evicted.append(best.entries.pop())
                self._size -= 1
            if not best.entries:
                self._detach(best)  # parent may become the new leaf

    # -- the PrefixIndex contract -------------------------------------------

    def match(self, hashes: Sequence[bytes]) -> List[int]:
        """Longest cached prefix of ``hashes``; returns its pages (path
        stamps refreshed). A match ending mid-node splits it, so the
        matched run's recency refreshes without dragging the divergent
        tail along. No references are taken — the caller maps the pages
        via ``PageAllocator.map_shared`` under the engine lock."""
        pages: List[int] = []
        with self._lock:
            self._clock += 1
            node, i = self._root, 0
            while i < len(hashes):
                child = node.children.get(hashes[i])
                if child is None:
                    break
                j = 0
                while (
                    j < len(child.entries)
                    and i < len(hashes)
                    and child.entries[j][0] == hashes[i]
                ):
                    pages.append(child.entries[j][1])
                    i += 1
                    j += 1
                if j < len(child.entries):
                    # match ended mid-run (divergence OR a shorter
                    # prompt): split so only the MATCHED prefix's
                    # recency refreshes — stamping the whole node would
                    # keep its cold unmatched tail permanently warm
                    self._split(child, j)
                    child.stamp = self._clock
                    break
                child.stamp = self._clock
                node = child
            if pages:
                self.hits += 1
            else:
                self.misses += 1
        return pages

    def peek(self, hashes: Sequence[bytes]) -> int:
        """Length of the longest cached prefix WITHOUT touching hit/miss
        counters, stamps, or structure — the serving router's read-only
        overlap probe. Partial-node overlap IS credited: a prompt
        diverging inside a cached run scores the blocks it shares."""
        n = 0
        with self._lock:
            node, i = self._root, 0
            while i < len(hashes):
                child = node.children.get(hashes[i])
                if child is None:
                    break
                j = 0
                while (
                    j < len(child.entries)
                    and i < len(hashes)
                    and child.entries[j][0] == hashes[i]
                ):
                    n += 1
                    i += 1
                    j += 1
                if j < len(child.entries):
                    break
                node = child
        return n

    def put(self, hashes: Sequence[bytes], pages: Sequence[int]) -> None:
        """Register freshly computed (or host-restored) prefix blocks, one
        index reference per NEW entry; blocks already cached are traversed
        (recency refreshed), so callers may pass a chain whose lead is
        resident — the restore path passes lead + restored segment so the
        graft lands at the right tree position. Entries past ``max_pages``
        evict leaf-LRU — spilled to the host tier first when a ``spill``
        hook is set."""
        hashes = list(hashes)
        pages = list(pages)
        evicted: List[Tuple[bytes, int]] = []
        with self._lock:
            self._clock += 1
            node, i = self._root, 0
            while i < len(hashes):
                child = node.children.get(hashes[i])
                if child is None:
                    break
                j = 0
                while (
                    j < len(child.entries)
                    and i < len(hashes)
                    and child.entries[j][0] == hashes[i]
                ):
                    i += 1
                    j += 1
                if j < len(child.entries):
                    # split BEFORE stamping (divergence OR a shorter
                    # chain): the unshared suffix keeps the node's old
                    # stamp and ages on its own
                    self._split(child, j)
                    node = child
                    child.stamp = self._clock
                    break
                child.stamp = self._clock
                node = child
            if i < len(hashes) and i < len(pages):
                new = _RadixNode(node)
                new.stamp = self._clock
                for h, page in zip(hashes[i:], pages[i:]):
                    self.alloc.incref(page)
                    new.entries.append((h, page))
                node.children[hashes[i]] = new
                self._size += len(new.entries)
            self._evict_overflow(evicted)
        self._drop(evicted)

    def clear(self) -> None:
        """Drop every entry (and its page reference) WITHOUT spilling —
        the warmup/shutdown path (synthetic blocks must not pollute the
        host tier)."""
        with self._lock:
            stack = [self._root]
            while stack:
                n = stack.pop()
                for _, page in n.entries:
                    self.alloc.decref(page)
                stack.extend(n.children.values())
            self._root = _RadixNode(None)
            self._size = 0

    def reclaimable(self) -> int:
        """How many entries ``reclaim`` could free right now: an entry is
        reclaimable iff its page is held ONLY by the tree (refcount 1)
        AND everything below it in its subtree is reclaimable too —
        removal is suffix-of-tree only, or a cached chain would lose a
        middle block and strand its tail."""
        with self._lock:
            total = 0
            fully: Dict[int, bool] = {}
            stack: List[Tuple[_RadixNode, bool]] = [(self._root, False)]
            while stack:
                node, seen = stack.pop()
                if not seen:
                    stack.append((node, True))
                    for c in node.children.values():
                        stack.append((c, False))
                    continue
                f = all(
                    fully.pop(id(c)) for c in node.children.values()
                )
                if f:
                    run = 0
                    for _, page in reversed(node.entries):
                        if self.alloc.refcount(page) == 1:
                            run += 1
                        else:
                            break
                    total += run
                    f = run == len(node.entries)
                fully[id(node)] = f
            return total

    def reclaim(self, n: int) -> int:
        """Drop up to ``n`` cold entries whose pages are held ONLY by the
        tree — called by the allocator when the free list runs dry.
        Bottom-up and LRU-first: tail entries of the coldest leaves pop
        until a live-shared page blocks that chain; a leaf that empties
        detaches, exposing its parent's tail next. Dropped pages spill to
        the host tier (hook set) before they free."""
        evicted: List[Tuple[bytes, int]] = []
        with self._lock:
            while len(evicted) < n:
                cands = [
                    l for l in self._leaves()
                    if l.entries
                    and self.alloc.refcount(l.entries[-1][1]) == 1
                ]
                if not cands:
                    break
                leaf = min(cands, key=lambda l: l.stamp)
                while (
                    leaf.entries
                    and len(evicted) < n
                    and self.alloc.refcount(leaf.entries[-1][1]) == 1
                ):
                    evicted.append(leaf.entries.pop())
                    self._size -= 1
                if not leaf.entries:
                    self._detach(leaf)
        self._drop(evicted)
        return len(evicted)

    def snapshot(self) -> Dict[bytes, int]:
        """Point-in-time hash -> page mapping of every cached block
        (tests/diagnostics; same contract as ``PrefixIndex.snapshot``)."""
        with self._lock:
            out: Dict[bytes, int] = {}
            stack = [self._root]
            while stack:
                n = stack.pop()
                out.update(n.entries)
                stack.extend(n.children.values())
            return out

    def digest(self, limit: int) -> List[Tuple[bytes, int]]:
        """Up to ``limit`` ``(chain hash, depth-in-blocks)`` pairs for
        the gossiped fleet prefix digest — breadth-first, so when the
        cap bites, SHALLOW blocks survive: a remote prompt shorter than
        a cached chain still finds its prefix hash in the digest, while
        an over-deep match merely degrades to the advertised depth.
        Read-only (same contract as ``peek``)."""
        if not limit:
            return []
        out: List[Tuple[bytes, int]] = []
        with self._lock:
            queue: List[Tuple[_RadixNode, int]] = [(self._root, 0)]
            while queue and len(out) < limit:
                node, depth = queue.pop(0)
                d = depth
                for h, _ in node.entries:
                    d += 1
                    out.append((h, d))
                    if len(out) >= limit:
                        break
                for child in node.children.values():
                    queue.append((child, d))
        return out
