"""Cursor-based streaming merging iterator over sorted runs + memtable.

This replaces the old scan path's seek-retry loop (re-seeking *every* run and
sort-merging ``count`` candidates per attempt, restarting with a bigger window
on truncation) with the classic LSM design (DESIGN.md §3): one cursor per run
holding a position that only moves forward, plus a merge buffer refilled
incrementally.

Each refill:
  1. takes a window of entries from every source — sources are ordered
     newest-first (memtable, then runs as ``LSMStore._runs_newest_first``
     yields them), the same resolution order the scalar ``get`` path walks;
  2. clamps every window to the *frontier* — the smallest last-key among
     truncated windows, below which every version of every key is guaranteed
     visible (numpy slice views, nothing is copied);
  3. merges the clamped keys with one stable sort, so the first occurrence of
     a key is its newest version (no sequence numbers needed);
  4. emits at most ``demand`` winners, consuming each source only up to the
     last emitted key — unconsumed entries stay put and are re-windowed by
     the next refill, so oversized windows cost views, not work;
  5. materializes winning values with one batched row-gather + ``tobytes``
     per source (tombstone winners emit ``None`` and are skipped on read).

Cursors never move backwards and nothing is re-seeked.  ``scan`` passes its
``count`` as the demand hint, so a scan usually completes in one refill;
plain ``next`` streaming starts small and doubles the demand per refill.

I/O cost model: ``seek`` charges every participating run one iterator seek
(``stats.seeks``/``runs_touched_range``); ``consume`` charges every run the
data blocks *spanned* by the prefix the merged stream actually consumed from
it, deduplicated across refills at block granularity — matching
``SortedRun.blocks_spanned`` on the consumed ranges.  With a block cache
attached (``core.cache.BlockCache``) every spanned block first consults the
cache; only misses charge ``blocks_read``.
"""
from __future__ import annotations

import time
from bisect import bisect_right
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .memtable import Memtable
from .run import SortedRun
from .telemetry import span
from .types import KEY_DTYPE, TOMBSTONE_LEN, IOStats

_FIRST_DEMAND = 16
_MAX_WINDOW = 4096


def combined_mem_items(memtables: Sequence[Memtable], key: int
                       ) -> List[Tuple[int, int, Optional[bytes]]]:
    """Newest-wins combination of the memtable rotation queue's scans.

    ``memtables`` is newest first ([active, imm_newest, ..., imm_oldest],
    the engine's ``_mem_sources`` order); the first source holding a key
    owns it, so the merge core (and the range view's scan, DESIGN.md §13)
    sees one key-sorted memtable stream.
    """
    if not memtables:
        return []
    if len(memtables) == 1:
        return memtables[0].scan(key)
    combined = {}
    for mt in memtables:
        for k, s, v in mt.scan(key):
            if k not in combined:
                combined[k] = (s, v)
    return [(k, s, v) for k, (s, v) in sorted(combined.items())]


class _RunCursor:
    """Forward-only position over one immutable run, with block accounting."""

    __slots__ = ("run", "stats", "cache", "n", "pos", "_charged")

    def __init__(self, run: SortedRun, stats: IOStats, cache=None):
        self.run = run
        self.stats = stats
        self.cache = cache
        self.n = len(run)
        self.pos = self.n
        self._charged = -1

    def seek(self, key: int) -> None:
        self.stats.seeks += 1
        self.stats.runs_touched_range += 1
        self.pos = int(self.run.keys.searchsorted(np.uint64(key)))
        self._charged = -1

    def window(self, w: int):
        """Up to ``w`` keys at the cursor: (keys_view, truncated)."""
        i = self.pos
        e = i + w
        if e >= self.n:
            return self.run.keys[i:], False
        return self.run.keys[i:e], True

    def consume(self, cnt: int) -> None:
        """Advance past ``cnt`` entries, charging the blocks they span.

        Blocks already charged by an earlier refill are not re-charged; with a
        block cache attached each newly spanned block is a hit (free) or a
        miss (charged + admitted) instead of an unconditional read.
        """
        if cnt <= 0:
            return
        i = self.pos
        bo = self.run.block_of
        b0, b1 = int(bo[i]), int(bo[i + cnt - 1])
        first_new = max(b0, self._charged + 1)
        if self.cache is None:
            self.stats.blocks_read += b1 - first_new + 1
        else:
            # span-charge the newly consumed blocks in one cache call
            self.cache.read_block_span(self.run.run_id, first_new, b1,
                                       self.run.block_bytes, self.stats)
        self._charged = b1
        self.pos = i + cnt


class MergingIterator:
    """Streaming merge of runs (newest-first order) + an optional memtable.

    Usage: ``it.seek(k)`` then ``it.next()`` until None; or ``it.scan(k, n)``;
    or iterate (``for key, value in it`` after a seek).  Entries come out in
    strictly increasing key order; tombstones and shadowed versions are
    consumed internally.
    """

    def __init__(self, runs: Sequence[SortedRun],
                 memtable: Optional[Memtable] = None,
                 stats: Optional[IOStats] = None,
                 chunk: int = _MAX_WINDOW, cache=None,
                 memtables: Optional[Sequence[Memtable]] = None):
        """``memtables`` (newest first) supersedes ``memtable`` when given:
        the async engine passes [active, imm_newest, ..., imm_oldest] so the
        immutable-memtable queue stays visible between the active memtable
        and L0 (DESIGN.md §11); duplicates resolve newest-memtable-wins at
        seek time, so the merge core still sees one memtable stream."""
        self.stats = stats if stats is not None else IOStats()
        self._cursors: List[_RunCursor] = [
            _RunCursor(r, self.stats, cache) for r in runs if len(r)]
        if memtables is None:
            memtables = [memtable] if memtable is not None else []
        self._memtables: List[Memtable] = [m for m in memtables
                                           if m is not None]
        self._mem_keys = np.zeros(0, dtype=KEY_DTYPE)
        self._mem_items: List[Tuple[int, int, Optional[bytes]]] = []
        self._mem_pos = 0
        self._max_window = max(int(chunk), _FIRST_DEMAND)
        self._demand = _FIRST_DEMAND
        self._tomb_carry = 0
        self._exhausted = True
        self._bk: List[int] = []                    # emitted keys
        self._bv: List[Optional[bytes]] = []        # emitted values (aligned)
        self._bi = 0

    # ------------------------------------------------------------ interface
    def seek(self, key: int, expected: int = 0) -> None:
        """Position every cursor at its first entry >= key.

        ``expected`` hints how many entries the caller intends to consume so
        the first refill can size itself to demand.
        """
        key = int(key)
        for cur in self._cursors:
            cur.seek(key)
        self._mem_items = combined_mem_items(self._memtables, key)
        self._mem_keys = np.fromiter((e[0] for e in self._mem_items),
                                     KEY_DTYPE, len(self._mem_items))
        self._mem_pos = 0
        self._demand = max(int(expected), _FIRST_DEMAND)
        self._tomb_carry = 0
        self._exhausted = False
        self._bk = []
        self._bv = []
        self._bi = 0

    def next(self) -> Optional[Tuple[int, bytes]]:
        """The next live entry, or None when the stream is exhausted."""
        while True:
            i = self._bi
            if i < len(self._bk):
                self._bi = i + 1
                v = self._bv[i]
                if v is None:          # tombstone winner
                    continue
                return self._bk[i], v
            if self._exhausted or not self._refill():
                return None

    def scan(self, start_key: int, count: int) -> List[Tuple[int, bytes]]:
        """First ``count`` live entries with key >= start_key.  The seek's
        wall time is charged to ``stats.scan_seek_ns``."""
        t0 = time.perf_counter_ns()
        with span("lsm.scan_seek", runs=len(self._cursors)):
            self.seek(start_key, expected=count)
        self.stats.scan_seek_ns += time.perf_counter_ns() - t0
        out: List[Tuple[int, bytes]] = []
        with span("lsm.scan_merge", count=count):
            while len(out) < count:
                i = self._bi
                bk, bv = self._bk, self._bv
                nb = len(bk)
                if i >= nb:
                    if self._exhausted or not self._refill():
                        break
                    continue
                need = count - len(out)
                while i < nb and need:
                    v = bv[i]
                    if v is not None:
                        out.append((bk[i], v))
                        need -= 1
                    i += 1
                self._bi = i
        return out

    def __iter__(self) -> Iterator[Tuple[int, bytes]]:
        while True:
            e = self.next()
            if e is None:
                return
            yield e

    # ---------------------------------------------------------------- merge
    def _refill(self) -> bool:
        """Merge the sources' next windows into the emit buffer.

        ``demand`` — the emission cap — is the base geometric ramp plus
        *twice* the count of tombstone winners the previous refill emitted
        (``_tomb_carry``): tombstones occupy demand slots but yield no live
        entries, so without the carry a scan over a heavily-deleted range
        degrades to O(deleted / max_window) refills of mostly-dead winners.
        The 2x is what makes the growth geometric — a refill that was all
        tombstones doubles the next dead-prefix budget (carry alone would
        only add ``max_window`` per refill: O(sqrt(deleted)) refills, not
        O(log)).  The window follows demand past the ``_MAX_WINDOW`` cap
        when tombstone-driven, so the refill count stays O(log deleted).
        """
        demand = self._demand + 2 * self._tomb_carry
        self._demand = min(self._demand * 2, self._max_window)
        w = min(max(2 * demand, _FIRST_DEMAND),
                max(self._max_window, demand))
        # 1. windows, newest source first (memtable, then runs)
        parts_k: List[np.ndarray] = []
        sids: List[int] = []                        # -1 = memtable
        rows0: List[int] = []
        frontier: Optional[int] = None
        mi = self._mem_pos
        if mi < len(self._mem_keys):
            k = self._mem_keys[mi:mi + w]
            parts_k.append(k)
            sids.append(-1)
            rows0.append(mi)
            if mi + w < len(self._mem_keys):
                frontier = int(k[-1])
        for sid, cur in enumerate(self._cursors):
            k, truncated = cur.window(w)
            if not len(k):
                continue
            if truncated:
                fk = int(k[-1])
                frontier = fk if frontier is None else min(frontier, fk)
            parts_k.append(k)
            sids.append(sid)
            rows0.append(cur.pos)
        if not parts_k:
            self._exhausted = True
            return False
        # 2. clamp windows to the frontier (slice views, no copies)
        if frontier is not None:
            fb = np.uint64(frontier)
            cnts = [int(p.searchsorted(fb, side="right")) for p in parts_k]
            parts_k = [p[:c] for p, c in zip(parts_k, cnts)]
        else:
            cnts = [len(p) for p in parts_k]
        # 3. one stable sort; first occurrence of a key = newest version
        K = np.concatenate(parts_k) if len(parts_k) > 1 else parts_k[0]
        order = np.argsort(K, kind="stable")
        Ks = K[order]
        first = np.empty(Ks.size, dtype=bool)
        first[0] = True
        np.not_equal(Ks[1:], Ks[:-1], out=first[1:])
        widx = order[first]                 # concat-indices of winners
        wkeys = Ks[first]
        # 4. cap emission at demand; consume only up to the last emitted key
        if wkeys.size > demand:
            cutoff = np.uint64(wkeys[demand - 1])
            wkeys = wkeys[:demand]
            widx = widx[:demand]
            cnts = [int(p.searchsorted(cutoff, side="right"))
                    for p in parts_k]
        elif frontier is None:
            self._exhausted = True          # every source fully drained
        for sid, c in zip(sids, cnts):
            if sid < 0:
                self._mem_pos += c
            else:
                self._cursors[sid].consume(c)
        # 5. map winners back to (source, row) and batch-extract values
        starts = [0]
        for p in parts_k:
            starts.append(starts[-1] + len(p))
        nsrc = len(parts_k)
        vals: List[Optional[bytes]] = [None] * wkeys.size
        if nsrc == 1:
            groups = [(0, np.arange(wkeys.size), widx + rows0[0])]
        else:
            part_of = np.searchsorted(starts, widx, side="right") - 1
            groups = []
            for g in range(nsrc):
                sel = np.nonzero(part_of == g)[0]
                if sel.size:
                    groups.append((g, sel, widx[sel] - starts[g] + rows0[g]))
        for g, sel, rows in groups:
            sid = sids[g]
            if sid < 0:
                items = self._mem_items
                for t, r in zip(sel.tolist(), rows.tolist()):
                    vals[t] = items[r][2]
            else:
                run = self._cursors[sid].run
                vl = run.vlens[rows]
                vmax = run.vals.shape[1] if run.vals.ndim == 2 else 0
                flat = run.vals[rows].tobytes() if vmax else b""
                for o, (t, l) in enumerate(zip(sel.tolist(), vl.tolist())):
                    if l != TOMBSTONE_LEN:
                        off = o * vmax
                        vals[t] = flat[off:off + l]
        self._bk = wkeys.tolist()
        self._bv = vals
        self._bi = 0
        # tombstone winners consumed demand without yielding entries; grow
        # the next refill's demand by exactly that count (see docstring)
        self._tomb_carry = vals.count(None)
        return True
