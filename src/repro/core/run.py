"""Immutable sorted runs (the engine's SST analog).

A run stores its entries as parallel numpy arrays sorted by key:
  keys  : uint64 (strictly increasing — duplicates are resolved at build time,
          newest sequence number wins, matching LSM merge semantics)
  seqs  : uint64 sequence numbers (MVCC ordering across runs)
  vlens : int32 value lengths; TOMBSTONE_LEN marks a delete marker
  vals  : uint8 (n, Vmax) padded value payload

Entries are packed into BLOCK_SIZE blocks; ``block_of`` maps each entry to its
block id and the *fence pointers* (first key per block, kept in host memory —
"main memory" in the paper) let a reader locate the single candidate block of
any key with zero block touches, exactly the paper's fence-pointer model.
"""
from __future__ import annotations

import heapq
import itertools
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .bloom import BloomFilter
from .faults import CorruptionError, crc32c_rows
from .types import (BLOCK_SIZE, KEY_BYTES, KEY_DTYPE, SEQ_DTYPE,
                    TOMBSTONE_LEN, IOStats)

_run_ids = itertools.count()


def _entry_crcs(keys: np.ndarray, seqs: np.ndarray, vlens: np.ndarray,
                vals: np.ndarray) -> np.ndarray:
    """CRC-32C per entry over its canonical bytes (DESIGN.md §16.2):
    key(8 LE) | seq(8 LE) | vlen(4 LE, signed — tombstones included) |
    value[:max(vlen,0)].  One vectorized pass over a padded byte matrix."""
    n = int(keys.size)
    if n == 0:
        return np.zeros(0, dtype=np.uint32)
    vmax = vals.shape[1] if vals.ndim == 2 else 0
    mat = np.zeros((n, 20 + vmax), dtype=np.uint8)
    mat[:, 0:8] = keys.astype("<u8").view(np.uint8).reshape(n, 8)
    mat[:, 8:16] = seqs.astype("<u8").view(np.uint8).reshape(n, 8)
    mat[:, 16:20] = vlens.astype("<i4").view(np.uint8).reshape(n, 4)
    if vmax:
        mat[:, 20:] = vals
    lens = 20 + np.maximum(vlens, 0).astype(np.int64)
    return crc32c_rows(mat, lens)


class SortedRun:
    __slots__ = ("run_id", "keys", "seqs", "vlens", "vals", "block_of",
                 "fence_keys", "n_blocks", "data_bytes", "block_size",
                 "bloom", "level_hint", "block_crcs", "_uniform_vals")

    def __init__(self, keys: np.ndarray, seqs: np.ndarray, vlens: np.ndarray,
                 vals: np.ndarray, bits_per_key: float = 0.0,
                 block_size: int = BLOCK_SIZE, key_bytes: int = KEY_BYTES,
                 hash_fn=None):
        assert keys.ndim == 1
        self.block_size = block_size
        self.run_id = next(_run_ids)
        self.keys = np.ascontiguousarray(keys, dtype=KEY_DTYPE)
        self.seqs = np.ascontiguousarray(seqs, dtype=SEQ_DTYPE)
        self.vlens = np.ascontiguousarray(vlens, dtype=np.int32)
        self.vals = np.ascontiguousarray(vals, dtype=np.uint8)
        n = self.keys.size
        entry_sizes = key_bytes + np.maximum(self.vlens, 0).astype(np.int64)
        cum = np.cumsum(entry_sizes)
        self.data_bytes = int(cum[-1]) if n else 0
        # Entry i lives in the block containing its *starting* byte.
        starts = cum - entry_sizes
        self.block_of = (starts // block_size).astype(np.int64)
        self.n_blocks = int(self.block_of[-1]) + 1 if n else 0
        # Fence pointer = first key of each block (in-memory index).
        if n:
            first_idx = np.searchsorted(self.block_of,
                                        np.arange(self.n_blocks), side="left")
            self.fence_keys = self.keys[first_idx]
            # Per-block checksum = XOR of member-entry CRC-32Cs (§16.2):
            # order-independent, so verification can recompute any single
            # block without materializing its byte stream.
            self.block_crcs = self._block_crcs_from(
                _entry_crcs(self.keys, self.seqs, self.vlens, self.vals))
        else:
            self.fence_keys = np.zeros(0, dtype=KEY_DTYPE)
            self.block_crcs = np.zeros(0, dtype=np.uint32)
        self.bloom = BloomFilter(self.keys, bits_per_key, hash_fn=hash_fn)
        self.level_hint = -1  # set by the manifest; informational
        self._uniform_vals = None  # lazy: every value full-width, no tombs?

    # ------------------------------------------------------------------ size
    def __len__(self) -> int:
        return int(self.keys.size)

    @property
    def min_key(self) -> int:
        return int(self.keys[0]) if len(self) else 0

    @property
    def max_key(self) -> int:
        return int(self.keys[-1]) if len(self) else 0

    def bit_equal(self, other: "SortedRun") -> bool:
        """Bit-for-bit payload equality: keys/seqs/vlens/vals/bloom bits.

        The single definition of run equality used by every async-vs-sync
        differential oracle (tests and the micro_dbbench inline assert), so
        a future run field is added to the contract in exactly one place.
        """
        return bool(
            np.array_equal(self.keys, other.keys)
            and np.array_equal(self.seqs, other.seqs)
            and np.array_equal(self.vlens, other.vlens)
            and np.array_equal(self.vals, other.vals)
            and np.array_equal(self.bloom.bits, other.bloom.bits))

    def block_bytes(self, block_id: int) -> int:
        """Physical bytes stored in one block (the last block may be short)."""
        if block_id < 0 or block_id >= self.n_blocks:
            return 0
        if block_id == self.n_blocks - 1:
            return self.data_bytes - block_id * self.block_size
        return self.block_size

    # ------------------------------------------------------------- integrity
    def _block_crcs_from(self, entry_crcs: np.ndarray) -> np.ndarray:
        """Fold per-entry CRCs into per-block checksums (XOR-reduce at each
        block's first entry).  A block spanned entirely by a giant
        neighbouring entry has no member entries; its checksum is 0."""
        bounds = np.searchsorted(self.block_of, np.arange(self.n_blocks),
                                 side="left")
        crcs = np.bitwise_xor.reduceat(entry_crcs, bounds)
        # reduceat yields entry_crcs[bounds[i]] for empty segments — fix up
        nxt = np.append(bounds[1:], entry_crcs.size)
        crcs[bounds == nxt] = 0
        return crcs.astype(np.uint32)

    def verify_block(self, block_id: int) -> bool:
        """Recompute one block's checksum from its entries; True iff clean."""
        sel = np.nonzero(self.block_of == block_id)[0]
        if sel.size == 0:
            return int(self.block_crcs[block_id]) == 0
        fresh = _entry_crcs(self.keys[sel], self.seqs[sel],
                            self.vlens[sel], self.vals[sel])
        return int(np.bitwise_xor.reduce(fresh)) == int(self.block_crcs[block_id])

    def verify(self) -> List[int]:
        """Recompute every block checksum; returns the bad block ids
        (empty list == run is clean).  Used by ``scrub()`` and recovery."""
        if len(self) == 0:
            return []
        fresh = self._block_crcs_from(
            _entry_crcs(self.keys, self.seqs, self.vlens, self.vals))
        return np.nonzero(fresh != self.block_crcs)[0].tolist()

    def _charge_block(self, block_id: int, stats: IOStats, cache,
                      paranoid: bool = False, faults=None) -> None:
        """One block touch: through the cache when present, else raw I/O.

        ``faults`` fires the ``block_read`` injection site; ``paranoid``
        re-verifies the block's checksum after the read and raises
        :class:`CorruptionError` on a mismatch (``LSMConfig.paranoid_checks``).
        """
        if faults is not None:
            faults.check("block_read")
        if cache is None:
            stats.blocks_read += 1
        else:
            cache.read_block(self.run_id, int(block_id),
                             self.block_bytes(int(block_id)), stats)
        if paranoid and not self.verify_block(int(block_id)):
            raise CorruptionError(self.run_id, int(block_id))

    # ----------------------------------------------------------------- reads
    def point_get(self, key: int, stats: IOStats,
                  use_bloom: bool = True, cache=None,
                  paranoid: bool = False,
                  faults=None) -> Tuple[bool, Optional[bytes], int]:
        """Returns (found, value_or_None_if_tombstone, seq).

        Cost model: one bloom probe (CPU), then one block read iff the bloom
        says maybe (fence pointers locate the block for free; the read goes
        through ``cache`` when one is attached — hits charge no block I/O).
        """
        k = np.uint64(key)
        if use_bloom and self.bloom.k > 0:
            stats.bloom_probes += 1
            if not bool(self.bloom.may_contain(np.asarray([k]))[0]):
                stats.bloom_negatives += 1
                return False, None, -1
        if len(self) == 0:
            return False, None, -1  # no blocks to read
        i = int(np.searchsorted(self.keys, k))
        # fence pointers give the unique candidate block
        self._charge_block(self.block_of[min(i, len(self) - 1)], stats, cache,
                           paranoid=paranoid, faults=faults)
        if i < len(self) and self.keys[i] == k:
            vlen = int(self.vlens[i])
            if vlen == TOMBSTONE_LEN:
                return True, None, int(self.seqs[i])
            return True, bytes(self.vals[i, :vlen]), int(self.seqs[i])
        stats.false_positives += 1
        return False, None, -1

    def point_get_batch(self, keys: np.ndarray, stats: IOStats,
                        use_bloom: bool = True, probe_fn=None, cache=None,
                        paranoid: bool = False, faults=None
                        ) -> Tuple[np.ndarray, List[Optional[bytes]]]:
        """Vectorized ``point_get`` over a batch of keys.

        Returns ``(found, values)``: found[i] True means key i's newest
        version lives in this run (values[i] is its bytes, or None for a
        tombstone).  One bloom pass + one searchsorted over the whole batch;
        aggregate IOStats accounting is identical to len(keys) scalar
        ``point_get`` calls.  ``probe_fn(bloom, keys) -> bool mask`` optionally
        reroutes the filter probe (e.g. through the device probe); ``cache``
        routes the candidate block reads through the block cache, in batch
        order (so two candidates sharing a block cost one miss + one hit).
        """
        keys = np.ascontiguousarray(keys, dtype=KEY_DTYPE)
        n = keys.size
        found = np.zeros(n, dtype=bool)
        values: List[Optional[bytes]] = [None] * n
        if len(self) == 0:
            return found, values  # no blocks to read
        if use_bloom and self.bloom.k > 0:
            stats.bloom_probes += n
            if probe_fn is not None:
                maybe = np.asarray(probe_fn(self.bloom, keys), dtype=bool)
            else:
                maybe = self.bloom.may_contain(keys)
            stats.bloom_negatives += int(n - np.count_nonzero(maybe))
            cand = np.nonzero(maybe)[0]
        else:
            cand = np.arange(n)
        if cand.size == 0:
            return found, values
        # Fence pointers give each candidate its unique block: 1 read apiece.
        idx = np.searchsorted(self.keys, keys[cand])
        blocks = self.block_of[np.minimum(idx, len(self) - 1)]
        if faults is not None:
            for _ in range(int(cand.size)):  # one injection check per read
                faults.check("block_read")
        if cache is None:
            stats.blocks_read += int(cand.size)
        else:
            cache.read_blocks(self.run_id, blocks.tolist(),
                              self.block_bytes, stats)
        if paranoid:
            for b in np.unique(blocks):
                if not self.verify_block(int(b)):
                    raise CorruptionError(self.run_id, int(b))
        inb = idx < len(self)
        hit = np.zeros(cand.size, dtype=bool)
        hit[inb] = self.keys[idx[inb]] == keys[cand][inb]
        stats.false_positives += int(cand.size - np.count_nonzero(hit))
        for p in np.nonzero(hit)[0]:
            i = int(idx[p])
            j = int(cand[p])
            found[j] = True
            vlen = int(self.vlens[i])
            if vlen != TOMBSTONE_LEN:
                values[j] = bytes(self.vals[i, :vlen])
        return found, values

    def values_at(self, rows: np.ndarray) -> List[Optional[bytes]]:
        """Batched value extraction for the given rows: one row-gather +
        one ``tobytes`` for the whole batch (the same idiom the merging
        iterator uses per refill), ``None`` at tombstone rows.  Used by the
        range-view scan's per-run materialization (DESIGN.md §13)."""
        vmax = self.vals.shape[1] if self.vals.ndim == 2 else 0
        if vmax == 0:
            return [None if l == TOMBSTONE_LEN else b""
                    for l in self.vlens[rows].tolist()]
        if self._uniform_vals is None:
            # runs are immutable: pay the whole-run check once, then every
            # fixed-value_size workload splits at a fixed stride with no
            # per-row length gather at all
            self._uniform_vals = bool((self.vlens == vmax).all())
        if self._uniform_vals:
            flat = self.vals[rows].tobytes()
            return [flat[o:o + vmax] for o in range(0, len(flat), vmax)]
        lens = self.vlens[rows].tolist()
        flat = self.vals[rows].tobytes()
        out: List[Optional[bytes]] = []
        for o, l in enumerate(lens):
            if l == TOMBSTONE_LEN:
                out.append(None)
            else:
                off = o * vmax
                out.append(flat[off:off + l])
        return out

    def seek_idx(self, key: int) -> int:
        return int(np.searchsorted(self.keys, np.uint64(key), side="left"))

    def slice_from(self, start_idx: int, count: int):
        """Entries [start_idx, start_idx+count) as (keys, seqs, vlens, vals)."""
        e = min(start_idx + count, len(self))
        return (self.keys[start_idx:e], self.seqs[start_idx:e],
                self.vlens[start_idx:e], self.vals[start_idx:e])

    def blocks_spanned(self, start_idx: int, end_idx: int) -> int:
        """Number of blocks touched to read entries [start_idx, end_idx)."""
        if end_idx <= start_idx or start_idx >= len(self):
            return 0
        end_idx = min(end_idx, len(self))
        return int(self.block_of[end_idx - 1] - self.block_of[start_idx]) + 1


def levels_bit_equal(levels_a: Sequence[Sequence[SortedRun]],
                     levels_b: Sequence[Sequence[SortedRun]]) -> bool:
    """Bit-for-bit tree equality: same level count, same runs per level,
    every run pair :meth:`SortedRun.bit_equal`.

    The one definition of the async-vs-sync differential oracle's tree
    comparison, shared by the property tests and the micro_dbbench inline
    assert so the contract cannot drift between them.
    """
    if len(levels_a) != len(levels_b):
        return False
    for la, lb in zip(levels_a, levels_b):
        if len(la) != len(lb):
            return False
        for ra, rb in zip(la, lb):
            if not ra.bit_equal(rb):
                return False
    return True


# --------------------------------------------------------------------- build
def build_run(keys: np.ndarray, seqs: np.ndarray, vlens: np.ndarray,
              vals: np.ndarray, bits_per_key: float = 0.0,
              assume_unique_sorted: bool = False,
              drop_tombstones: bool = False,
              block_size: int = BLOCK_SIZE, key_bytes: int = KEY_BYTES,
              hash_fn=None) -> SortedRun:
    """Sort by key, deduplicate keeping the newest seq, optionally GC deletes.

    ``block_size``/``key_bytes`` shape the constructed run's block layout
    (threaded from ``LSMConfig`` by the engine); ``hash_fn`` optionally
    reroutes the bloom build's hash pass (e.g. through the device hash
    family — see ``core.bloom.BloomFilter``).
    """
    keys = np.asarray(keys, dtype=KEY_DTYPE)
    seqs = np.asarray(seqs, dtype=SEQ_DTYPE)
    vlens = np.asarray(vlens, dtype=np.int32)
    vals = np.asarray(vals, dtype=np.uint8)
    if vals.ndim == 1:
        vals = vals.reshape(len(keys), -1) if len(keys) else vals.reshape(0, 0)
    if not assume_unique_sorted and len(keys):
        # Stable sort by (key, -seq): newest version of each key comes first.
        order = np.lexsort((np.iinfo(np.uint64).max - seqs, keys))
        keys, seqs, vlens, vals = keys[order], seqs[order], vlens[order], vals[order]
        keep = np.ones(len(keys), dtype=bool)
        keep[1:] = keys[1:] != keys[:-1]
        keys, seqs, vlens, vals = keys[keep], seqs[keep], vlens[keep], vals[keep]
    if drop_tombstones and len(keys):
        live = vlens != TOMBSTONE_LEN
        keys, seqs, vlens, vals = keys[live], seqs[live], vlens[live], vals[live]
    return SortedRun(keys, seqs, vlens, vals, bits_per_key=bits_per_key,
                     block_size=block_size, key_bytes=key_bytes,
                     hash_fn=hash_fn)


def _account_merge_output(out: SortedRun, stats: IOStats) -> SortedRun:
    """Write-side cost model, shared by every merge path (paper §2.2)."""
    stats.blocks_written += out.n_blocks
    stats.entries_compacted += len(out)
    stats.bytes_compacted += out.data_bytes
    stats.compactions += 1
    return out


# A pair merge gallops (searchsorted) only when one side is much smaller;
# balanced pairs fall back to one stable (radix) argsort over the concat,
# which is faster than per-element binary search on balanced inputs.
_GALLOP_RATIO = 8
# Below this many total input entries the fully vectorized path's fixed
# numpy-call overhead exceeds its per-entry win over concat+lexsort.
_VECTOR_MIN_ENTRIES = 8192


def _merge_pair(a, b, seqs_cat: np.ndarray, pair_merge=None):
    """Merge two (keys, gid) nodes of the ladder into one.

    Inputs have strictly increasing keys; the output does too (the newer
    sequence number wins each duplicate).  Nodes carry only the key column
    and a *global index* into the concatenated inputs — sequence numbers are
    gathered from ``seqs_cat`` only at the (few) duplicate positions, so
    each ladder round moves two columns instead of four.

    Backend selection (all three produce identical output):
      * skewed pair — gallop: one ``np.searchsorted`` of the smaller side
        into the larger (each element's output slot is its own index plus
        its rank in the other run), then two scatters; O(small·log(large))
        lookups instead of sorting ``large`` again;
      * balanced pair — one stable argsort of the concatenated keys
        (radix for integer keys, so no comparison sort either);
      * ``pair_merge(keys_a, keys_b) -> (merged_keys, src_idx)`` reroutes
        the interleave through an accelerator
        (``kernels.ops.merge_runs_tiled``: merge-path partition + bitonic
        network), ``src_idx`` uint32 with bit 31 flagging ``b`` entries.

    Entries with equal key AND equal seq resolve arbitrarily between the
    backends (the engine's sequence numbers are unique).
    """
    ka, ga = a
    kb, gb = b
    na, nb = ka.size, kb.size
    if na == 0:
        return b
    if nb == 0:
        return a
    n = na + nb
    if pair_merge is not None:
        keys, sidx = pair_merge(ka, kb)
        keys = np.asarray(keys)
        sidx = np.asarray(sidx)
        from_b = (sidx & np.uint32(1 << 31)) != 0
        r = (sidx & np.uint32(0x7FFFFFFF)).astype(np.int64)
        gid = np.empty(n, dtype=np.int64)
        in_a = ~from_b
        gid[in_a] = ga[r[in_a]]
        gid[from_b] = gb[r[from_b]]
    elif min(na, nb) * _GALLOP_RATIO <= n:
        if na <= nb:
            small_k, small_g, big_k, big_g, side = ka, ga, kb, gb, "left"
        else:
            small_k, small_g, big_k, big_g, side = kb, gb, ka, ga, "right"
        # 'left'/'right' keep equal keys a-first, matching the argsort path
        pos = np.arange(small_k.size, dtype=np.int64) \
            + np.searchsorted(big_k, small_k, side)
        in_big = np.ones(n, dtype=bool)
        in_big[pos] = False
        keys = np.empty(n, dtype=ka.dtype)
        keys[pos] = small_k
        keys[in_big] = big_k     # boolean fill preserves sorted order
        gid = np.empty(n, dtype=np.int64)
        gid[pos] = small_g
        gid[in_big] = big_g
    else:
        keys = np.concatenate([ka, kb])
        order = np.argsort(keys, kind="stable")  # radix; a-first on ties
        keys = keys[order]
        gid = np.concatenate([ga, gb])[order]
    # Dedup: a key occurs at most twice and duplicates are adjacent; the
    # newer seq wins (equal-seq ties keep the first occurrence, matching
    # the scalar path's stable lexsort).
    dup = np.nonzero(keys[1:] == keys[:-1])[0]
    if dup.size == 0:
        return keys, gid
    keep = np.ones(n, dtype=bool)
    second_newer = seqs_cat[gid[dup + 1]] > seqs_cat[gid[dup]]
    keep[np.where(second_newer, dup, dup + 1)] = False
    return keys[keep], gid[keep]


def merge_runs(runs: Sequence[SortedRun], bits_per_key: float,
               stats: IOStats, drop_tombstones: bool = False,
               block_size: int = BLOCK_SIZE, key_bytes: int = KEY_BYTES,
               pair_merge=None, bloom_hash=None) -> SortedRun:
    """K-way compaction merge exploiting input sortedness (DESIGN.md §10).

    A balanced tournament of pairwise merges over (key, global-index)
    columns: each round interleaves sorted pairs with ``np.searchsorted``
    (or the Pallas merge-path lane via ``pair_merge``) and drops shadowed
    duplicates immediately, so seqs/vlens/values are each moved exactly once
    — one gather per column at the end, against the scalar oracle's
    pad + concat + full lexsort + permute + mask of every column.
    Bit-for-bit identical output and IOStats to the retained
    ``merge_runs_scalar`` oracle (differentially tested).

    Cost model: every input block is read, every output block written; the
    entry/byte counters feed write-amplification (paper §2.2).
    """
    if not runs:
        return build_run(np.zeros(0, KEY_DTYPE), np.zeros(0, SEQ_DTYPE),
                         np.zeros(0, np.int32), np.zeros((0, 0), np.uint8),
                         bits_per_key, block_size=block_size,
                         key_bytes=key_bytes, hash_fn=bloom_hash)
    if pair_merge is None and sum(len(r) for r in runs) < _VECTOR_MIN_ENTRIES:
        # tiny merges: the concat+lexsort core has the smaller constant
        # factor (identical output either way); the Pallas lane is never
        # shortcut so the kernel route stays exercised end to end
        return merge_runs_scalar(runs, bits_per_key, stats,
                                 drop_tombstones=drop_tombstones,
                                 block_size=block_size, key_bytes=key_bytes,
                                 bloom_hash=bloom_hash)
    for r in runs:
        stats.blocks_read += r.n_blocks
    lens = [len(r) for r in runs]
    offs = np.zeros(len(runs) + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    seqs_cat = runs[0].seqs if len(runs) == 1 else \
        np.concatenate([r.seqs for r in runs])
    # Huffman-ordered tournament: always merge the two smallest nodes, so a
    # dominant run (the usual dst level) joins only the final merges and
    # total element moves stay near the entropy bound.
    heap = [(len(r), i, (r.keys, np.arange(offs[i], offs[i + 1],
                                           dtype=np.int64)))
            for i, r in enumerate(runs)]
    heapq.heapify(heap)
    tick = len(runs)
    while len(heap) > 1:
        _, ia, a = heapq.heappop(heap)
        _, ib, b = heapq.heappop(heap)
        if ib < ia:          # keep earlier-run-first orientation for ties
            a, b = b, a
        merged = _merge_pair(a, b, seqs_cat, pair_merge)
        heapq.heappush(heap, (merged[0].size, tick, merged))
        tick += 1
    keys, gid = heap[0][2]
    vlens_cat = runs[0].vlens if len(runs) == 1 else \
        np.concatenate([r.vlens for r in runs])
    vlens = vlens_cat[gid]
    if drop_tombstones and keys.size:
        live = vlens != TOMBSTONE_LEN
        keys, vlens, gid = keys[live], vlens[live], gid[live]
    seqs = seqs_cat[gid]
    # Winner values move in two bulk passes (concat + one row gather),
    # against the scalar oracle's concat + full permute + keep-mask three;
    # only sources narrower than vmax need padding first.
    vmax = max((r.vals.shape[1] if r.vals.ndim == 2 else 0) for r in runs)
    if vmax == 0:
        vals = np.zeros((keys.size, 0), dtype=np.uint8)
    else:
        mats = []
        for r in runs:
            v = r.vals if r.vals.ndim == 2 else r.vals.reshape(len(r), 0)
            if v.shape[1] < vmax:
                v = np.pad(v, ((0, 0), (0, vmax - v.shape[1])))
            mats.append(v)
        vals_cat = mats[0] if len(mats) == 1 else np.concatenate(mats)
        vals = vals_cat[gid]
    out = SortedRun(keys, seqs, vlens, vals, bits_per_key=bits_per_key,
                    block_size=block_size, key_bytes=key_bytes,
                    hash_fn=bloom_hash)
    return _account_merge_output(out, stats)


def merge_runs_scalar(runs: Sequence[SortedRun], bits_per_key: float,
                      stats: IOStats, drop_tombstones: bool = False,
                      block_size: int = BLOCK_SIZE,
                      key_bytes: int = KEY_BYTES,
                      bloom_hash=None) -> SortedRun:
    """Reference compaction merge (concat + re-lexsort from scratch).

    The pre-vectorization implementation, kept as the differential-test
    oracle and the benchmarks' scalar baseline: it ignores that its inputs
    are already sorted.  Identical output and IOStats to ``merge_runs``.
    """
    if not runs:
        return build_run(np.zeros(0, KEY_DTYPE), np.zeros(0, SEQ_DTYPE),
                         np.zeros(0, np.int32), np.zeros((0, 0), np.uint8),
                         bits_per_key, block_size=block_size,
                         key_bytes=key_bytes, hash_fn=bloom_hash)
    vmax = max((r.vals.shape[1] if r.vals.ndim == 2 else 0) for r in runs)
    ks, ss, ls, vs = [], [], [], []
    for r in runs:
        stats.blocks_read += r.n_blocks
        ks.append(r.keys)
        ss.append(r.seqs)
        ls.append(r.vlens)
        v = r.vals if r.vals.ndim == 2 else r.vals.reshape(len(r), 0)
        if v.shape[1] < vmax:
            v = np.pad(v, ((0, 0), (0, vmax - v.shape[1])))
        vs.append(v)
    out = build_run(np.concatenate(ks), np.concatenate(ss),
                    np.concatenate(ls),
                    np.concatenate(vs) if vmax else np.zeros((sum(map(len, runs)), 0), np.uint8),
                    bits_per_key=bits_per_key, drop_tombstones=drop_tombstones,
                    block_size=block_size, key_bytes=key_bytes,
                    hash_fn=bloom_hash)
    return _account_merge_output(out, stats)
