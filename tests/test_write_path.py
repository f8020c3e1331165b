"""Differential tests for the vectorized write & compaction subsystem
(DESIGN.md §10).

Randomized workloads drive the two new write paths against their scalar
oracles, asserting they are exact drop-ins:

  * ``LSMStore.write_batch``/``put_batch`` == the scalar put/delete loop —
    values, WAL bytes, tree structure, and IOStats field by field
    (identical flush boundaries), plus torn-tail crash recovery of a
    partially synced batch;
  * the vectorized ``merge_runs`` == the retained ``merge_runs_scalar``
    oracle — bit-for-bit keys/seqs/vlens/vals/bloom bits and identical
    compaction counters, with and without tombstone GC;
  * the Pallas merge-path lane (``use_pallas_merge``) and the Pallas bloom
    build route (``use_pallas_bloom``) produce bit-identical runs;
  * ``BlockCache.read_blocks``/``read_block_span`` == a per-block
    ``read_block`` loop on a twin cache;
  * ``LSMConfig.block_size``/``key_bytes`` reach every constructed run
    (flush and compaction), and ``total_live_entries`` /
    ``space_amplification`` match a brute-force oracle.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import IOStats, LSMConfig, LSMStore, build_run
from repro.core.run import merge_runs, merge_runs_scalar
from repro.core.types import KEY_BYTES, TOMBSTONE_LEN


def small_cfg(**kw):
    base = dict(policy="garnering", T=2.0, c=0.8, memtable_bytes=1 << 12,
                base_level_bytes=1 << 14, bits_per_key=8,
                bloom_allocation="monkey")
    base.update(kw)
    return LSMConfig(**base)


def gen_ops(seed: int, n_ops: int, key_space: int = 300, del_frac: float = 0.2):
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_ops):
        k = int(rng.integers(0, key_space))
        if rng.random() < del_frac:
            ops.append((k, None))
        else:
            ops.append((k, bytes([65 + i % 26]) * int(rng.integers(0, 120))))
    return ops


def assert_same_tree(db_a: LSMStore, db_b: LSMStore):
    assert len(db_a._levels) == len(db_b._levels)
    for la, lb in zip(db_a._levels, db_b._levels):
        assert len(la) == len(lb)
        for ra, rb in zip(la, lb):
            np.testing.assert_array_equal(ra.keys, rb.keys)
            np.testing.assert_array_equal(ra.seqs, rb.seqs)
            np.testing.assert_array_equal(ra.vlens, rb.vlens)
            np.testing.assert_array_equal(ra.vals, rb.vals)
            np.testing.assert_array_equal(ra.bloom.bits, rb.bloom.bits)


def assert_same_stats(a: IOStats, b: IOStats):
    """Every counter equal; *_ns fields are wall-clock timers."""
    for f in dataclasses.fields(IOStats):
        if not f.name.endswith("_ns"):
            assert getattr(a, f.name) == getattr(b, f.name), f.name


# ----------------------------------------------------------- batched ingest
@given(st.integers(0, 10_000), st.integers(1, 600))
@settings(max_examples=12, deadline=None)
def test_write_batch_matches_scalar_loop(seed, wave):
    """Property: write_batch in arbitrary wave sizes is bit-for-bit the
    scalar loop — WAL bytes, IOStats (incl. write-amp counters), the run
    arrays of every level, and every readable value."""
    ops = gen_ops(seed, 1200)
    db_s, db_b = LSMStore(small_cfg()), LSMStore(small_cfg())
    for k, v in ops:
        (db_s.delete(k) if v is None else db_s.put(k, v))
    for i in range(0, len(ops), wave):
        db_b.write_batch(ops[i:i + wave])
    assert bytes(db_s.wal._buf) == bytes(db_b.wal._buf)
    assert_same_stats(db_s.stats, db_b.stats)
    assert db_s.stats.write_amplification() == \
        db_b.stats.write_amplification()
    assert_same_tree(db_s, db_b)
    for k in range(300):
        assert db_s.get(k) == db_b.get(k), k


def test_put_batch_values_and_duplicates():
    db = LSMStore(small_cfg(memtable_bytes=1 << 20))
    db.put_batch([1, 2, 3], [b"a", b"b", b"c"])
    db.put_batch([4, 5], b"bcast")           # broadcast single value
    db.write_batch([(2, None), (6, b"x"), (6, b"y"), (7, None)])
    assert db.multi_get([1, 2, 3, 4, 5, 6, 7, 8]) == \
        [b"a", None, b"c", b"bcast", b"bcast", b"y", None, None]
    db.write_batch([])                        # empty batch is a no-op
    assert db.total_live_entries() == 5


def test_put_batch_fsync_every_write_durability():
    """With wal_fsync_every_write the batch group-commits per chunk: a
    crash right after put_batch returns loses nothing."""
    db = LSMStore(small_cfg(wal_fsync_every_write=True,
                            memtable_bytes=1 << 20))
    db.put_batch(list(range(40)), b"durable")
    db.crash()
    db.recover()
    for k in range(40):
        assert db.get(k) == b"durable", k


def test_write_batch_fsync_coalescing_counts():
    """With wal_fsync_every_write=True a batch fsyncs once per *chunk*
    (group commit), not once per record — asserted by counting actual WAL
    fsync calls, not just the documented contract.

    A single-chunk batch (big memtable) costs exactly one WAL fsync for
    hundreds of records; the scalar twin pays one per record.  A multi-chunk
    batch (small memtable) costs one per chunk plus the flush-path fsyncs.
    """
    ops = [(k, b"x" * 10) for k in range(500)]
    # ---- single chunk: 500 records, exactly 1 WAL fsync ----
    db = LSMStore(small_cfg(wal_fsync_every_write=True,
                            memtable_bytes=1 << 20))
    fsyncs = []
    orig_fsync = db.wal.fsync
    db.wal.fsync = lambda stats: (fsyncs.append(1), orig_fsync(stats))[1]
    db.write_batch(ops)
    assert len(fsyncs) == 1
    # scalar twin: one fsync per record
    db_s = LSMStore(small_cfg(wal_fsync_every_write=True,
                              memtable_bytes=1 << 20))
    s0 = db_s.stats.snapshot()
    for k, v in ops:
        db_s.put(k, v)
    assert db_s.stats.delta(s0).wal_fsyncs == 500
    # ---- multi chunk: one fsync per chunk + one per flush, nothing more ----
    db_m = LSMStore(small_cfg(wal_fsync_every_write=True))  # 4 KiB memtable
    chunks, fsyncs_m, flushes = [], [], []
    orig_append = db_m.wal.append_batch_cols
    orig_fsync_m = db_m.wal.fsync
    orig_flush = db_m.flush
    db_m.wal.append_batch_cols = \
        lambda *a, **k: (chunks.append(1), orig_append(*a, **k))[1]
    db_m.wal.fsync = lambda stats: (fsyncs_m.append(1), orig_fsync_m(stats))[1]
    db_m.flush = lambda: (flushes.append(1), orig_flush())[1]
    db_m.write_batch(ops)
    assert len(chunks) > 1 and len(flushes) >= 1
    assert len(chunks) < len(ops), "chunking degenerated to per-record"
    assert len(fsyncs_m) == len(chunks) + len(flushes)


def test_torn_batch_tail_recovery():
    """A partially synced batch recovers exactly the records that fit the
    fsync watermark; the torn record and everything after are lost."""
    from repro.core.memtable import FRAME_OVERHEAD

    db = LSMStore(small_cfg(memtable_bytes=1 << 20))
    db.put_batch(list(range(50)), b"v" * 10)
    rec = FRAME_OVERHEAD + 10        # frame (crc+header) + payload per record
    db.wal._synced_upto = 7 * rec + 13   # cut mid-record 7
    db.crash()
    db.recover()
    for k in range(50):
        assert db.get(k) == (b"v" * 10 if k < 7 else None), k
    # same cut inside a *ragged* batch (deletes interleaved)
    db2 = LSMStore(small_cfg(memtable_bytes=1 << 20))
    db2.write_batch([(k, None) if k % 3 == 0 else (k, bytes(k))
                     for k in range(30)])
    db2.wal.fsync(db2.stats)
    db2.wal._synced_upto -= 5        # tear the last record
    db2.crash()
    db2.recover()
    for k in range(29):
        expect = None if k % 3 == 0 else bytes(k)
        assert db2.get(k) == expect, k
    assert db2.get(29) is None       # the torn record never replays


def test_wal_append_batch_bytes_match_scalar_appends():
    """The row-form WAL batch append (and the engine's column fast path
    behind it) writes byte-identical records to a scalar append loop."""
    from repro.core.memtable import WriteAheadLog

    items = [(5, b"abc"), (9, None), (2 ** 63, b""), (7, b"x" * 120),
             (1, None), (3, b"yz")]
    w_scalar, w_batch = WriteAheadLog(), WriteAheadLog()
    s1, s2 = IOStats(), IOStats()
    for i, (k, v) in enumerate(items):
        w_scalar.append(1 if v is None else 0, k, 10 + i, v or b"", s1)
    w_batch.append_batch(items, 10, s2)
    assert bytes(w_scalar._buf) == bytes(w_batch._buf)
    assert s1.wal_appends == s2.wal_appends == len(items)
    assert list(w_scalar.records()) == list(w_batch.records())
    # uniform-length batch exercises the 2-D interleave fast path
    uni = [(k, b"u" * 16) for k in range(40)]
    w_scalar2, w_batch2 = WriteAheadLog(), WriteAheadLog()
    for i, (k, v) in enumerate(uni):
        w_scalar2.append(0, k, 1 + i, v, s1)
    w_batch2.append_batch(uni, 1, s2)
    assert bytes(w_scalar2._buf) == bytes(w_batch2._buf)


def test_wal_outlier_length_batch_spans_stay_bounded_and_bit_exact():
    """A batch mixing many small records with a few huge values must not
    build one n*max padded CRC matrix: the pass splits into bounded spans
    (each under the scratch budget) and stays byte-identical to scalar
    appends — including replay through the same spanned verification."""
    from repro.core.memtable import (WriteAheadLog, _CRC_PAD_BUDGET, _HDR,
                                     _pad_spans)

    rng = np.random.default_rng(11)
    items = []
    for i in range(3000):
        if i % 500 == 250:               # scattered 4KB outliers
            items.append((i, bytes(rng.integers(0, 256, 4096, np.uint8))))
        elif i % 9 == 0:
            items.append((i, None))
        else:
            items.append((i, bytes(rng.integers(0, 256,
                                                int(rng.integers(0, 32)),
                                                np.uint8))))
    w_scalar, w_batch = WriteAheadLog(), WriteAheadLog()
    s = IOStats()
    for i, (k, v) in enumerate(items):
        w_scalar.append(1 if v is None else 0, k, 7 + i, v or b"", s)
    w_batch.append_batch(items, 7, s)
    assert bytes(w_scalar._buf) == bytes(w_batch._buf)
    assert list(w_scalar.records()) == list(w_batch.records())
    # the span generator's bound: rows * padded-width <= budget, except a
    # single row wider than the whole budget (the record itself, not padding)
    vlens = np.array([len(v) if v is not None else 0 for _, v in items],
                     np.int64)
    spans = list(_pad_spans(vlens, _HDR.size))
    assert len(spans) > 1                 # the outliers force a split
    assert spans[0][0] == 0 and spans[-1][1] == len(items)
    for (i, j), (i2, _) in zip(spans, spans[1:] + [(len(items), None)]):
        assert j == i2                    # contiguous, gap-free cover
        w = _HDR.size + int(vlens[i:j].max())
        assert (j - i) * w <= _CRC_PAD_BUDGET or j - i == 1


# ------------------------------------------------------- vectorized merges
def make_run(seed: int, n: int, key_space: int = 3000, vmax: int = 24,
             tomb: float = 0.15, seq0: int = 0):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, key_space, n).astype(np.uint64))
    n = len(keys)
    seqs = seq0 + rng.permutation(n).astype(np.uint64)
    vlens = rng.integers(0, vmax + 1, n).astype(np.int32)
    vlens[rng.random(n) < tomb] = TOMBSTONE_LEN
    vals = np.zeros((n, vmax), dtype=np.uint8)
    for i in range(n):
        if vlens[i] > 0:
            vals[i, :vlens[i]] = rng.integers(1, 255, vlens[i])
    return build_run(keys, seqs, vlens, vals, assume_unique_sorted=True)


def assert_same_run(a, b):
    np.testing.assert_array_equal(a.keys, b.keys)
    np.testing.assert_array_equal(a.seqs, b.seqs)
    np.testing.assert_array_equal(a.vlens, b.vlens)
    np.testing.assert_array_equal(a.vals, b.vals)
    np.testing.assert_array_equal(a.bloom.bits, b.bloom.bits)
    assert a.n_blocks == b.n_blocks and a.data_bytes == b.data_bytes


@given(st.integers(0, 10_000), st.integers(1, 6), st.booleans())
@settings(max_examples=12, deadline=None)
def test_merge_matches_scalar_oracle(seed, n_runs, drop):
    """Property: the vectorized k-way merge is bit-for-bit the concat +
    lexsort oracle — keys/seqs/vlens/vals/bloom AND the write-amp counter
    algebra (blocks read/written, entries/bytes compacted)."""
    rng = np.random.default_rng(seed)
    # disjoint seq ranges per run, as engine flush/compaction produces
    runs = [make_run(seed * 13 + i, int(rng.integers(1, 900)),
                     seq0=i * 1_000_000) for i in range(n_runs)]
    s_ref, s_vec = IOStats(), IOStats()
    ref = merge_runs_scalar(runs, 6.0, s_ref, drop_tombstones=drop)
    out = merge_runs(runs, 6.0, s_vec, drop_tombstones=drop)
    assert_same_run(ref, out)
    assert_same_stats(s_ref, s_vec)


def test_merge_large_hits_vector_path():
    """Above the adaptive threshold the ladder (not the scalar fallback)
    runs; output must still be bit-for-bit."""
    runs = [make_run(i + 1, 9000, key_space=60_000, seq0=i * 1_000_000)
            for i in range(3)]
    assert sum(len(r) for r in runs) > 8192
    s_ref, s_vec = IOStats(), IOStats()
    ref = merge_runs_scalar(runs, 4.0, s_ref)
    out = merge_runs(runs, 4.0, s_vec)
    assert_same_run(ref, out)
    assert_same_stats(s_ref, s_vec)


def test_merge_tombstone_gc_at_deepest_level():
    """Engine-level: a full merge into the deepest level drops tombstones
    on the batched write path exactly as on the scalar one."""
    from repro.core import CompactionTask
    db = LSMStore(small_cfg())
    db.put_batch(list(range(400)), b"x" * 30)
    db.delete_batch(list(range(400)))
    db.flush()
    assert db.total_live_entries() == 0
    deepest = db._deepest_nonempty()
    for i in range(1, deepest):
        if db._levels[i]:
            db._apply(CompactionTask(i, deepest, True, "test-force"))
    if db._levels[0]:
        db._apply(CompactionTask(0, deepest, True, "test-force"))
    assert sum(len(r) for lvl in db._levels[1:] for r in lvl) == 0
    assert db.get(5) is None


def test_pallas_merge_lane_bit_for_bit():
    """use_pallas_merge routes compaction through the bitonic merge-path
    kernel (interpret mode) and must be a bit-for-bit drop-in."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.kernels.ops import merge_runs_tiled

    runs = [make_run(i + 1, 700, key_space=4000, seq0=i * 1_000_000)
            for i in range(3)]
    s_ref, s_vec = IOStats(), IOStats()
    ref = merge_runs_scalar(runs, 5.0, s_ref)
    out = merge_runs(runs, 5.0, s_vec, pair_merge=merge_runs_tiled)
    assert_same_run(ref, out)
    assert_same_stats(s_ref, s_vec)


def test_pallas_merge_handles_max_u64_key():
    """Regression: a real key equal to the uint64 maximum must survive the
    kernel's tile padding (pads tie-break behind real entries by payload)."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.kernels.ops import merge_runs_tiled

    top = np.iinfo(np.uint64).max
    ka = np.array([1, 5, top], dtype=np.uint64)
    kb = np.array([2, 5, 9], dtype=np.uint64)
    mk, mp = merge_runs_tiled(ka, kb, tile=64)
    np.testing.assert_array_equal(mk, np.sort(np.concatenate([ka, kb])))
    src_a = (mp >> 31) == 0
    np.testing.assert_array_equal(mk[src_a], ka[mp[src_a] & 0x7FFFFFFF])
    np.testing.assert_array_equal(mk[~src_a], kb[mp[~src_a] & 0x7FFFFFFF])
    # end to end: max-key entries merge bit-for-bit through the ladder
    ra = build_run(ka, np.array([1, 2, 3], np.uint64),
                   np.array([3, 3, 3], np.int32),
                   np.tile(np.array([7, 8, 9], np.uint8), (3, 1)))
    rb = build_run(kb, np.array([11, 12, 13], np.uint64),
                   np.array([3, 3, 3], np.int32),
                   np.tile(np.array([4, 5, 6], np.uint8), (3, 1)))
    s_ref, s_vec = IOStats(), IOStats()
    ref = merge_runs_scalar([ra, rb], 0.0, s_ref)
    out = merge_runs([ra, rb], 0.0, s_vec, pair_merge=merge_runs_tiled)
    assert_same_run(ref, out)


def test_pallas_merge_engine_route_matches_numpy():
    jax = pytest.importorskip("jax")  # noqa: F841
    ops = gen_ops(21, 900, key_space=200)
    db_n = LSMStore(small_cfg())
    db_p = LSMStore(small_cfg(use_pallas_merge=True))
    db_n.write_batch(ops)
    db_p.write_batch(ops)
    db_n.flush()
    db_p.flush()
    assert_same_tree(db_n, db_p)
    assert_same_stats(db_n.stats, db_p.stats)
    for k in range(200):
        assert db_n.get(k) == db_p.get(k), k


def test_pallas_bloom_build_route_matches_numpy():
    """use_pallas_bloom also reroutes the filter *build* hash pass; the
    constructed bitsets must be identical to the numpy family."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.bloom import BloomFilter
    from repro.kernels.ops import bloom_build_hashes

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 2 ** 63, 1500, dtype=np.uint64)
    np.testing.assert_array_equal(
        BloomFilter(keys, 10).bits,
        BloomFilter(keys, 10, hash_fn=bloom_build_hashes).bits)
    # end to end through flush + compaction
    ops = gen_ops(33, 800, key_space=150)
    db_n = LSMStore(small_cfg())
    db_p = LSMStore(small_cfg(use_pallas_bloom=True))
    db_n.write_batch(ops)
    db_p.write_batch(ops)
    db_n.flush()
    db_p.flush()
    assert_same_tree(db_n, db_p)


# ------------------------------------------------ block-size threading bug
def test_config_block_size_and_key_bytes_reach_runs():
    """Regression: build_run/merge_runs/Memtable.to_run used to ignore
    LSMConfig.block_size/key_bytes and always built module-default runs."""
    cfg = small_cfg(block_size=512, key_bytes=8, bits_per_key=0)
    db = LSMStore(cfg)
    db.put_batch(list(range(2000)), b"v" * 40)
    db.flush()
    seen = 0
    for lvl in db._levels:
        for run in lvl:
            seen += 1
            assert run.block_size == 512
            expect_bytes = int(np.sum(8 + np.maximum(run.vlens, 0)))
            assert run.data_bytes == expect_bytes
            assert run.n_blocks == -(-expect_bytes // 512)
    assert seen >= 1
    assert db.stats.compactions > 0     # merge outputs were checked too
    assert db.stats.blocks_written > 0
    # same tree built with defaults packs far fewer, larger blocks
    db_def = LSMStore(small_cfg(bits_per_key=0))
    db_def.put_batch(list(range(2000)), b"v" * 40)
    db_def.flush()
    assert db.stats.blocks_written > db_def.stats.blocks_written


# ------------------------------------------- live-entry / space-amp algebra
@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_total_live_entries_and_space_amp_match_oracle(seed):
    ops = gen_ops(seed, 800, key_space=200)
    db = LSMStore(small_cfg())
    oracle = {}
    for k, v in ops:
        if v is None:
            db.delete(k)
            oracle[k] = None
        else:
            db.put(k, v)
            oracle[k] = v
    live = {k: v for k, v in oracle.items() if v is not None}
    assert db.total_live_entries() == len(live)
    phys = sum(r.data_bytes for lvl in db._levels for r in lvl) \
        + db.memtable.size_bytes
    logical = sum(KEY_BYTES + len(v) for v in live.values())
    if logical:
        assert db.space_amplification() == pytest.approx(phys / logical)
    else:
        assert db.space_amplification() == 1.0


def test_space_amp_shrinks_after_full_compaction():
    from repro.core import CompactionTask
    db = LSMStore(small_cfg(bits_per_key=0, memtable_bytes=1 << 15))
    for rep in range(3):                  # stack shadowed versions in L0
        db.put_batch(list(range(300)), bytes([rep + 1]) * 40)
        db.flush()                        # 3 L0 runs, below the L0 trigger
    amp_before = db.space_amplification()
    assert amp_before > 1.2               # duplicates inflate physical bytes
    deepest = db._deepest_nonempty()
    for i in range(1, deepest):
        if db._levels[i]:
            db._apply(CompactionTask(i, deepest, True, "test-force"))
    if db._levels[0]:
        db._apply(CompactionTask(0, deepest, True, "test-force"))
    amp_after = db.space_amplification()
    assert amp_after < amp_before
    assert amp_after == pytest.approx(1.0)   # one run, all live


# ---------------------------------------------------- cache span charging
def test_read_blocks_and_span_match_scalar_read_block():
    """The batched cache lanes are charge-for-charge identical to a
    per-block read_block loop on a twin cache."""
    from repro.core.cache import BlockCache

    rng = np.random.default_rng(3)
    for policy in ("lru", "clock"):
        a = BlockCache(8 * 512, policy)
        b = BlockCache(8 * 512, policy)
        sa, sb = IOStats(), IOStats()
        for _ in range(40):
            rid = int(rng.integers(0, 3))
            ids = rng.integers(0, 24, int(rng.integers(1, 9))).tolist()
            if rng.random() < 0.5:
                lo, hi = min(ids), max(ids)
                a.read_block_span(rid, lo, hi, lambda bid: 512, sa)
                for bid in range(lo, hi + 1):
                    b.read_block(rid, bid, 512, sb)
            else:
                a.read_blocks(rid, ids, lambda bid: 512, sa)
                for bid in ids:
                    b.read_block(rid, bid, 512, sb)
        assert (a.hits, a.misses, a.evictions) == (b.hits, b.misses,
                                                   b.evictions)
        assert list(a._entries) == list(b._entries)   # same eviction order
        assert_same_stats(sa, sb)


def test_batched_reads_cached_match_scalar_accounting():
    """End to end: with a cache attached, multi_get/scan accounting equals
    the scalar paths' on an identically built twin store."""
    ops = gen_ops(11, 1500, key_space=400)
    db_a = LSMStore(small_cfg(cache_bytes=64 << 10, pin_l0_bytes=8 << 10))
    db_b = LSMStore(small_cfg(cache_bytes=64 << 10, pin_l0_bytes=8 << 10))
    db_a.write_batch(ops)
    for k, v in ops:
        (db_b.delete(k) if v is None else db_b.put(k, v))
    queries = list(np.random.default_rng(5).integers(0, 500, 300))
    s_a = db_a.stats.snapshot()
    batched = db_a.multi_get(queries)
    scans_a = [db_a.scan(int(k), 20) for k in queries[:30]]
    d_a = db_a.stats.delta(s_a)
    s_b = db_b.stats.snapshot()
    scalar = [db_b.get(int(k)) for k in queries]
    scans_b = [db_b.scan(int(k), 20) for k in queries[:30]]
    d_b = db_b.stats.delta(s_b)
    assert batched == scalar and scans_a == scans_b
    assert_same_stats(d_a, d_b)
