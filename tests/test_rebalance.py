"""Dynamic shard rebalancing (DESIGN.md §15): differential + crash safety.

The single synchronous store stays the retained oracle *through* splitter
migration: for any op sequence, a rebalancing ``ShardedLSMStore`` must
return byte-identical reads before, during, and after any number of
splits/merges/cross-shard run migrations, because the migration protocol
(quiesce -> import -> routing commit -> source strip) never makes an
out-of-routing byte reader-visible.  On top:

  * the automatic trigger fires under skew (write-boundary and quiesce
    paths), never under uniform load, and converges — the histogram-
    weighted derivation cuts a concentrated hot range in one step;
  * explicit ``rebalance_to`` splits and merges land exactly and the
    shared-cache budgets follow the load (hot shard > cold shard, and a
    merge-back restores them; the integer split always sums to the
    configured total);
  * snapshots pinned before a migration keep reading the pre-migration
    state (their routing travels with them; manifest pins keep source
    runs alive), and release leaks nothing;
  * a crash in either migration window — before the routing-log commit,
    or after it but before source cleanup — recovers to exactly the
    pre- or post-migration state respectively (the recovery clip
    finishes whichever side the log says);
  * ``shard_stats``/``shard_load_summary`` expose the per-shard load
    summary and ``EventTrace`` carries shard_split/shard_merge/
    run_migrate/rebalance_* so tail attribution can blame migrations.

All property tests run under both real hypothesis and the fixed-seed shim
(tests/_hypothesis_compat.py).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (LSMConfig, LSMStore, ShardedLSMStore, Telemetry,
                        make_store, uniform_splitters)

KEY_SPACE = 4_000


def cfg(**kw):
    base = dict(policy="garnering", T=2.0, c=0.8, memtable_bytes=1 << 12,
                base_level_bytes=1 << 14, bits_per_key=8,
                bloom_allocation="monkey")
    base.update(kw)
    return LSMConfig(**base)


def sharded_cfg(shards, key_space=KEY_SPACE, **kw):
    return cfg(shards=shards,
               shard_splitters=uniform_splitters(shards, key_space),
               **kw)


def close_quiet(db):
    if hasattr(db, "close"):
        db.close()


def hot_ops(seed, n_ops, hot_lo=0, hot_hi=KEY_SPACE // 10,
            hot_frac=0.9, del_frac=0.1):
    """Skewed op stream: ``hot_frac`` of ops in [hot_lo, hot_hi)."""
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_ops):
        if rng.random() < hot_frac:
            k = int(rng.integers(hot_lo, hot_hi))
        else:
            k = int(rng.integers(0, KEY_SPACE))
        if rng.random() < del_frac:
            ops.append((k, None))
        else:
            ops.append((k, bytes([65 + i % 26]) * int(rng.integers(1, 60))))
    return ops


def assert_reads_equal(db, oracle, rng, scans=4):
    probes = rng.integers(0, KEY_SPACE, 256).tolist()
    assert db.multi_get(probes) == oracle.multi_get(probes)
    for _ in range(scans):
        start = int(rng.integers(0, KEY_SPACE))
        assert db.scan(start, 50) == oracle.scan(start, 50)
    k = int(rng.integers(0, KEY_SPACE))
    live = db.scan(k, 1)
    for store in (db, oracle):
        got = store.seek(k)
        if live:
            assert got is not None and k <= got <= live[0][0]


def no_leaked_pins(db):
    for s in db.shards:
        assert s.manifest.total_pin_refs() == 0, "leaked version pins"


# ------------------------------------------------- differential under churn
@given(st.integers(0, 10_000), st.sampled_from([2, 4]))
@settings(max_examples=6, deadline=None)
def test_rebalancing_reads_identical_to_single_store(seed, shards):
    """Property: a skewed stream auto-triggers migrations on an async
    facade while reads are compared wave-by-wave against the synchronous
    single store — byte identity must hold across every split/merge/
    migration the trigger decides to make."""
    rebalancing_vs_single_store(seed, shards)


def test_rebalancing_reads_identical_seed_5509():
    """The stream that found the ``seek`` fault: at its second wave both
    stores' memtables hold a tombstone at 2126 and a live 2154, and
    ``seek(2099)`` returned a run's later key (2279 on the single store,
    2222 on the facade) where ``scan(2099, 1)`` gives 2154."""
    rebalancing_vs_single_store(5509, 2)


@pytest.mark.parametrize("views", [False, True])
def test_seek_skips_a_memtable_tombstone(views):
    """A memtable whose first entry at or past the key is a delete still
    answers ``seek`` with its first live key, ahead of a run's."""
    db = LSMStore(cfg(use_range_views=views))
    db.put(30, b"run")
    db.flush()
    db.put(10, b"x")
    db.delete(10)
    db.put(20, b"mem")
    assert db.scan(5, 1) == [(20, b"mem")]
    assert db.seek(5) == 20


def rebalancing_vs_single_store(seed, shards):
    oracle = LSMStore(cfg())
    db = make_store(sharded_cfg(shards, async_compaction=True,
                                compaction_workers=2,
                                rebalance_interval_ops=400,
                                rebalance_ratio=1.3))
    rng = np.random.default_rng(seed)
    try:
        for wave in range(6):
            ops = hot_ops(seed + 31 * wave, 400)
            oracle.write_batch(ops)
            db.write_batch(ops)
            assert_reads_equal(db, oracle, rng)
        db.flush()
        assert db.wait_for_quiesce(60)
        assert db.rebalances >= 1, "skewed stream never triggered"
        keys = list(range(KEY_SPACE))
        assert db.multi_get(keys) == oracle.multi_get(keys)
        assert db.scan(0, KEY_SPACE) == oracle.scan_scalar(0, KEY_SPACE)
        assert db.total_live_entries() == oracle.total_live_entries()
        no_leaked_pins(db)
    finally:
        close_quiet(db)


def test_ordered_zipfian_multiget_stream_on_four_shards():
    """The ``ycsb-b-multiget-4shard`` benchmark cell's stream at a small
    size: records loaded in key order over 4 pre-split shards, then YCSB
    B's key shares (256-key ``multi_get`` calls beside single-key updates,
    19 of 275 calls reads) drawn zipfian 0.99 over the ordered keys, so
    the hot set is the lowest keys, one contiguous range.  Every answer
    equals the synchronous single store's and a dict's, the skew on shard
    0 triggers at least one rebalance, the facade counts each key asked
    for once, and its ``shard_skew`` lies between 1 and the shard
    count."""
    n, shards, batch = 4_000, 4, 256
    rng = np.random.default_rng(20261020)
    cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** 0.99)
    cdf /= cdf[-1]
    oracle = LSMStore(cfg())
    db = make_store(sharded_cfg(shards, key_space=n, async_compaction=True,
                                compaction_workers=shards,
                                rebalance_interval_ops=n // 2 + 48,
                                rebalance_ratio=2.0))
    ref = {}

    def value(k, tag):
        return tag.to_bytes(8, "little") * 12 + k.to_bytes(4, "little")

    try:
        for i in range(0, n, 500):
            ks = list(range(i, i + 500))
            vs = [value(k, 0) for k in ks]
            oracle.put_batch(ks, vs)
            db.put_batch(ks, vs)
            ref.update(zip(ks, vs))
        assert db.rebalances == 0, "the ordered load read as skew"
        s0 = db.stats.snapshot()
        asked = 0
        for tag in range(1, 3_000):
            if rng.random() < 19 / 275:
                ks = np.searchsorted(cdf, rng.random(batch)).tolist()
                want = [ref.get(k) for k in ks]
                assert db.multi_get(ks) == want
                assert oracle.multi_get(ks) == want
                asked += batch
            else:
                k = int(np.searchsorted(cdf, rng.random()))
                v = value(k, tag)
                oracle.put(k, v)
                db.put(k, v)
                ref[k] = v
        d = db.stats.delta(s0)
        assert db.rebalances >= 1, "the zipfian head never triggered"
        assert d.routed_keys == asked > 0
        skew = (d.route_shards / d.sharded_multi_gets
                * d.hot_shard_keys / d.routed_keys)
        assert 1.0 <= skew <= shards
        db.flush()
        assert db.wait_for_quiesce(60)
        keys = list(range(n))
        assert db.multi_get(keys) == oracle.multi_get(keys) == \
            [ref[k] for k in keys]
        no_leaked_pins(db)
    finally:
        close_quiet(db)


def test_uniform_load_never_triggers():
    db = ShardedLSMStore(sharded_cfg(2, rebalance_interval_ops=200,
                                     rebalance_ratio=1.5))
    rng = np.random.default_rng(5)
    ks = rng.integers(0, KEY_SPACE, 4_000, dtype=np.uint64)
    for i in range(0, ks.size, 256):
        db.put_batch(ks[i:i + 256].tolist(), b"u" * 24)
    assert db.rebalances == 0
    assert db.splitters == tuple(uniform_splitters(2, KEY_SPACE))


# ------------------------------------------- explicit split/merge + budgets
def test_rebalance_to_split_merge_and_cache_budgets():
    """Explicit split toward the hot range, then merge back: splitters land
    exactly, reads stay oracle-equal, and the shared-cache namespace
    budgets follow the measured load — hot shard above a cold one, integer
    split summing to the configured total in both directions."""
    total_cache = 1 << 16
    oracle = LSMStore(cfg())
    db = ShardedLSMStore(sharded_cfg(2, cache_bytes=total_cache,
                                     pin_l0_bytes=0))
    ops = hot_ops(11, 3_000)
    oracle.write_batch(ops)
    db.write_batch(ops)
    oracle.flush()
    hot_splitter = KEY_SPACE // 20
    assert db.rebalance_to([hot_splitter])
    assert db.splitters == (hot_splitter,)
    assert db.rebalances == 1 and db.migrated_entries > 0
    budgets = [s.block_cache.budget_bytes for s in db.shards]
    assert sum(budgets) == total_cache
    assert budgets[0] > budgets[1], \
        "hot shard should get the larger cache slice"
    keys = list(range(KEY_SPACE))
    assert db.multi_get(keys) == oracle.multi_get(keys)
    assert db.scan(0, KEY_SPACE) == oracle.scan_scalar(0, KEY_SPACE)
    # merge back to the uniform split: the cold-merge direction
    mid = KEY_SPACE // 2
    assert db.rebalance_to([mid])
    assert db.splitters == (mid,)
    budgets = [s.block_cache.budget_bytes for s in db.shards]
    assert sum(budgets) == total_cache
    assert db.multi_get(keys) == oracle.multi_get(keys)
    assert db.scan(0, KEY_SPACE) == oracle.scan_scalar(0, KEY_SPACE)
    no_leaked_pins(db)


def test_rebalance_to_validates_splitters():
    db = ShardedLSMStore(sharded_cfg(4))
    with pytest.raises(ValueError):
        db.rebalance_to([10, 20])            # wrong count
    with pytest.raises(ValueError):
        db.rebalance_to([30, 20, 10])        # not ascending


# --------------------------------------------------- snapshots vs migration
def test_snapshot_pins_survive_migration():
    """A snapshot taken before a migration reads the pre-migration state
    afterwards: its routing travels with it and the manifest pins keep the
    source runs alive through export/strip."""
    db = ShardedLSMStore(sharded_cfg(2))
    db.write_batch([(k, b"old-%d" % k) for k in range(0, KEY_SPACE, 3)])
    db.flush()
    snap = db.get_snapshot()
    try:
        assert db.rebalance_to([KEY_SPACE // 8])
        db.write_batch([(k, b"new-%d" % k) for k in range(0, KEY_SPACE, 3)])
        db.flush()
        for k in range(0, KEY_SPACE, 301):
            want = b"old-%d" % k if k % 3 == 0 else None
            assert db.get(k, snapshot=snap) == want
            got = db.get(k)
            assert got == (b"new-%d" % k if k % 3 == 0 else None)
        assert db.scan(0, KEY_SPACE, snapshot=snap) == \
            [(k, b"old-%d" % k) for k in range(0, KEY_SPACE, 3)]
    finally:
        db.release_snapshot(snap)
    no_leaked_pins(db)


# ------------------------------------------------------- crash mid-migration
def _filled_pair(seed=17):
    oracle = LSMStore(cfg())
    db = ShardedLSMStore(sharded_cfg(2, wal_fsync_every_write=True))
    ops = hot_ops(seed, 2_500)
    oracle.write_batch(ops)
    db.write_batch(ops)
    oracle.flush()
    db.flush()
    return oracle, db


def _assert_equal_after_recovery(db, oracle):
    keys = list(range(KEY_SPACE))
    assert db.multi_get(keys) == oracle.multi_get(keys)
    assert db.scan(0, KEY_SPACE) == oracle.scan_scalar(0, KEY_SPACE)
    assert db.total_live_entries() == oracle.total_live_entries()
    no_leaked_pins(db)


def test_crash_before_routing_commit_recovers_pre_migration(monkeypatch):
    """Window 1: imports were durably committed in the destinations but the
    routing log was not — recovery must clip the imported copies and land
    on the exact pre-migration state under the old splitters."""
    oracle, db = _filled_pair()
    old = db.splitters

    def boom(new):
        raise RuntimeError("crash before routing commit")

    monkeypatch.setattr(db, "_commit_routing", boom)
    with pytest.raises(RuntimeError):
        db.rebalance_to([KEY_SPACE // 8])
    monkeypatch.undo()
    db.crash()
    db.recover()
    assert db.splitters == old
    _assert_equal_after_recovery(db, oracle)


def test_crash_after_routing_commit_recovers_post_migration(monkeypatch):
    """Window 2: the routing log committed but the sources were not yet
    stripped — recovery must finish the cleanup and land on the exact
    post-migration state under the new splitters."""
    oracle, db = _filled_pair(seed=23)
    target = KEY_SPACE // 8

    def boom(new):
        raise RuntimeError("crash before source cleanup")

    monkeypatch.setattr(db, "_cleanup_sources", boom)
    with pytest.raises(RuntimeError):
        db.rebalance_to([target])
    monkeypatch.undo()
    db.crash()
    db.recover()
    assert db.splitters == (target,)
    _assert_equal_after_recovery(db, oracle)


def test_rebalance_then_crash_then_recover_roundtrip():
    """A completed migration survives crash/recover: new splitters are the
    durable routing and reads still match the oracle."""
    oracle, db = _filled_pair(seed=29)
    assert db.rebalance_to([KEY_SPACE // 8])
    db.crash()
    db.recover()
    assert db.splitters == (KEY_SPACE // 8,)
    _assert_equal_after_recovery(db, oracle)


# --------------------------------------------- quiesce trigger + telemetry
def test_quiesce_boundary_consumes_rebalance_flag():
    """The scheduler-idle hook only flags; ``wait_for_quiesce`` is a
    rebalance boundary that consumes the flag on the foreground thread and
    re-drains afterwards."""
    db = ShardedLSMStore(sharded_cfg(2, async_compaction=True,
                                     compaction_workers=2,
                                     rebalance_interval_ops=300,
                                     rebalance_ratio=1.3))
    try:
        ops = hot_ops(41, 2_000, del_frac=0.0)
        db.write_batch(ops)
        db.flush()
        assert db.wait_for_quiesce(60)
        assert db.rebalances >= 1
        assert not db._rebalance_needed
        hot_width = KEY_SPACE // 10
        assert db.splitters[0] < uniform_splitters(2, KEY_SPACE)[0], \
            "splitter should have moved toward the hot range"
        assert db.splitters[0] <= 2 * hot_width, db.splitters
    finally:
        close_quiet(db)


def test_rebalance_events_and_shard_stats():
    """Satellite 2: per-shard IOStats via ``shard_stats``, the load summary,
    and shard_split/shard_merge/run_migrate/rebalance_* on the EventTrace
    (what serve_latency's tail attribution blames)."""
    tel = Telemetry()
    db = ShardedLSMStore(sharded_cfg(2, telemetry=tel))
    db.write_batch(hot_ops(43, 2_000, del_frac=0.0))
    db.flush()
    stats = db.shard_stats
    assert len(stats) == 2 and all(isinstance(d, dict) for d in stats)
    assert sum(d["wal_appends"] for d in stats) > 0
    summary = db.shard_load_summary()
    assert [d["shard"] for d in summary] == [0, 1]
    assert summary[0]["lo"] == 0 and summary[1]["hi"] == 1 << 64
    assert abs(sum(d["op_share"] for d in summary) - 1.0) < 1e-9
    assert summary[0]["ops"] > summary[1]["ops"], "hot shard must lead"
    assert db.rebalance_now(force=True)
    kinds = [e.kind for e in tel.trace.dump()]
    assert "rebalance_start" in kinds and "rebalance_end" in kinds
    assert "run_migrate" in kinds
    assert "shard_split" in kinds or "shard_shift" in kinds \
        or "shard_merge" in kinds
    assert tel.percentile("rebalance", 50) > 0


def test_arm_rebalancing_resets_window():
    """arm_rebalancing (the bulk-load-then-serve protocol): a sequential
    preload with rebalancing disarmed never migrates; arming afterwards
    resets the load window so the preload's skew cannot trigger."""
    db = ShardedLSMStore(sharded_cfg(2))
    for i in range(0, KEY_SPACE, 256):
        db.put_batch(list(range(i, min(i + 256, KEY_SPACE))), b"s" * 24)
    assert db.rebalances == 0
    db.arm_rebalancing(500, ratio=1.4)
    assert db._load == [0, 0] and db._ops_since_check == 0
    assert db.config.rebalance_interval_ops == 500
    # balanced post-arm traffic: still no trigger
    rng = np.random.default_rng(47)
    ks = rng.integers(0, KEY_SPACE, 1_500, dtype=np.uint64)
    db.put_batch(ks.tolist(), b"t" * 24)
    db.flush()
    assert db.rebalances == 0
