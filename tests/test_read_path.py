"""Differential tests for the batched read subsystem (DESIGN.md §3).

Randomized workloads (puts / deletes / overwrites / flushes / snapshots)
drive every merge policy, asserting that the two new read paths are exact
drop-ins for the scalar ones:

  * ``LSMStore.multi_get(keys) == [get(k) for k in keys]`` — results AND
    aggregate IOStats accounting;
  * ``MergingIterator`` / ``LSMStore.scan`` == a brute-force sorted-dict
    oracle == the reference ``scan_scalar`` path;
  * the numpy bloom probe and the Pallas kernel probe agree bit-for-bit.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import LSMConfig, LSMStore

# all five policies; c only shapes Garnering (c=1 == Leveling, paper §4.1)
POLICY_C = [
    ("leveling", 1.0),
    ("tiering", 1.0),
    ("lazy-leveling", 1.0),
    ("qlsm-bush", 1.0),
    ("garnering", 1.0),
    ("garnering", 0.8),
    ("garnering", 0.4),
]
IDS = [f"{p}-c{c}" for p, c in POLICY_C]


def make_db(policy: str, c: float, **kw) -> LSMStore:
    base = dict(policy=policy, c=c, T=2.0, memtable_bytes=1 << 11,
                base_level_bytes=1 << 13, bits_per_key=8,
                bloom_allocation="monkey")
    base.update(kw)
    return LSMStore(LSMConfig(**base))


def run_workload(db: LSMStore, seed: int, n_ops: int = 1500,
                 key_space: int = 400):
    """Random puts/deletes/flushes; returns (oracle, snapshot, snap_oracle).

    The snapshot is taken right after a flush mid-workload, so the snapshot
    oracle is exactly the durable state at that point.
    """
    rng = np.random.default_rng(seed)
    oracle = {}
    snap = None
    snap_oracle = None
    for i in range(n_ops):
        k = int(rng.integers(0, key_space))
        u = rng.random()
        if u < 0.2:
            db.delete(k)
            oracle.pop(k, None)
        else:
            v = f"s{seed}i{i}".encode()
            db.put(k, v)
            oracle[k] = v
        if i == n_ops // 2:
            db.flush()
            snap = db.get_snapshot()
            snap_oracle = dict(oracle)
        elif u > 0.995:
            db.flush()
    return oracle, snap, snap_oracle


@pytest.mark.parametrize("policy,c", POLICY_C, ids=IDS)
def test_multi_get_matches_scalar_get(policy, c):
    db = make_db(policy, c)
    oracle, snap, snap_oracle = run_workload(db, seed=hash(policy) % 97 + 1)
    rng = np.random.default_rng(5)
    # present, absent, and duplicate keys in one batch
    queries = list(rng.integers(0, 500, 300)) + [7, 7, 7]
    s0 = db.stats.snapshot()
    scalar = [db.get(int(k)) for k in queries]
    d_scalar = db.stats.delta(s0)
    s1 = db.stats.snapshot()
    batch = db.multi_get(queries)
    d_batch = db.stats.delta(s1)
    assert batch == scalar
    assert scalar == [oracle.get(int(k)) for k in queries]
    # reads don't mutate the tree: accounting must match field by field
    # (*_ns fields are wall-clock timers)
    for f in dataclasses.fields(d_scalar):
        if not f.name.endswith("_ns"):
            assert getattr(d_scalar, f.name) == getattr(d_batch, f.name), \
                f.name
    # snapshot reads
    assert db.multi_get(queries, snapshot=snap) == \
        [snap_oracle.get(int(k)) for k in queries]


@pytest.mark.parametrize("policy,c", POLICY_C, ids=IDS)
def test_scan_matches_oracle_and_scalar(policy, c):
    db = make_db(policy, c)
    oracle, snap, snap_oracle = run_workload(db, seed=hash(policy) % 89 + 2)
    exp = sorted(oracle.items())
    assert db.scan(0, len(exp) + 10) == exp
    rng = np.random.default_rng(6)
    for start in rng.integers(0, 450, 12):
        for count in (1, 5, 37):
            got = db.scan(int(start), count)
            assert got == db.scan_scalar(int(start), count), (start, count)
            assert got == [e for e in exp if e[0] >= start][:count]
    # snapshot scans see the frozen state only
    snap_exp = sorted(snap_oracle.items())
    assert db.scan(0, len(snap_exp) + 10, snapshot=snap) == snap_exp
    assert db.scan_scalar(0, len(snap_exp) + 10, snapshot=snap) == snap_exp


def test_iterator_streaming_api():
    db = make_db("garnering", 0.8)
    oracle, _, _ = run_workload(db, seed=13)
    exp = sorted(oracle.items())
    it = db.iterator()
    it.seek(0)
    assert [e for e in it] == exp
    # re-seek mid-stream, stream via next()
    it.seek(200)
    got = []
    while True:
        e = it.next()
        if e is None:
            break
        got.append(e)
    assert got == [e for e in exp if e[0] >= 200]
    # keys come out strictly increasing
    keys = [k for k, _ in exp]
    assert keys == sorted(set(keys))


def test_multi_get_empty_and_memtable_only():
    db = make_db("garnering", 0.8)
    assert db.multi_get([]) == []
    db.put(1, b"a")
    db.delete(2)
    # memtable-resolved: value, tombstone, miss
    assert db.multi_get([1, 2, 3]) == [b"a", None, None]


def test_scan_interleaves_memtable_and_runs():
    db = make_db("garnering", 0.8, memtable_bytes=1 << 14)
    for k in range(0, 100, 2):
        db.put(k, b"run")
    db.flush()
    for k in range(1, 100, 2):
        db.put(k, b"mem")           # stays in the memtable
    db.delete(4)
    got = db.scan(0, 8)
    assert got == [(0, b"run"), (1, b"mem"), (2, b"run"), (3, b"mem"),
                   (5, b"mem"), (6, b"run"), (7, b"mem"), (8, b"run")]


def test_snapshot_pinned_across_many_compactions():
    """get_snapshot pins the version: its runs survive manifest GC no matter
    how many commits follow, until release_snapshot."""
    db = make_db("garnering", 0.8)
    for k in range(100):
        db.put(k, b"old")
    db.flush()
    snap = db.get_snapshot()
    for rep in range(30):            # >> the manifest's 8-version tail
        for k in range(100):
            db.put(k, f"r{rep}".encode())
        db.flush()
    assert db.get(5, snapshot=snap) == b"old"
    assert db.multi_get([5, 6, 7], snapshot=snap) == [b"old"] * 3
    assert db.scan(5, 3, snapshot=snap) == [(5, b"old"), (6, b"old"),
                                            (7, b"old")]
    db.release_snapshot(snap)
    assert db.get(5) == b"r29"


def test_bloom_numpy_and_pallas_probe_agree():
    """The core filter and the Pallas kernel share one hash family."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.bloom import BloomFilter
    from repro.kernels.ops import bloom_probe_filter

    rng = np.random.default_rng(3)
    keys = rng.integers(0, 2 ** 63, 900, dtype=np.uint64)
    bf = BloomFilter(keys, bits_per_key=10)
    for nq in (1, 64, 512, 700):   # below / at / above the kernel block
        q = rng.integers(0, 2 ** 63, nq, dtype=np.uint64)
        np.testing.assert_array_equal(bloom_probe_filter(bf, q),
                                      bf.may_contain(q))
    assert bloom_probe_filter(bf, keys).all()   # no false negatives


def test_multi_get_pallas_route_matches_numpy():
    jax = pytest.importorskip("jax")  # noqa: F841
    db = make_db("garnering", 0.8)
    oracle, _, _ = run_workload(db, seed=21, n_ops=600)
    db.flush()
    queries = list(np.random.default_rng(9).integers(0, 500, 200))
    expected = db.multi_get(queries)
    db.config.use_pallas_bloom = True   # toggling on a live store takes effect
    assert db.multi_get(queries) == expected
    assert expected == [oracle.get(int(k)) for k in queries]


def test_pallas_bloom_differential_bit_for_bit_same_batches():
    """``use_pallas_bloom=True`` (interpret mode) is a bit-for-bit drop-in:
    on the same key batches the engine returns identical values AND identical
    filter decisions — every probe/negative/false-positive/block counter in
    the IOStats delta matches the numpy route exactly."""
    jax = pytest.importorskip("jax")  # noqa: F841
    db = make_db("garnering", 0.8, bits_per_key=10)
    oracle, _, _ = run_workload(db, seed=33, n_ops=1200)
    db.flush()
    rng = np.random.default_rng(17)
    batches = [list(rng.integers(0, 600, sz)) for sz in (1, 63, 64, 257, 500)]
    s0 = db.stats.snapshot()
    numpy_results = [db.multi_get(b) for b in batches]
    d_numpy = db.stats.delta(s0)
    db.config.use_pallas_bloom = True
    s1 = db.stats.snapshot()
    pallas_results = [db.multi_get(b) for b in batches]
    d_pallas = db.stats.delta(s1)
    assert pallas_results == numpy_results
    assert numpy_results == [[oracle.get(int(k)) for k in b] for b in batches]
    # identical filter decisions => identical accounting, field by field,
    # but for the wall-clock timers (*_ns) and the count of device probe
    # calls, which only the device route makes
    for f in dataclasses.fields(d_numpy):
        if not f.name.endswith("_ns") and f.name != "probe_calls":
            assert getattr(d_numpy, f.name) == getattr(d_pallas, f.name), \
                f.name
    assert d_numpy.probe_calls == 0 < d_pallas.probe_calls


# ------------------------------------------- tombstone-dense range scans (§3)
def test_tombstone_dense_scan_refill_count_is_logarithmic():
    """Regression (Issue 6 satellite): tombstone winners occupy demand
    slots, so a scan across a heavily-deleted range used to pay
    O(deleted / window) refills of mostly-dead winners before reaching the
    live tail.  The tombstone carry must grow the demand (and the window,
    past the ``_MAX_WINDOW`` cap) geometrically with the dead prefix:
    ~120k contiguous tombstones must be crossed in O(log deleted) refills
    — the un-fixed iterator needs >200 at the default chunk — and the
    result must stay byte-identical to ``scan_scalar``."""
    db = make_db("garnering", 0.8, memtable_bytes=1 << 16,
                 base_level_bytes=1 << 18, bits_per_key=0)
    n, live_tail, wave = 120_000, 1_000, 8_192
    for i in range(0, n, wave):
        ks = list(range(i, min(i + wave, n)))
        db.put_batch(ks, [b"v%d" % k for k in ks])
    for i in range(0, n - live_tail, wave):
        db.delete_batch(list(range(i, min(i + wave, n - live_tail))))
    db.flush()
    it = db.iterator()
    refills = [0]
    orig = it._refill

    def counting():
        refills[0] += 1
        return orig()

    it._refill = counting
    got = it.scan(0, 100)
    assert got == db.scan_scalar(0, 100)
    assert [k for k, _ in got] == list(range(n - live_tail,
                                             n - live_tail + 100))
    assert refills[0] <= 14, \
        f"{refills[0]} refills to cross {n - live_tail} tombstones"
    # the carry must reset between seeks: a fresh scan over live keys
    # starts from the base ramp again (no leftover giant windows)
    it2 = db.iterator()
    assert it2.scan(n - live_tail, 5) == db.scan_scalar(n - live_tail, 5)
    db.close()


def test_deleted_range_scan_differential_mid_range_probes():
    """Scans *starting inside* a tombstone-dense band (and exactly at its
    edges) must match the scalar oracle — the carry-boosted windows may
    overshoot the band's end, and unconsumed entries must re-window
    correctly on the next refill."""
    db = make_db("garnering", 0.8, memtable_bytes=1 << 13,
                 base_level_bytes=1 << 15)
    n = 6_000
    db.put_batch(list(range(n)), [b"x%d" % k for k in range(n)])
    db.flush()
    db.delete_batch(list(range(1_000, 5_000)))
    db.flush()
    for start in (0, 999, 1_000, 1_001, 2_500, 4_999, 5_000, 5_001, n - 10):
        assert db.scan(start, 64) == db.scan_scalar(start, 64), start
    # interleave fresh writes INTO the dead band (memtable + runs merge)
    db.put_batch(list(range(2_000, 2_050)), [b"new%d" % k
                                             for k in range(2_000, 2_050)])
    for start in (1_500, 1_999, 2_000, 2_025, 2_050, 3_000):
        assert db.scan(start, 64) == db.scan_scalar(start, 64), start
    db.close()
