"""Tests for the telemetry subsystem (DESIGN.md §14) and lossless stats.

* ``LatencyHistogram``: percentile vs a sorted-array nearest-rank oracle
  (bucket-exact — both land in the same log bucket by construction), the
  fieldwise merge algebra (concat-equivalence, associativity, identity),
  and ``record_many`` == scalar ``record`` loop.
* ``EventTrace``: ring-buffer wraparound, ``since(cursor)`` incremental
  consumption.
* Disabled-mode no-op identity: a store with ``telemetry=None`` (the
  default) is bit-for-bit identical — tree, read results, IOStats — to
  seed behavior, and a telemetry-*on* store produces the identical tree
  (telemetry is an observer, never a behavior change).
* ``StatsHub``: the lost-update hammer — concurrent increments from many
  threads merge losslessly (the race this PR fixes), both raw and through
  a live engine with background workers churning.
* Engine wiring: op classes recorded, lifecycle events emitted, sharded
  aggregation through one shared Telemetry, ``IOStats.to_dict`` contract.
* Profiler spans and the always-on timers: the store's ``lsm.*`` and
  ``kernel.*`` spans nest as documented and share the profiler's clock
  with the device programs; no span object exists without a profiler
  session; a store with its device routes off never imports jax; the
  timers' invariants.
* The sharded facade's spans (``lsm.route``, ``lsm.rebalance`` and the
  migration's steps) and its routing and rebalance counters.
"""
import glob
import math
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EventTrace, IOStats, LatencyHistogram, LSMConfig,
                        LSMStore, StatsHub, Telemetry, make_store,
                        uniform_splitters)
from repro.core.run import levels_bit_equal
from repro.core.telemetry import N_BUCKETS, bucket_of


# ------------------------------------------------------------ histogram math
def _oracle_nearest_rank(vals, p):
    rank = max(1, math.ceil(len(vals) * p / 100.0))
    return int(np.sort(np.asarray(vals))[rank - 1])


@given(st.lists(st.integers(1, 10**9), min_size=1, max_size=400),
       st.sampled_from([0.0, 50.0, 90.0, 99.0, 99.9, 100.0]))
@settings(max_examples=60, deadline=None)
def test_histogram_percentile_matches_sorted_oracle(vals, p):
    h = LatencyHistogram()
    for v in vals:
        h.record(v)
    est = h.percentile(p)
    assert np.isfinite(est) and est >= 1.0
    # Nearest-rank oracle: the true sample and the histogram's estimate must
    # land in the same log bucket (exact, not tolerance-based: the estimate
    # is the geometric midpoint of the bucket holding the rank-th sample).
    true = _oracle_nearest_rank(vals, p)
    assert bucket_of(int(est)) == bucket_of(true), (p, est, true)


@given(st.lists(st.integers(1, 10**12), min_size=0, max_size=200),
       st.lists(st.integers(1, 10**12), min_size=0, max_size=200),
       st.lists(st.integers(1, 10**12), min_size=0, max_size=200))
@settings(max_examples=40, deadline=None)
def test_histogram_merge_algebra(a, b, c):
    def hist(vals):
        h = LatencyHistogram()
        for v in vals:
            h.record(v)
        return h

    ha, hb, hc = hist(a), hist(b), hist(c)
    # merge == concat
    concat = hist(a + b)
    merged = ha + hb
    assert np.array_equal(merged.counts, concat.counts)
    assert (merged.n, merged.sum_ns, merged.max_ns, merged.min_ns) == \
        (concat.n, concat.sum_ns, concat.max_ns, concat.min_ns)
    # associativity
    l = (ha + hb) + hc
    r = ha + (hb + hc)
    assert np.array_equal(l.counts, r.counts)
    assert (l.n, l.sum_ns, l.max_ns, l.min_ns) == (r.n, r.sum_ns, r.max_ns,
                                                   r.min_ns)
    # identity + sum() support (the IOStats algebra contract)
    ident = ha + LatencyHistogram()
    assert np.array_equal(ident.counts, ha.counts) and ident.n == ha.n
    s = sum([ha, hb, hc])
    assert s.n == len(a) + len(b) + len(c)
    assert s.n == LatencyHistogram.merge([ha, hb, hc]).n


@given(st.lists(st.integers(0, 10**13), min_size=1, max_size=300))
@settings(max_examples=40, deadline=None)
def test_record_many_matches_scalar_record(vals):
    h_scalar = LatencyHistogram()
    for v in vals:
        h_scalar.record(v)
    h_bulk = LatencyHistogram()
    h_bulk.record_many(np.asarray(vals, dtype=np.int64))
    assert np.array_equal(h_scalar.counts, h_bulk.counts)
    assert (h_scalar.n, h_scalar.sum_ns, h_scalar.max_ns, h_scalar.min_ns) \
        == (h_bulk.n, h_bulk.sum_ns, h_bulk.max_ns, h_bulk.min_ns)


def test_histogram_edge_cases():
    h = LatencyHistogram()
    assert math.isnan(h.percentile(50)) and math.isnan(h.mean())
    h.record(0)          # clamps to 1 ns
    h.record(1 << 50)    # clamps into the top bucket
    assert h.n == 2 and h.min_ns == 1
    assert int(h.counts[N_BUCKETS - 1]) == 1
    d = h.to_dict()
    assert list(d.keys()) == ["count", "p50_ns", "p99_ns", "p999_ns",
                              "max_ns", "min_ns", "mean_ns"]


# ------------------------------------------------------------- event trace
def test_event_trace_wraparound_and_since():
    tr = EventTrace(capacity=8)
    for i in range(20):
        tr.emit("ev", i=i)
    assert len(tr) == 8
    assert tr.dropped == 12
    evs = tr.dump()
    assert [e.seq for e in evs] == list(range(13, 21))      # oldest dropped
    assert [e.fields["i"] for e in evs] == list(range(12, 20))
    assert all(evs[i].ts_ns <= evs[i + 1].ts_ns for i in range(len(evs) - 1))
    # incremental consumption: cursor walks, wraparound past the cursor is
    # simply whatever is still buffered
    got, cur = tr.since(0)
    assert [e.seq for e in got] == list(range(13, 21)) and cur == 20
    got, cur = tr.since(cur)
    assert got == [] and cur == 20
    tr.emit("late", x=1)
    got, cur = tr.since(cur)
    assert len(got) == 1 and got[0].kind == "late" and cur == 21
    # interval reconstruction from end-event fields
    s = tr.emit("flush_end", t0=1000, dur_ns=50)
    ev = tr.dump()[-1]
    assert ev.seq == s and ev.interval() == (1000, 1050)
    assert tr.dump()[0].interval() is None


# -------------------------------------------------- disabled-mode identity
def _mixed_workload(db, n=3000):
    keys = np.random.default_rng(3).integers(0, n * 4, n, dtype=np.uint64)
    db.put_batch(keys[:n // 2].tolist(), b"x" * 40)
    for k in keys[n // 2:n // 2 + 200]:
        db.put(int(k), b"y" * 10)
    db.delete_batch(keys[:50].tolist())
    db.flush()
    reads = [db.get(int(k)) for k in keys[:300]]
    reads.append(db.multi_get(keys[:128]))
    reads.append(db.scan(0, 50))
    reads.append(db.seek(int(keys[0])))
    db.write_batch((int(k), b"z") for k in keys[200:400])
    db.flush()
    reads.append(db.scan(int(keys[5]), 30))
    return reads


@pytest.mark.parametrize("shards", [1, 2])
def test_disabled_mode_is_noop_identity(shards):
    """telemetry=None (seed behavior) vs telemetry=Telemetry(): identical
    tree bytes, identical read results, identical IOStats — telemetry is
    an observer, and the disabled path *is* the seed path."""
    def build(tel):
        cfg = LSMConfig(memtable_bytes=1 << 14, bits_per_key=8,
                        shards=shards, use_range_views=True, telemetry=tel)
        return make_store(cfg)

    db_off = build(None)
    db_on = build(Telemetry())
    r_off = _mixed_workload(db_off)
    r_on = _mixed_workload(db_on)
    assert r_off == r_on
    offs = db_off.shards if shards > 1 else [db_off]
    ons = db_on.shards if shards > 1 else [db_on]
    for a, b in zip(offs, ons):
        assert levels_bit_equal(a._levels, b._levels)
    # counters are deterministic; *_ns fields are wall-clock timers
    d_off = {k: v for k, v in db_off.stats.to_dict().items()
             if not k.endswith("_ns")}
    d_on = {k: v for k, v in db_on.stats.to_dict().items()
            if not k.endswith("_ns")}
    assert d_off == d_on
    # and the on-store actually observed the run
    tel = db_on.telemetry
    assert tel.histogram("get").n == 300
    assert tel.histogram("put").n >= 200
    assert any(e.kind == "flush_end" for e in tel.trace.dump())


# ------------------------------------------------------- lost-update hammer
def test_stats_hub_loses_no_increments():
    """The raw race this PR fixes: T threads x K read-modify-writes on the
    same counter.  Per-thread shards make the merged total exact (the old
    shared-IOStats ``+=`` dropped increments under contention)."""
    hub = StatsHub()
    T, K = 8, 20_000
    barrier = threading.Barrier(T)

    def worker():
        st = hub.local()
        barrier.wait()
        for _ in range(K):
            st.point_reads += 1
            st.stall_ns += 3
    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    merged = hub.merged()
    assert merged.point_reads == T * K
    assert merged.stall_ns == 3 * T * K
    # merged() is a fresh object; shards keep accumulating independently
    hub.local().point_reads += 1
    assert hub.merged().point_reads == T * K + 1
    assert merged.point_reads == T * K


@pytest.mark.slow
def test_engine_counters_exact_under_concurrent_readers():
    """End-to-end hammer: reader threads + the foreground writer + the
    background scheduler worker all charge counters concurrently; the
    merged totals are exact, not approximately right."""
    db = LSMStore(LSMConfig(memtable_bytes=1 << 14, bits_per_key=8,
                            async_compaction=True, compaction_workers=2,
                            slowdown_trigger=0, stall_trigger=0))
    n_keys = 6000
    db.put_batch(list(range(500)), b"seed")    # something to read
    R, M = 4, 1500
    barrier = threading.Barrier(R + 1)
    rng = np.random.default_rng(9)
    read_keys = rng.integers(0, n_keys, (R, M), dtype=np.uint64)

    def reader(r):
        barrier.wait()
        for k in read_keys[r]:
            db.get(int(k))
    threads = [threading.Thread(target=reader, args=(r,)) for r in range(R)]
    for t in threads:
        t.start()
    barrier.wait()
    # foreground writer churns (unique keys => every entry flushes once)
    for k in range(500, n_keys):
        db.put(k, b"v" * 20)
    for t in threads:
        t.join()
    db.flush()
    assert db.wait_for_quiesce(600)
    db.close()
    s = db.stats
    assert s.point_reads == R * M                      # readers, exactly
    assert s.wal_appends == n_keys                     # writer, exactly
    assert s.entries_flushed == n_keys                 # workers, exactly
    assert s.bg_flushes > 0                            # and it WAS concurrent


# ------------------------------------------------------------ engine wiring
def test_engine_records_op_classes_and_events():
    tel = Telemetry()
    db = LSMStore(LSMConfig(memtable_bytes=1 << 13, bits_per_key=8,
                            telemetry=tel))
    for i in range(4000):
        db.put(i, b"v" * 16)
    db.flush()
    db.get(1)
    db.multi_get([1, 2, 3])
    db.scan(0, 20)
    db.seek(7)
    db.delete(3)
    db.put_batch([10_000, 10_001], b"w")
    db.write_batch([(10_002, b"q"), (10_003, None)])
    s = tel.summary()
    for op in ("get", "multi_get", "put", "put_batch", "write_batch",
               "scan", "seek", "flush", "compaction", "wal_fsync"):
        assert op in s and s[op]["count"] > 0, op
        assert np.isfinite(s[op]["p99_ns"]) and s[op]["p99_ns"] > 0
    kinds = {e.kind for e in tel.trace.dump()}
    assert {"flush_start", "flush_end",
            "compaction_start", "compaction_end"} <= kinds
    ends = [e for e in tel.trace.dump() if e.kind == "compaction_end"]
    assert all(e.interval() is not None and "entries" in e.fields
               and "src" in e.fields and "dst" in e.fields for e in ends)
    assert db.telemetry is tel


def test_slowdown_pressure_events_and_stall_histogram():
    tel = Telemetry()
    db = LSMStore(LSMConfig(memtable_bytes=1 << 12, telemetry=tel,
                            async_compaction=True, compaction_workers=1,
                            slowdown_trigger=1, stall_trigger=0))
    for i in range(4000):
        db.put(i, b"v" * 16)
    db.flush()
    assert db.wait_for_quiesce(600)
    db.close()
    assert db.stats.write_slowdowns > 0
    assert tel.histogram("stall").n == db.stats.write_slowdowns
    evs = [e for e in tel.trace.dump() if e.kind == "slowdown"]
    assert evs and all(e.interval() is not None and e.fields["depth"] >= 1
                       for e in evs)


def test_sharded_aggregates_one_telemetry():
    tel = Telemetry()
    db = make_store(LSMConfig(shards=3, memtable_bytes=1 << 14,
                              telemetry=tel))
    assert db.telemetry is tel
    assert all(s.telemetry is tel for s in db.shards)
    db.put_batch(list(range(3000)), b"x" * 30)
    db.flush()
    for k in (1, 1001, 2001, 2999):
        db.get(k)
    # every shard records into the same facade-level histograms
    assert tel.histogram("get").n >= 4
    assert tel.histogram("flush").n >= 3     # one flush per non-empty shard
    snap = db.get_snapshot()                  # exercised; retry event only
    db.release_snapshot(snap)                 # fires under real contention


# ------------------------------------------------------------------ to_dict
def test_iostats_to_dict_stable_order():
    import dataclasses
    s = IOStats(blocks_read=3, point_reads=7)
    d = s.to_dict()
    field_names = [f.name for f in dataclasses.fields(IOStats)]
    assert list(d.keys()) == field_names + ["write_amp"]
    assert d["blocks_read"] == 3 and d["point_reads"] == 7
    assert d["write_amp"] == s.write_amplification()
    # deltas dump through the same path
    s2 = IOStats(blocks_read=5, point_reads=7)
    assert s2.delta(s).to_dict()["blocks_read"] == 2


# --------------------------------------------------- profiler spans (§14)
# child span -> the spans that may enclose it on its thread
PARENTS = {
    "lsm.memtable_probe": ("lsm.multi_get",),
    "lsm.filter_probe": ("lsm.multi_get",),
    "lsm.fence_search": ("lsm.multi_get",),
    "lsm.block_read": ("lsm.multi_get",),
    "lsm.value_copy": ("lsm.multi_get",),
    "kernel.probe_upload": ("lsm.filter_probe",),
    "kernel.probe_launch": ("lsm.filter_probe",),
    "kernel.probe_readback": ("lsm.filter_probe",),
    "lsm.throttle": ("lsm.rotate",),
    "lsm.wal_fsync": ("lsm.rotate", "lsm.flush"),
    "lsm.run_build": ("lsm.flush", "lsm.compaction"),
    "lsm.commit": ("lsm.flush", "lsm.compaction"),
    "kernel.hash_pass": ("lsm.run_build",),
    "lsm.merge": ("lsm.compaction",),
    "kernel.merge_pack": ("lsm.merge",),
    "kernel.merge_launch": ("lsm.merge",),
    "kernel.merge_unpack": ("lsm.merge",),
    "lsm.scan_seek": ("lsm.scan",),
    "lsm.scan_merge": ("lsm.scan",),
}
DEVICE_CFG = dict(memtable_bytes=1 << 12, bits_per_key=8,
                  l0_compaction_trigger=2, use_pallas_bloom=True,
                  use_pallas_merge=True)


def _load(db, base, n=600):
    for i in range(base, base + n):
        db.put(i * 7, b"v" * 16)
    db.flush()


def _trace_events(tmp_path):
    """{thread line: [(name, start, end, args)]} and the device programs'
    events [(program, start, end)] of the one trace under tmp_path."""
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, programs = {}, []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                args = {k: v for k, v in e.stats}
                if "hlo_module" in args:      # a compiled program's op
                    programs.append((args["hlo_module"], a, b))
                else:
                    spans.setdefault((plane.name, li), []).append(
                        (e.name, a, b, args))
    return spans, programs


def test_spans_nest_and_share_the_device_clock(tmp_path):
    """One trace around a sync store's flush, compaction, multi_get and
    scan with the device probe, hash pass and merge on, and an async
    store's rotations: every store span nests under its documented
    parent on its thread, the probe program runs inside
    ``lsm.filter_probe``, and all of it lies inside the test's own span,
    on one clock.  Rotation, flush and compaction spans carry the same
    memtable id."""
    import jax
    from jax.profiler import TraceAnnotation

    sync = LSMStore(LSMConfig(**DEVICE_CFG))
    _load(sync, 0)
    async_db = LSMStore(LSMConfig(**DEVICE_CFG, async_compaction=True,
                                  slowdown_trigger=1, stall_trigger=0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test"):
            _load(sync, 600)                  # flush + compaction
            sync.multi_get(list(range(0, 9000, 7)))
            sync.scan(5, 20)
            _load(async_db, 0)
            assert async_db.wait_for_quiesce(120)
    finally:
        jax.profiler.stop_trace()
        async_db.close()
    assert sync.stats.compactions > 0 and async_db.stats.bg_flushes > 0

    spans, programs = _trace_events(tmp_path)
    outer, = [(a, b) for line in spans.values() for n, a, b, _ in line
              if n == "test"]
    store = [(n, a, b, args) for line in spans.values()
             for n, a, b, args in line if n.startswith(("lsm.", "kernel."))]
    assert {n for n, *_ in store} >= set(PARENTS) | {
        "lsm.multi_get", "lsm.flush", "lsm.compaction", "lsm.scan",
        "lsm.rotate", "lsm.wal_append", "lsm.memtable_put"}
    inside = [(a, b) for _, a, b, _ in store] + \
        [(a, b) for _, a, b in programs]
    assert all(outer[0] <= a <= b <= outer[1] for a, b in inside)
    for line in spans.values():
        for name, a, b, _ in line:
            if name in PARENTS:
                assert any(p in PARENTS[name] and pa <= a and b <= pb
                           for p, pa, pb, _ in line), name
    probes = [(a, b) for n, a, b, _ in store if n == "lsm.filter_probe"]
    runs = [(a, b) for m, a, b in programs if m == "jit_bloom_probe"]
    assert runs and all(any(pa <= a and b <= pb for pa, pb in probes)
                        for a, b in runs)
    # a rotation's id names its flush, and that flush's id the compactions
    # it caused
    rotated = {args["memtable"] for n, _, _, args in store
               if n == "lsm.rotate"}
    flushed = {args["memtable"] for n, _, _, args in store
               if n == "lsm.flush"}
    causes = {args["cause"] for n, _, _, args in store
              if n == "lsm.compaction"}
    assert rotated and rotated <= flushed and causes and causes <= flushed


def test_no_span_object_without_a_profiler_session(monkeypatch, tmp_path):
    import jax

    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    db = LSMStore(LSMConfig(**DEVICE_CFG))
    _load(db, 0)
    _load(db, 600)
    db.multi_get(list(range(0, 9000, 7)))
    db.scan(5, 20)
    assert db.stats.compactions > 0 and made == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        db.multi_get([7, 14])
    finally:
        jax.profiler.stop_trace()
    assert "lsm.multi_get" in made and "kernel.probe_launch" in made


def test_store_without_device_routes_never_imports_jax():
    code = (
        "import sys\n"
        "from repro.core import LSMConfig, LSMStore, make_store\n"
        "for cfg in (LSMConfig(memtable_bytes=1 << 12, bits_per_key=8,\n"
        "                      async_compaction=True),\n"
        "            LSMConfig(memtable_bytes=1 << 12, shards=2)):\n"
        "    db = make_store(cfg)\n"
        "    for i in range(3000):\n"
        "        db.put(i, b'v' * 16)\n"
        "    db.flush(); db.wait_for_quiesce(60)\n"
        "    assert db.multi_get([1, 2])[0] == b'v' * 16\n"
        "    assert len(db.scan(0, 10)) == 10\n"
        "    db.close()\n"
        "print('jax' in sys.modules)\n")
    src = Path(__file__).resolve().parents[1] / "src"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**os.environ, "PYTHONPATH": str(src)})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


def test_span_timers_invariants():
    """The always-on timers on an async store with the device routes on:
    the probe's time lies inside multi_get's, the seek's inside scan's,
    the entries' inside flush and compaction, CPU time within wall time;
    rotated memtables waited for their flush; and the timers are charged
    on the thread that did the work (the worker's flushes, the caller's
    reads) and merge through the StatsHub."""
    db = LSMStore(LSMConfig(**DEVICE_CFG, async_compaction=True))
    for base in (0, 600, 1200):
        _load(db, base)
    assert db.wait_for_quiesce(120)
    db.multi_get(list(range(0, 20000, 7)))
    for k in range(0, 2000, 97):
        db.scan(k, 30)
    db.close()
    s = db.stats
    assert s.bg_flushes > 0 and s.compactions > 0 and s.probe_calls > 0
    assert 0 < s.probe_ns <= s.multi_get_ns
    assert 0 < s.scan_seek_ns <= s.scan_ns
    assert s.flush_queue_ns > 0
    assert 0 < s.entry_ns <= s.flush_ns + s.compaction_ns
    slack = 1_000_000                          # 1 ms of clock granularity
    assert s.flush_cpu_ns <= s.flush_ns + slack
    assert s.compaction_cpu_ns <= s.compaction_ns + slack
    shards = list(db._stats._shards)
    assert any(sh.flush_ns and not sh.multi_get_ns for sh in shards)
    assert any(sh.multi_get_ns and not sh.flush_ns for sh in shards)
    merged = IOStats.merge(shards)
    for f in ("multi_get_ns", "probe_ns", "flush_queue_ns", "flush_ns",
              "compaction_ns", "entry_ns", "scan_ns", "scan_seek_ns"):
        assert getattr(merged, f) == getattr(s, f), f


# ------------------------------------------ the sharded facade (§14, §15)
ROUTE_FIELDS = ("sharded_multi_gets", "sharded_multi_get_ns", "route_ns",
                "routed_keys", "hot_shard_keys", "route_shards",
                "shard_calls", "rebalances", "migrated_entries")


def _sharded(n=4, **kw):
    db = make_store(LSMConfig(memtable_bytes=1 << 12, bits_per_key=8,
                              shards=n,
                              shard_splitters=uniform_splitters(n, 4000),
                              **kw))
    for i in range(0, 4000, 3):
        db.put(i, b"v" * 16)
    db.flush()
    return db


def test_sharded_spans_nest_route_and_rebalance(tmp_path):
    """A facade ``multi_get`` opens ``lsm.route`` around its shards'
    ``lsm.multi_get`` spans, and a rebalance opens ``lsm.rebalance`` around
    its export, import and strip steps, on the caller's thread."""
    import jax
    from jax.profiler import TraceAnnotation

    db = _sharded(2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test"):
            db.multi_get(list(range(0, 4000, 7)))
            assert db.rebalance_to([1000])
    finally:
        jax.profiler.stop_trace()
    spans, _ = _trace_events(tmp_path)
    line, = [ln for ln in spans.values()
             if any(n == "lsm.route" for n, *_ in ln)]
    route, = [(a, b) for n, a, b, _ in line if n == "lsm.route"]
    reads = [(a, b) for n, a, b, _ in line if n == "lsm.multi_get"]
    assert len(reads) == 2
    assert all(route[0] <= a <= b <= route[1] for a, b in reads)
    reb, = [(a, b) for n, a, b, _ in line if n == "lsm.rebalance"]
    steps = [(n, a, b, args) for n, a, b, args in line
             if n in ("lsm.migrate_export", "lsm.migrate_import",
                      "lsm.strip")]
    assert {n for n, *_ in steps} == {"lsm.migrate_export",
                                      "lsm.migrate_import", "lsm.strip"}
    assert all(reb[0] <= a <= b <= reb[1] for _, a, b, _ in steps)
    imports = [args for n, _, _, args in steps if n == "lsm.migrate_import"]
    assert sum(a["entries"] for a in imports) == db.migrated_entries > 0


def test_sharded_route_counters():
    """The facade's counters against what its calls asked for: one
    ``multi_get`` each, every key counted once, the largest shard's part
    and the shards called per batch as the splitters assign them, the
    routing time outside the shards' own ``multi_get`` time, and the
    rebalance counters behind the ``rebalances``/``migrated_entries``
    attributes.  A plain store charges none of them."""
    db = _sharded(4)
    rng = np.random.default_rng(7)
    s0 = db.stats.snapshot()
    shard0 = [s.stats.multi_get_ns for s in db.shards]
    batches = [rng.integers(0, 4000, int(m)).tolist()
               for m in rng.integers(1, 300, 12)]
    batches.append(list(range(0, 900)))          # one shard only
    for b in batches:
        assert db.multi_get(b) == [b"v" * 16 if k % 3 == 0 else None
                                   for k in b]
    d = db.stats.delta(s0)
    per = [np.bincount(np.searchsorted([1000, 2000, 3000], b, side="right"),
                       minlength=4) for b in batches]
    assert d.sharded_multi_gets == len(batches)
    assert d.routed_keys == sum(len(b) for b in batches)
    assert d.hot_shard_keys == sum(int(p.max()) for p in per)
    assert d.shard_calls == sum(int((p > 0).sum()) for p in per)
    assert d.route_shards == 4 * len(batches)
    shard_ns = sum(s.stats.multi_get_ns - t0
                   for s, t0 in zip(db.shards, shard0))
    assert 0 < d.route_ns < d.sharded_multi_get_ns
    assert d.route_ns + shard_ns == d.sharded_multi_get_ns
    assert d.rebalances == 0 and d.migrated_entries == 0
    assert db.rebalance_to([500, 1500, 2500])
    assert db.stats.rebalances == db.rebalances == 1
    assert db.stats.migrated_entries == db.migrated_entries > 0
    plain = LSMStore(LSMConfig(memtable_bytes=1 << 12, bits_per_key=8))
    for i in range(600):
        plain.put(i, b"v")
    plain.flush()
    plain.multi_get(list(range(0, 600, 5)))
    assert all(getattr(plain.stats, f) == 0 for f in ROUTE_FIELDS)
