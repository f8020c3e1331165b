"""Paper Fig. 4 + Table 3: YCSB core workloads A-F.

Load + six workloads with zipfian (0.99) key selection, comparing RocksDB
(Leveling) vs Autumn c=.8 vs Autumn c=.4, reporting throughput (kops/s),
avg/p95/p99 read latencies, write stalls, and space amplification — the
paper's §4.3 metrics at container scale.  The load phase runs through the
batched ingest lane (``put_batch``, DESIGN.md §10).

Two extra lanes ride on the read-only workload C tree state:
``Cbatch*`` resolves the same zipfian stream through ``multi_get`` waves
(numpy probes, then the Pallas bloom kernel route — ``Cbatch_pallas_kops``),
and the ``autumn(.8)+cache`` system row runs with the memory subsystem
(block cache + pinned L0, DESIGN.md §9) enabled, reporting its block-cache
hit rate (``cachehit_pct``) across the whole workload sweep.

The ``autumn(.8)+async`` row runs the whole sweep with the background
compaction scheduler (DESIGN.md §11): the load phase reports the
*foreground* ingest rate (flush/compaction drain on a worker thread) and
every mixed workload exercises reads racing live background installs.

The ``autumn(.8)+sharded`` row runs the sweep on a 4-shard
``ShardedLSMStore`` (DESIGN.md §12): the scrambled keys range-partition
uniformly, background work drains on parallel per-shard schedulers, and
every workload exercises the facade's cross-shard read paths.

The **skew gauntlet** (``skew_gauntlet``, DESIGN.md §15) is the measured
claim behind dynamic shard rebalancing: uniform / zipfian(0.99) / hotspot /
shifting-hotspot rows, each driving a static-splitter facade, a
rebalancing facade, and the single-store oracle in lockstep with an
identical batched op stream.  Crucially the gauntlet routes the **raw
order-preserving key stream** — the classic sharded lanes above hash every
key through ``fnv_scramble``, which uniformizes the keyspace and *hides*
skew from the splitters, so a hotspot would never reach one shard in the
first place.  Reads are byte-compared against the oracle before, during,
and after the rebalancing epoch (inline asserts), and each row reports the
per-shard op imbalance (max/mean) both lanes actually saw.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, List

import numpy as np

from repro.core import LSMStore

from .common import (Hotspot, ShiftingHotspot, Zipfian, cache_hit_pct,
                     fnv_scramble, make_db, pct, shard_imbalance)

VALUE = 256   # scaled from the paper's 1 KB


def _load(db: LSMStore, n: int, batch: int = 4096) -> Dict:
    """YCSB load phase through the batched ingest lane (``put_batch``
    waves, DESIGN.md §10) — identical resulting tree to a scalar put loop."""
    val = bytes(VALUE)
    keys = fnv_scramble(np.arange(n, dtype=np.uint64))
    t0 = time.perf_counter()
    for i in range(0, n, batch):
        db.put_batch(keys[i:i + batch].tolist(), val)
    db.flush()
    dt = time.perf_counter() - t0
    return dict(kops=n / dt / 1e3, stalls=db.stats.write_stalls)


def _mix(db: LSMStore, n: int, n_ops: int, read_frac: float,
         insert_frac: float = 0.0, rmw_frac: float = 0.0,
         scan_frac: float = 0.0, scan_len: int = 100, latest: bool = False,
         seed: int = 11) -> Dict:
    zipf = Zipfian(n, seed=seed)
    rng = np.random.default_rng(seed)
    idx = zipf.sample(n_ops)
    if latest:  # read-latest: recency-weighted (YCSB D)
        idx = n - 1 - idx
    keys = fnv_scramble(idx.astype(np.uint64))
    ops = rng.random(n_ops)
    next_insert = n
    val = bytes(VALUE)
    read_lat: List[float] = []
    scan_lat: List[float] = []
    t0 = time.perf_counter()
    for i in range(n_ops):
        u = ops[i]
        if u < read_frac:
            t1 = time.perf_counter()
            db.get(int(keys[i]))
            read_lat.append((time.perf_counter() - t1) * 1e6)
        elif u < read_frac + scan_frac:
            t1 = time.perf_counter()
            db.scan(int(keys[i]), scan_len)
            scan_lat.append((time.perf_counter() - t1) * 1e6)
        elif u < read_frac + scan_frac + rmw_frac:
            t1 = time.perf_counter()
            db.get(int(keys[i]))
            db.put(int(keys[i]), val)
            read_lat.append((time.perf_counter() - t1) * 1e6)
        elif u < read_frac + scan_frac + rmw_frac + insert_frac:
            db.put(int(fnv_scramble(np.asarray([next_insert],
                                               np.uint64))[0]), val)
            next_insert += 1
        else:
            db.put(int(keys[i]), val)
    dt = time.perf_counter() - t0
    lat = read_lat or scan_lat
    return dict(kops=n_ops / dt / 1e3,
                avg_us=float(np.mean(lat)) if lat else 0.0,
                p95_us=pct(lat, 95) if lat else 0.0,
                p99_us=pct(lat, 99) if lat else 0.0)


def _mix_batched_reads(db: LSMStore, n: int, n_ops: int, batch: int = 256,
                       seed: int = 11) -> Dict:
    """Workload C through the batched read path: zipfian keys resolved in
    ``batch``-sized ``multi_get`` waves (the KV-serving lookup shape)."""
    zipf = Zipfian(n, seed=seed)
    keys = fnv_scramble(zipf.sample(n_ops).astype(np.uint64))
    lat: List[float] = []          # per-key us, one sample per wave
    t0 = time.perf_counter()
    for i in range(0, n_ops, batch):
        wave = keys[i:i + batch]
        t1 = time.perf_counter()
        db.multi_get(wave)
        lat.append((time.perf_counter() - t1) * 1e6 / len(wave))
    dt = time.perf_counter() - t0
    return dict(kops=n_ops / dt / 1e3,
                avg_us=float(np.mean(lat)),
                p95_us=pct(lat, 95),
                p99_us=pct(lat, 99))


WORKLOADS = {
    "A": dict(read_frac=0.5),                                  # 50r/50u
    "B": dict(read_frac=0.95),                                 # 95r/5u
    "C": dict(read_frac=1.0),                                  # read only
    "D": dict(read_frac=0.95, insert_frac=0.05, latest=True),  # read latest
    "E": dict(read_frac=0.0, scan_frac=0.95, insert_frac=0.05),
    "F": dict(read_frac=0.5, rmw_frac=0.5),                    # rmw
}


SYSTEMS = (  # (name, c, cache_kb, pin_l0_kb, async_compaction, shards)
    ("rocksdb", 1.0, 0, 0, False, 1),
    ("autumn(.8)", 0.8, 0, 0, False, 1),
    ("autumn(.4)", 0.4, 0, 0, False, 1),
    ("autumn(.8)+cache", 0.8, 1024, 128, False, 1),
    # background flush/compaction (DESIGN.md §11) at the steady-state
    # pressure defaults: load_kops is the *foreground* ingest rate, the
    # workload mixes then run with reads racing live background churn
    ("autumn(.8)+async", 0.8, 0, 0, True, 1),
    # sharded keyspace (DESIGN.md §12): 4 range-partitioned stores, parallel
    # per-shard schedulers under a 4-worker budget; the scrambled YCSB keys
    # are uniform over uint64, so the default splitters balance
    ("autumn(.8)+sharded", 0.8, 0, 0, True, 4),
)


def run(n: int = 60_000, n_ops: int = 8_000) -> List[Dict]:
    rows = []
    for name, c, cache_kb, pin_l0_kb, async_c, shards in SYSTEMS:
        db = make_db(c=c, T=5.0, bits_per_key=10, bloom_allocation="monkey",
                     cache_kb=cache_kb, pin_l0_kb=pin_l0_kb,
                     async_compaction=async_c, shards=shards,
                     compaction_workers=shards)
        load = _load(db, n)
        # levels/space_amp need the settled tree; stalls are re-read after
        # quiesce so the async row's count is deterministic (the background
        # L0 rate limiter shares the write_stalls counter)
        assert db.wait_for_quiesce(600), f"{name}: load failed to quiesce"
        row = dict(system=name, load_kops=load["kops"],
                   stalls=db.stats.write_stalls, levels=db.num_levels_in_use,
                   space_amp=db.space_amplification())
        s_sweep = db.stats.snapshot()
        for w, kw in WORKLOADS.items():
            ops = n_ops if w != "E" else max(n_ops // 8, 500)
            m = _mix(db, n, ops, **kw)
            row[f"{w}_kops"] = m["kops"]
            if w in ("A", "C", "E"):
                row[f"{w}_avg_us"] = m["avg_us"]
                row[f"{w}_p95_us"] = m["p95_us"]
                row[f"{w}_p99_us"] = m["p99_us"]
            if w == "C":
                # same tree state as C (read-only workload): batched vs
                # scalar point reads are a like-for-like comparison here
                mb = _mix_batched_reads(db, n, n_ops)
                row["Cbatch_kops"] = mb["kops"]
                row["Cbatch_speedup"] = (mb["kops"] / m["kops"]
                                         if m["kops"] else 0.0)
                # same stream again through the device bloom-probe route
                db.config.use_pallas_bloom = True
                row["Cbatch_pallas_kops"] = _mix_batched_reads(
                    db, n, n_ops)["kops"]
                db.config.use_pallas_bloom = False
        # drain churn from the last write mix before the sweep-wide stats
        assert db.wait_for_quiesce(600), f"{name}: sweep failed to quiesce"
        row["cachehit_pct"] = cache_hit_pct(db.stats.delta(s_sweep))
        rows.append(row)
        db.close()
    return rows


# -------------------------------------------------- skew gauntlet (§15)

SKEW_WORKLOADS = ("uniform", "zipfian", "hotspot", "shifting")


def _skew_stream(name: str, n: int, n_ops: int, seed: int = 13
                 ) -> np.ndarray:
    """RAW order-preserving keys over [0, n) — no fnv_scramble, so shard
    routing actually sees the hot range (satellite bugfix: the hashed
    lanes' scrambling made every distribution look uniform to the
    splitters)."""
    if name == "uniform":
        return np.random.default_rng(seed).integers(0, n, n_ops,
                                                    dtype=np.uint64)
    if name == "zipfian":
        return Zipfian(n, seed=seed).sample(n_ops).astype(np.uint64)
    if name == "hotspot":
        # 90% of ops on [0, n/10): entirely inside one static shard —
        # the worst case for fixed splitters
        return Hotspot(n, seed=seed).sample(n_ops)
    if name == "shifting":
        return ShiftingHotspot(n, period=max(1, n_ops // 4),
                               seed=seed).sample(n_ops)
    raise ValueError(name)


def _gauntlet_check(systems: Dict, oracle, n: int, keys: np.ndarray,
                    tag: str) -> None:
    """Inline byte-identity asserts vs the single-store oracle — run
    before / during / after the rebalancing epoch."""
    rng = np.random.default_rng(5)
    probe = np.unique(np.concatenate(
        [keys[: min(2000, keys.size)],
         rng.integers(0, n, 1000, dtype=np.uint64)]))
    exp = oracle.multi_get(probe)
    s0 = int(keys[0]) if keys.size else 0
    exp_scan = oracle.scan(s0, 300)
    for name, db in systems.items():
        assert db.multi_get(probe) == exp, \
            f"{tag}: {name} multi_get diverged from single-store oracle"
        assert db.scan(s0, 300) == exp_scan, \
            f"{tag}: {name} scan diverged from single-store oracle"


def skew_gauntlet(n: int = 100_000, n_ops: int = 0, shards: int = 0,
                  batch: int = 2048, quiet: bool = False) -> List[Dict]:
    """Static splitters vs dynamic rebalancing vs the single-store oracle,
    lockstep-fed the same skewed op stream (7/8 update waves, 1/8
    ``multi_get`` waves, wave-varying values so stale reads cannot pass the
    oracle compare).  Per-store time = its own foreground calls + its own
    drain, so a hot shard's serialized background backlog lands on the lane
    that caused it."""
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 2
    shards = shards or max(2, min(4, cores))
    n_ops = n_ops or n
    rows: List[Dict] = []
    for wl in SKEW_WORKLOADS:
        keys = _skew_stream(wl, n, n_ops)
        oracle = make_db(c=0.8, T=5.0, bits_per_key=10,
                         bloom_allocation="monkey")
        systems = {
            "static": make_db(c=0.8, T=5.0, bits_per_key=10,
                              bloom_allocation="monkey",
                              async_compaction=True,
                              compaction_workers=shards, shards=shards,
                              shard_key_space=n),
            # the rebal lane is built UNARMED (interval 0) and armed after
            # the preload: a sequential bulk load looks maximally skewed to
            # the windowed tracker (every sorted wave lands in one shard),
            # and migrating during it both churns and poisons the splitters
            # for the serving phase — arm_rebalancing is the documented
            # bulk-load-then-serve protocol (DESIGN.md §15)
            "rebal": make_db(c=0.8, T=5.0, bits_per_key=10,
                             bloom_allocation="monkey",
                             async_compaction=True,
                             compaction_workers=shards, shards=shards,
                             shard_key_space=n),
        }
        # balanced preload of the full keyspace, identical waves everywhere
        load_keys = np.arange(n, dtype=np.uint64)
        val0 = bytes(VALUE)
        for db in (*systems.values(), oracle):
            for i in range(0, n, 4096):
                db.put_batch(load_keys[i:i + 4096].tolist(), val0)
            db.flush()
        for name, db in systems.items():
            assert db.wait_for_quiesce(600), f"{wl}/{name}: preload quiesce"
        systems["rebal"].arm_rebalancing(max(2000, n_ops // 16), ratio=1.4)
        _gauntlet_check(systems, oracle, n, keys, f"{wl}/before")
        loads0 = {name: db.shard_load_ops() for name, db in systems.items()}
        t_acc = {name: 0.0 for name in systems}
        t_acc["single"] = 0.0
        # same burst discipline as fill_random_batch_async: long GIL slices
        # for the writer, foreground pinned off the workers' core
        prev_switch = sys.getswitchinterval()
        sys.setswitchinterval(0.02)
        prev_aff = None
        try:
            aff = sorted(os.sched_getaffinity(0))
            if len(aff) > 1:
                prev_aff = set(aff)
                os.sched_setaffinity(0, set(aff[:-1]))
        except (AttributeError, OSError):
            pass
        try:
            half_wave = (n_ops // batch) // 2
            for wi, i in enumerate(range(0, n_ops, batch)):
                wave = keys[i:i + batch].tolist()
                write = wi % 8 != 7
                val = (b"%08d" % wi) * (VALUE // 8)
                for name, db in (*systems.items(), ("single", oracle)):
                    t1 = time.perf_counter()
                    if write:
                        db.put_batch(wave, val)
                    else:
                        db.multi_get(wave)
                    t_acc[name] += time.perf_counter() - t1
                if wi == half_wave:
                    # mid-epoch: rebalances (and background churn) live
                    _gauntlet_check(systems, oracle, n, keys,
                                    f"{wl}/during")
            for name, db in systems.items():
                t1 = time.perf_counter()
                db.flush()
                assert db.wait_for_quiesce(600), f"{wl}/{name}: quiesce"
                t_acc[name] += time.perf_counter() - t1
            t1 = time.perf_counter()
            oracle.flush()
            t_acc["single"] += time.perf_counter() - t1
        finally:
            sys.setswitchinterval(prev_switch)
            if prev_aff is not None:
                try:
                    os.sched_setaffinity(0, prev_aff)
                except OSError:
                    pass
        _gauntlet_check(systems, oracle, n, keys, f"{wl}/after")
        imb = {name: shard_imbalance(
                   [b - a for a, b in zip(loads0[name],
                                          db.shard_load_ops())])
               for name, db in systems.items()}
        row = dict(workload=wl, shards=shards,
                   single_kops=n_ops / t_acc["single"] / 1e3,
                   static_kops=n_ops / t_acc["static"] / 1e3,
                   rebal_kops=n_ops / t_acc["rebal"] / 1e3,
                   rebal_speedup=t_acc["static"] / t_acc["rebal"],
                   imb_static=imb["static"], imb_rebal=imb["rebal"],
                   rebalances=systems["rebal"].rebalances,
                   migrated_entries=systems["rebal"].migrated_entries)
        rows.append(row)
        if not quiet:
            print(f"# {wl}: static {row['static_kops']:.1f} kops, "
                  f"rebal {row['rebal_kops']:.1f} kops "
                  f"({row['rebal_speedup']:.2f}x), "
                  f"{row['rebalances']} rebalances, "
                  f"imbalance {imb['static']:.2f} -> {imb['rebal']:.2f}",
                  flush=True)
        for db in (*systems.values(), oracle):
            db.close()
    return rows


def _print_rows(rows: List[Dict]) -> None:
    cols = list(rows[0].keys())
    print(",".join(cols))
    for r in rows:
        print(",".join(f"{r[c]:.2f}" if isinstance(r[c], float)
                       else str(r[c]) for c in cols))


def main(n: int = 60_000, n_ops: int = 8_000, gauntlet_n: int = 0,
         skew_only: bool = False, classic_only: bool = False,
         smoke: bool = False, json_path: str = None):
    out = {}
    if not skew_only:
        rows = run(n, n_ops)
        _print_rows(rows)
        out["classic"] = rows
    if not classic_only:
        grows = skew_gauntlet(n=gauntlet_n or n, quiet=smoke)
        _print_rows(grows)
        out["skew_gauntlet"] = grows
        if smoke:
            # CSV-contract + sanity: all four skew rows present, oracle
            # byte-identity held inline, and the hotspot row actually
            # rebalanced.  Speedup is asserted only at full scale — at
            # smoke scale the migration overhead dominates the tiny run.
            assert [r["workload"] for r in grows] == list(SKEW_WORKLOADS)
            assert all(r["static_kops"] > 0 and r["rebal_kops"] > 0
                       for r in grows)
            hot = next(r for r in grows if r["workload"] == "hotspot")
            assert hot["rebalances"] >= 1, "hotspot row never rebalanced"
            assert hot["migrated_entries"] > 0
            assert hot["imb_rebal"] <= hot["imb_static"] + 1e-9, \
                "rebalancing did not reduce hotspot imbalance"
            print(f"ycsb-ok: gauntlet rows={len(grows)} "
                  f"hotspot_rebalances={hot['rebalances']} "
                  f"imb {hot['imb_static']:.2f}->{hot['imb_rebal']:.2f}")
    if json_path:
        import json
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", type=int, default=60_000,
                    help="loaded keys (classic sweep + gauntlet default)")
    ap.add_argument("--ops", type=int, default=8_000,
                    help="ops per classic workload mix")
    ap.add_argument("--gauntlet-n", type=int, default=0,
                    help="skew-gauntlet keys/ops (defaults to -n)")
    ap.add_argument("--skew-only", action="store_true",
                    help="run only the skew gauntlet")
    ap.add_argument("--classic-only", action="store_true",
                    help="run only the classic A-F sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI smoke: tiny skew gauntlet + contract asserts")
    ap.add_argument("--json", type=str, default=None,
                    help="write rows to this JSON file")
    args = ap.parse_args()
    if args.smoke:
        main(n=4_000, gauntlet_n=4_000, skew_only=True, smoke=True,
             json_path=args.json)
    else:
        main(n=args.n, n_ops=args.ops, gauntlet_n=args.gauntlet_n,
             skew_only=args.skew_only, classic_only=args.classic_only,
             json_path=args.json)
