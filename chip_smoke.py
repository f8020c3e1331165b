"""Chip smoke test: the store's device path and AutumnKV serving on one TPU.

Phase A builds a store through ``make_store`` at YCSB's record shape (1 KB
records of 10 fields x 100 B, keys scrambled as in ``benchmarks/ycsb.py``)
with Garnering c=0.8/T=2, Monkey filters at 10 bits/key, the block cache,
pinned L0, background compaction, and the device bloom probe, filter-build
hash pass and merge kernel turned on.  It loads ``--records`` records,
overwrites and deletes a slice of them, flushes and waits for compaction,
then checks a 64k-key ``multi_get`` (half present, half absent) and a few
hundred 100-entry scans against a dict oracle of the writes.  Launches and
compiles of each device entry are counted here, from outside the program.

Phase B serves smollm-135m at its published widths through ``ServeEngine``
over the AutumnKV prefix cache, with random weights from ``--seed``: a cold
wave of four 128-token prompts, a warm wave of the same prompts (four hits),
and the same prompts on an engine without the cache.  All tokens must agree.

The last line of stdout is one JSON object, ``{"ok": true, "device": ...}``,
printed only on a TPU with every check passed; anywhere else the script
exits non-zero.  Run it on the chip with no arguments:

    python chip_smoke.py

A CPU rehearsal runs the same code, model widths included, with
interpret-mode kernels and at most ``REHEARSAL_MAX_RECORDS`` records, and
still ends non-zero:

    JAX_PLATFORMS=cpu python chip_smoke.py --records 20000
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

RECORD_BYTES = 1000           # YCSB: 10 fields x 100 B
LOAD_BATCH = 4096
QUERIES = 1 << 16             # multi_get batch, half present, half absent
N_SCANS = 300
SCAN_LEN = 100
REHEARSAL_MAX_RECORDS = 50_000
SERVE_BATCH, PROMPT_TOKENS, S_MAX, GEN_TOKENS = 4, 128, 192, 8
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


_START = time.perf_counter()


def stamp(msg: str) -> None:
    """Print one progress line, prefixed with seconds since start."""
    print(f"[{time.perf_counter() - _START:8.1f}s] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching from
    the persistent cache), summed over every thread of the process."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


class EntryCounter:
    """Counts the launches of one jitted device entry of ``repro.kernels.ops``
    by wrapping it in place; its compiles are JAX's own count."""

    def __init__(self, module, name: str):
        self.jitted = getattr(module, name)
        self.launches = 0
        self._lock = threading.Lock()

        def counted(*args, **kwargs):
            with self._lock:
                self.launches += 1
            return self.jitted(*args, **kwargs)

        setattr(module, name, counted)

    def summary(self) -> dict:
        return {"launches": self.launches,
                "compiles": self.jitted._cache_size()}


def phase_store(args, rng, clock: CompileClock) -> list:
    """Phase A; returns the list of failed checks."""
    from repro.core import LSMConfig, make_store
    from repro.core.types import splitmix64
    from repro.kernels import ops

    entries = {"bloom_probe": EntryCounter(ops, "_probe_jit"),
               "bloom_build_hashes": EntryCounter(ops, "_hash_jit"),
               "merge_runs_tiled": EntryCounter(ops, "_merge_jit")}
    n = args.records
    t0 = time.perf_counter()
    keys = splitmix64(np.arange(n, dtype=np.uint64))
    vals = rng.integers(0, 256, (n, RECORD_BYTES), dtype=np.uint8)
    t_gen = time.perf_counter() - t0
    db = make_store(LSMConfig(
        policy="garnering", c=0.8, T=2.0, bits_per_key=10,
        bloom_allocation="monkey", memtable_bytes=1 << 20,
        base_level_bytes=10 << 20, cache_bytes=64 << 20,
        pin_l0_bytes=4 << 20, async_compaction=True,
        use_pallas_bloom=True, use_pallas_merge=True))
    oracle = {}
    clock0, hits0 = clock.secs, clock.cache_hits
    t0 = time.perf_counter()
    try:
        for i in range(0, n, LOAD_BATCH):
            ks = keys[i:i + LOAD_BATCH].tolist()
            vs = [row.tobytes() for row in vals[i:i + LOAD_BATCH]]
            db.put_batch(ks, vs)
            oracle.update(zip(ks, vs))
        del vals
        cut = max(1, n // 20)              # overwrite 5%, delete another 5%
        perm = rng.permutation(n)
        upd = keys[perm[:cut]].tolist()
        new = [row.tobytes() for row in
               rng.integers(0, 256, (cut, RECORD_BYTES), dtype=np.uint8)]
        db.put_batch(upd, new)
        oracle.update(zip(upd, new))
        dels = keys[perm[cut:2 * cut]]
        db.delete_batch(dels.tolist())
        for k in dels.tolist():
            del oracle[k]
        db.flush()
        if not db.wait_for_quiesce(timeout=1800):
            return ["background compaction did not quiesce"]
        t_load = time.perf_counter() - t0

        live = np.fromiter(oracle.keys(), np.uint64, len(oracle))
        half = min(QUERIES // 2, live.size)
        never = splitmix64(np.arange(n, n + half, dtype=np.uint64))
        absent = np.concatenate([rng.choice(dels, min(half // 2, dels.size),
                                            replace=False), never])[:half]
        queries = np.concatenate([rng.choice(live, half, replace=False),
                                  absent])
        rng.shuffle(queries)
        t1 = time.perf_counter()
        got = db.multi_get(queries.tolist())
        t_get = time.perf_counter() - t1
        bad_gets = sum(g != oracle.get(k)
                       for k, g in zip(queries.tolist(), got))

        live.sort()
        starts = splitmix64(rng.integers(0, 1 << 62, N_SCANS,
                                         dtype=np.uint64) + np.uint64(2 * n))
        bad_scans = 0
        t1 = time.perf_counter()
        for s in starts.tolist():
            out = [(int(k), v) for k, v in db.scan(s, SCAN_LEN)]
            i = int(np.searchsorted(live, np.uint64(s)))
            want = [(k, oracle[k]) for k in live[i:i + SCAN_LEN].tolist()]
            bad_scans += out != want
        t_scan = time.perf_counter() - t1

        n_live = db.total_live_entries()
        report = {
            "records": n, "record_bytes": RECORD_BYTES,
            "levels": [(lv["level"], lv["runs"], lv["entries"])
                       for lv in db.level_summary()],
            "live_entries": n_live, "oracle_entries": len(oracle),
            "logical_bytes": len(oracle) * (RECORD_BYTES + 8),
            "space_amplification": db.space_amplification(),
            "multi_get": {"keys": int(queries.size), "wrong": int(bad_gets),
                          "seconds": t_get},
            "scans": {"count": N_SCANS, "len": SCAN_LEN,
                      "wrong": int(bad_scans), "seconds": t_scan},
            "generate_seconds": t_gen,
            "load_compact_seconds": t_load,
            "setup_compile_seconds": clock.secs - clock0,
            "compile_cache_hits": clock.cache_hits - hits0,
            "device_entries": {k: e.summary() for k, e in entries.items()},
        }
    finally:
        db.close()
    stamp("phase A (store): " + json.dumps(report))
    failed = []
    if bad_gets:
        failed.append(f"{bad_gets} multi_get answers differ from the oracle")
    if bad_scans:
        failed.append(f"{bad_scans} scans differ from the oracle")
    if n_live != len(oracle):
        failed.append(f"live entries {n_live} != oracle {len(oracle)}")
    for name in ("bloom_probe", "merge_runs_tiled"):
        if entries[name].launches == 0:
            failed.append(f"{name} never launched on the device")
    for name, e in entries.items():
        if e.summary()["compiles"] > 32:
            failed.append(f"{name} compiled {e.summary()['compiles']} times")
    return failed


def phase_serve(args, rng, clock: CompileClock) -> list:
    """Phase B; returns the list of failed checks."""
    import jax

    from repro.configs import get_config
    from repro.models.params import init_params
    from repro.serve import Request, ServeEngine

    cfg = get_config("smollm_135m")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(args.seed)))
    t_init = time.perf_counter() - t0
    reqs = [Request(rng.integers(0, cfg.vocab, PROMPT_TOKENS, dtype=np.int32),
                    GEN_TOKENS) for _ in range(SERVE_BATCH)]
    clock0, hits0 = clock.secs, clock.cache_hits
    waves = {}
    eng = ServeEngine(cfg, params, batch=SERVE_BATCH, s_max=S_MAX)
    try:
        for wave in ("cold", "warm"):
            kv0 = eng.kv.hits
            t0 = time.perf_counter()
            out = eng.serve_batch(reqs)
            waves[wave] = dict(tokens=out, hits=eng.kv.hits - kv0,
                               seconds=time.perf_counter() - t0)
    finally:
        t0 = time.perf_counter()
        eng.close()     # drains the prefix cache's background compaction
        t_close = time.perf_counter() - t0
    plain = ServeEngine(cfg, params, batch=SERVE_BATCH, s_max=S_MAX,
                        use_prefix_cache=False)
    t0 = time.perf_counter()
    waves["no_cache"] = dict(tokens=plain.serve_batch(reqs), hits=0,
                             seconds=time.perf_counter() - t0)
    report = {"model": cfg.name, "layers": cfg.n_layers,
              "d_model": cfg.d_model, "heads": [cfg.n_q, cfg.n_kv],
              "vocab": cfg.vocab, "batch": SERVE_BATCH,
              "prompt_tokens": PROMPT_TOKENS, "s_max": S_MAX,
              "init_params_seconds": t_init,
              "setup_compile_seconds": clock.secs - clock0,
              "compile_cache_hits": clock.cache_hits - hits0,
              "close_seconds": t_close,
              "waves": {w: {"hits": r["hits"], "seconds": r["seconds"],
                            "first_tokens": [int(t[0]) for t in r["tokens"]]}
                        for w, r in waves.items()}}
    stamp("phase B (serve): " + json.dumps(report))
    failed = []
    if waves["cold"]["hits"] != 0 or waves["warm"]["hits"] != SERVE_BATCH:
        failed.append(f"hits cold={waves['cold']['hits']} "
                      f"warm={waves['warm']['hits']}, want 0 and "
                      f"{SERVE_BATCH}")
    for other in ("cold", "no_cache"):
        if not all(np.array_equal(a, b) for a, b in
                   zip(waves["warm"]["tokens"], waves[other]["tokens"])):
            failed.append(f"warm-wave tokens differ from the {other} wave")
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--records", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    stamp("device: " + json.dumps(device))
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.records > REHEARSAL_MAX_RECORDS:
        print(f"no TPU found (platform {dev.platform!r}); a CPU rehearsal "
              f"needs --records <= {REHEARSAL_MAX_RECORDS}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 1
    stamp("compile cache: " + enable_compile_cache())
    clock = CompileClock()
    rng = np.random.default_rng(args.seed)
    failed = []
    for name, phase in (("A", phase_store), ("B", phase_serve)):
        stamp(f"phase {name} starts")
        try:
            failed += [f"phase {name}: {f}" for f in phase(args, rng, clock)]
        except Exception:   # report the phase as failed, run the next one
            traceback.print_exc()
            failed.append(f"phase {name} raised")
    stamp("phases done")
    for f in failed:
        print("FAIL:", f, file=sys.stderr)
    if failed:
        return 1
    if not on_tpu:
        print(f"rehearsal passed on {dev.platform}; not a chip run",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
