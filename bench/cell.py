"""One run of one cell: build the store, load it, warm up, measure, check.

The window drives the store's public API from one closed-loop client: each
call is issued when the one before it has returned, as YCSB
(``threadcount=1``, no ``target``) and db_bench (``--threads=1``) do.
Every call is timed on the client's clock around the call alone.  The
window closes when the store has also finished, on its background workers,
the flushes and compactions that the window's writes queued (the drain), so
a rate is one the store sustains.  After the window the harness reads back
every key written during the run and a seeded sample of the loaded ones,
frees the store, and replays the client's calls on the plain reference
(``bench/reference.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from . import trace as tracemod
from .reference import Reference, read_back, replay
from .roofline import peaks
from .spec import Spec
from .traffic import (CLASS_OF, GET, MULTI_GET, PUT, SCAN, Calls, Values,
                      key_of, ops_in, seed_words)

DRAIN = "drain"               # host span of the window's drain

LOAD_BATCH = 4096             # records per put_batch while loading
READBACK_SAMPLE = 65536       # loaded keys read back besides the written
READBACK_CHUNK = 65536        # keys per multi_get while reading back
QUIESCE_TIMEOUT_S = 900
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "backend_compiles",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts JAX's traces, backend compiles and persistent-cache hits in
    this process, as ``chip_smoke.py``'s ``CompileClock`` does."""

    def __init__(self):
        import jax
        self.counts = {"traces": 0, "backend_compiles": 0, "cache_hits": 0}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        name = COMPILE_EVENTS.get(event)
        if name:
            with self._lock:
                self.counts[name] += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.counts["cache_hits"] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.counts)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    ops: int
    failed: int
    lat_ns: Dict[str, List[int]]
    drain_s: float = 0.0      # of the window, after the client's last call

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Client:
    """One closed-loop client.  ``log`` keeps every call made, warm-up
    included, in order; ``answers`` what the store returned to the calls
    marked for checking, by their index in ``log``."""

    def __init__(self, db, values: Values, annotate: bool):
        self.db = db
        self.values = values
        self.log: list = []
        self.answers: dict = {}
        self.first_error: Optional[str] = None
        if annotate:
            import jax
            self.span = jax.profiler.TraceAnnotation
        else:
            self.span = lambda _name: contextlib.nullcontext()

    def _call(self, kind, arg, extra):
        db = self.db
        if kind == PUT:
            value = self.values(extra)
            t = time.perf_counter_ns()
            db.put(arg, value)
            return None, time.perf_counter_ns() - t
        t = time.perf_counter_ns()
        if kind == MULTI_GET:
            out = db.multi_get(arg)
        elif kind == GET:
            out = db.get(arg)
        else:
            out = db.scan(arg, extra)
        return out, time.perf_counter_ns() - t

    def drain(self, window: Window) -> Window:
        """Wait until the store's background workers have flushed and
        compacted all that the window's writes queued, and close the window
        there."""
        t = time.perf_counter()
        with self.span(DRAIN):
            quiet = self.db.wait_for_quiesce(timeout=QUIESCE_TIMEOUT_S)
        if not quiet:
            raise RuntimeError("the background did not drain after the "
                               "window")
        window.t1 = time.perf_counter()
        window.drain_s = window.t1 - t
        return window

    def run(self, calls: Calls, *, n_calls: Optional[int] = None,
            until: Optional[float] = None) -> Window:
        lat: Dict[str, List[int]] = {"read": [], "write": [], "scan": []}
        log, answers, span = self.log, self.answers, self.span
        ops = failed = done = 0
        t0 = time.perf_counter()
        while (n_calls is None or done < n_calls) and \
                (until is None or time.perf_counter() < until):
            call = calls.next()
            kind, arg, extra, check = call
            idx = len(log)
            log.append(call)
            done += 1
            try:
                with span(kind):
                    out, ns = self._call(kind, arg, extra)
            except Exception:   # the client keeps going; the run is failed
                failed += ops_in(call)
                if self.first_error is None:
                    self.first_error = traceback.format_exc()
                continue
            lat[CLASS_OF[kind]].append(ns)
            ops += ops_in(call)
            if check:
                answers[idx] = out
        return Window(t0, time.perf_counter(), ops, failed, lat)


def percentile(values: List[int], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least q% of the
    samples at or below it."""
    s = np.sort(np.asarray(values, dtype=np.int64))
    return float(s[max(0, int(np.ceil(q / 100.0 * s.size)) - 1)])


def end_to_end(name: str, window: Window, setup_s: float) -> Optional[float]:
    """``setup_s``, ``ops_per_s`` or ``<read|write|scan>_p<q>_ms``."""
    if name == "setup_s":
        return setup_s
    if name == "ops_per_s":
        return window.ops / window.seconds
    cls, _, rest = name.partition("_p")
    if cls in window.lat_ns and rest.endswith("_ms"):
        samples = window.lat_ns[cls]
        return percentile(samples, float(rest[:-3])) / 1e6 if samples \
            else None
    raise ValueError(f"no end-to-end metric {name!r} in the harness")


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads: the window's ``IOStats`` delta and
    seconds, the same two for the drain that closes the window, the
    client's latencies, the trace and the device's peaks."""
    stats: dict
    window_s: float
    drain_stats: dict
    drain_s: float
    lat_ns: dict
    trace: Optional[tracemod.Trace]
    peaks: Optional[dict]

    @staticmethod
    def span_of(entry) -> str:
        """The host span the harness wraps a device entry's calls in."""
        return "entry:" + entry[1]


@contextlib.contextmanager
def recorded_entries(readers):
    """Wrap the device entries that per-layer metrics declare (``ENTRY``,
    ``span_args``) in host spans carrying each call's sizes, for a traced
    window; restore them on exit."""
    import importlib

    import jax

    saved = []
    for reader in readers:
        entry = getattr(reader, "ENTRY", None)
        if entry is None:
            continue
        module = importlib.import_module(entry[0])
        orig = getattr(module, entry[1])
        name, args_of = Context.span_of(entry), reader.span_args

        def wrapped(*a, _orig=orig, _name=name, _args_of=args_of, **kw):
            with jax.profiler.TraceAnnotation(_name, **_args_of(*a, **kw)):
                return _orig(*a, **kw)

        saved.append((module, entry[1], orig))
        setattr(module, entry[1], wrapped)
    try:
        yield
    finally:
        for module, attr, orig in reversed(saved):
            setattr(module, attr, orig)


def build_store(config: dict):
    from repro.core import LSMConfig, make_store
    return make_store(LSMConfig(**config["store"]))


def load(db, config: dict, records: int, values: Values, seed: int):
    """Insert every record through ``put_batch``, in record order or in a
    seeded permutation as the configuration says, then flush and wait for
    the background compaction to finish.  Returns (keys, tags)."""
    numbers = np.arange(records, dtype=np.uint64)
    if config["load_order"] == "shuffled":
        numbers = np.random.default_rng(seed_words(seed, 3)).permutation(
            numbers)
    elif config["load_order"] != "sequential":
        raise ValueError(f"unknown load_order {config['load_order']!r}")
    keys = key_of(numbers, config["key_map"]).tolist()
    tags = numbers.tolist()
    for i in range(0, records, LOAD_BATCH):
        db.put_batch(keys[i:i + LOAD_BATCH],
                     [values(t) for t in tags[i:i + LOAD_BATCH]])
    db.flush()
    if not db.wait_for_quiesce(timeout=QUIESCE_TIMEOUT_S):
        raise RuntimeError("background compaction did not quiesce after load")
    return keys, tags


def warm_write_path(max_entries: int) -> None:
    """Compile, or fetch from the persistent cache, the filter-build hash
    pass and the merge kernel at every bucketed size a flush or compaction
    of up to ``max_entries`` entries can use, so that compaction reaching a
    new size inside the window compiles nothing there.  The entries bucket
    their shapes to powers of two, so one call per power of two covers
    them."""
    from repro.kernels import ops

    n = 1024
    while n < 2 * max_entries:
        keys = np.arange(n, dtype=np.uint64)
        ops.bloom_build_hashes(keys)
        ops.merge_runs_tiled(keys[0::2], keys[1::2])
        n *= 2


def read_back_keys(log: list, loaded: List[int], seed: int) -> List[int]:
    """Every key written in the run, then a seeded sample of loaded keys."""
    written = list(dict.fromkeys(c[1] for c in log if c[0] == PUT))
    if not written:
        return []
    rng = np.random.default_rng(seed_words(seed, 4))
    pick = rng.choice(len(loaded), min(READBACK_SAMPLE, len(loaded)),
                      replace=False)
    return written + [loaded[i] for i in pick.tolist()]


def device_info() -> dict:
    import jax
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, on_tpu: bool, spec: Optional[Spec] = None,
             records: Optional[int] = None, plant=None) -> dict:
    """One run.  Returns ``{"line": <the result line>, "checks": {...},
    "answers_ok": bool, "info": {...}}``; ``line["correct"]`` is true only
    on a TPU with every check within its limit.  ``records`` overrides the
    configuration's record count (CPU rehearsals and tests only);
    ``plant(db)``, a context manager from ``bench/faults.py``, breaks the
    store under test from its build to its read-back (the controls and the
    fault tests only)."""
    import jax

    spec = spec or Spec()
    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    n_records = int(records or config["records"])
    counter = CompileCounter()
    values = Values(seed, int(config["value_bytes"]))

    db = build_store(config)
    planted = contextlib.ExitStack()
    if plant is not None:
        planted.enter_context(plant(db))
    loaded, tags = load(db, config, n_records, values, seed)
    if mix["ops"].get("update", 0) + mix["ops"].get("insert", 0) > 0:
        warm_write_path(2 * n_records)
    calls = Calls(mix, n_records, config["key_map"], seed)
    client = Client(db, values, annotate=trace)
    client.drain(client.run(calls, n_calls=int(mix["warmup_calls"])))
    gc.collect()
    gc.freeze()

    per_layer = spec.metrics("per_layer", workload) if trace else []
    readers = {m["name"]: spec.reader(m["name"]) for m in per_layer}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    stats0 = db.stats.snapshot()
    comp0 = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(recorded_entries(readers.values()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
            stack.enter_context(jax.profiler.TraceAnnotation(
                tracemod.WINDOW_SPAN))
        window = client.run(calls, until=time.perf_counter() + seconds)
        at_close = db.stats.snapshot()
        client.drain(window)
    delta = db.stats.delta(stats0).to_dict()
    drain_delta = db.stats.delta(at_close).to_dict()
    comp = {k: v - comp0[k] for k, v in counter.snapshot().items()}

    check_keys = read_back_keys(client.log, loaded, seed)
    got_back = []
    for i in range(0, len(check_keys), READBACK_CHUNK):
        got_back += db.multi_get(check_keys[i:i + READBACK_CHUNK])
    device = device_info()
    planted.close()
    db.close()
    del db
    gc.unfreeze()
    gc.collect()

    t_ref = time.perf_counter()
    ref = Reference(values, loaded, tags)
    del loaded, tags
    counts = replay(ref, client.log, client.answers)
    wrong_back = read_back(ref, check_keys, got_back)
    t_ref = time.perf_counter() - t_ref

    classes = {CLASS_OF[c[0]] for c in client.log}
    checks = {}
    if "read" in classes:
        checks["wrong_point_reads"] = {"value": counts["wrong_point"],
                                       "limit": 0}
    if "scan" in classes:
        checks["wrong_scans"] = {"value": counts["wrong_scans"], "limit": 0}
    if "write" in classes:
        checks["wrong_read_back"] = {"value": wrong_back, "limit": 0}
    checks["failed_ops"] = {"value": window.failed, "limit": 0}
    checked = {"point_keys": counts["point_keys"], "scans": counts["scans"],
               "read_back_keys": len(check_keys)}
    vacuous = [k for k, cls in (("point_keys", "read"), ("scans", "scan"),
                                ("read_back_keys", "write"))
               if cls in classes and checked[k] == 0]
    answers_ok = not vacuous and client.first_error is None and all(
        c["value"] <= c["limit"] for c in checks.values())

    trace_info = None
    if trace:
        trace_info = tracemod.load(
            tracemod.find_xplane(trace_dir),
            host_names=[tracemod.WINDOW_SPAN, GET, MULTI_GET, PUT, SCAN,
                        DRAIN]
            + [Context.span_of(r.ENTRY) for r in readers.values()
               if hasattr(r, "ENTRY")])
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    if trace:
        ctx = Context(stats=delta, window_s=window.seconds,
                      drain_stats=drain_delta, drain_s=window.drain_s,
                      lat_ns=window.lat_ns,
                      trace=trace_info if on_tpu else None,
                      peaks=peaks(device["kind"]) if on_tpu else None)
        for m in per_layer:
            if m["source"] == "device_trace" and not on_tpu:
                continue
            value = readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.metrics("end_to_end", workload):
            value = end_to_end(m["name"], window, setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    line = {"correct": bool(on_tpu and answers_ok), "attempted":
            window.ops + window.failed, "failed": window.failed,
            "metrics": metrics, "device": device}
    if trace and on_tpu:
        device["busy_s"] = trace_info.busy_s
        device["window_s"] = trace_info.window_s
        line["breakdown"] = {"device_ops": trace_info.top_programs(10),
                             "idle_gaps": trace_info.idle_gaps(10)}
    line["checks"] = checks

    tails = {cls: {"samples": len(v),
                   "p50_ms": percentile(v, 50) / 1e6 if v else None,
                   "p99_ms": percentile(v, 99) / 1e6 if v else None}
             for cls, v in window.lat_ns.items() if v}
    info = {"workload": workload, "seed": seed, "records": n_records,
            "window_s": window.seconds, "drain_s": window.drain_s,
            "drain_flushes": drain_delta["bg_flushes"],
            "drain_compactions": drain_delta["compactions"],
            "ops": window.ops,
            "setup_s": setup_s, "tails": tails,
            "window_compiles": comp, "checked": checked,
            "reference_s": t_ref,
            "store": {k: delta[k] for k in
                      ("point_reads", "range_reads", "bg_flushes",
                       "compactions", "bytes_flushed", "bytes_compacted",
                       "stall_ns", "write_stalls", "write_slowdowns")}}
    if vacuous:
        info["unchecked"] = vacuous
    if client.first_error is not None:
        info["first_error"] = client.first_error
    return {"line": line, "checks": checks, "answers_ok": answers_ok,
            "info": info}
