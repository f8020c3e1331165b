"""The per-layer metrics that read the store's own timers, on a made-up
``Context``: each value from its counters, ``None`` where its denominator
is zero, and ``None`` on a program that lacks the counters."""
import pytest

from bench.cell import Context
from bench.spec import Spec

STATS = {"multi_get_ns": 20_000, "probe_ns": 11_000,
         "flush_queue_ns": 9_000_000_000, "bg_flushes": 3,
         "flush_ns": 600, "compaction_ns": 400,
         "flush_cpu_ns": 300, "compaction_cpu_ns": 100, "entry_ns": 50,
         "scan_ns": 8_000, "scan_seek_ns": 2_000}
# (metric, value on STATS, counters whose zero leaves it undefined)
CASES = [
    ("probe_wait_pct", 55.0, ["multi_get_ns"]),
    ("flush_wait_ms", 3000.0, ["bg_flushes"]),
    ("bg_cpu_pct", 40.0, ["flush_ns", "compaction_ns"]),
    ("bg_device_pct", 5.0, ["flush_ns", "compaction_ns"]),
    ("scan_seek_pct", 25.0, ["scan_ns"]),
]
# what a program without the timers reports (the parent of this change)
OLD_STATS = {"bg_flushes": 3, "point_reads": 10, "range_reads": 4}


def ctx(stats):
    return Context(stats=stats, window_s=40.0, drain_stats={}, drain_s=1.0,
                   lat_ns={}, trace=None, peaks=None)


@pytest.mark.parametrize("metric,value,zero", CASES)
def test_reader(metric, value, zero):
    reader = Spec().reader(metric)
    assert reader.read(ctx(STATS)) == pytest.approx(value)
    assert reader.read(ctx(dict(STATS, **{k: 0 for k in zero}))) is None
    assert reader.read(ctx(OLD_STATS)) is None


@pytest.mark.parametrize("metric", [c[0] for c in CASES])
def test_declared(metric):
    entry, = [m for m in Spec().data["per_layer"] if m["name"] == metric]
    assert entry["source"] == "program_counter" and entry["workloads"]
