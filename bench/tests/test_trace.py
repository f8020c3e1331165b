"""The trace reduction and the byte counts: on spans made by hand, and on
a small trace recorded on one TPU v5e chip (``data/small.xplane.pb``)."""
from pathlib import Path

import pytest

from bench import trace as tm
from bench.roofline import merge_bytes, peaks, probe_bytes, roofline_pct

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
MS = 10**6


def span(name, a, b, **args):
    return tm.Span(name, a * MS, b * MS, args)


def hand_trace():
    host = [span("window", 0, 100),
            span("multi_get", 5, 30),
            span("entry:bloom_probe_filter", 10, 20, keys=256, k=7,
                 m_bits=10_000),
            span("put", 35, 40),
            span("get", 47, 48),
            span("entry:merge_runs_tiled", 60, 90, entries=1000)]
    modules = [[span("jit_bloom_probe(3)", 12, 14),
                span("jit_bloom_probe(3)", 15, 16),
                span("jit_bitonic_merge_pallas(7)", 70, 80),
                span("jit_hash_pair(1)", 95, 110)]]   # runs past the window
    ops = [[span("gather", 12, 14), span("fusion", 13, 16),
            span("bitonic_merge", 70, 80), span("fusion", 95, 110)]]
    return tm.Trace(modules, ops, host)


def test_busy_union_and_window():
    t = hand_trace()
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_intervals(0) == [(12 * MS, 16 * MS), (70 * MS, 80 * MS),
                                   (95 * MS, 100 * MS)]
    assert t.busy_s == pytest.approx(0.019)


def test_top_programs_clip_to_the_window():
    got = dict(hand_trace().top_programs())
    assert got == pytest.approx({"jit_bloom_probe": 0.003,
                                 "jit_bitonic_merge_pallas": 0.010,
                                 "jit_hash_pair": 0.005})


def test_idle_gaps_are_named_after_the_host_span():
    gaps = hand_trace().idle_gaps()
    # 16-70 ms: midpoint 43 ms, between the put and the get; 80-95 ms
    # inside the merge's span; 0-12 ms inside the multi_get
    assert gaps == [["client", pytest.approx(0.054)],
                    ["entry:merge_runs_tiled", pytest.approx(0.015)],
                    ["multi_get", pytest.approx(0.012)]]
    assert sum(g[1] for g in gaps) == pytest.approx(0.1 - 0.019)


def test_spans_with_programs():
    t = hand_trace()
    (s, events), = t.spans_with_programs("entry:bloom_probe_filter",
                                         "jit_bloom_probe")
    assert s.args["keys"] == 256 and len(events) == 2
    (s, events), = t.spans_with_programs("entry:merge_runs_tiled",
                                         "jit_bitonic_merge_pallas")
    assert [e.end - e.start for e in events] == [10 * MS]
    assert tm.program_name("jit_bloom_probe(12)") == "jit_bloom_probe"


def test_byte_counts():
    # 256 keys, 7 probes of 4 B each, filter far larger than the probes
    assert probe_bytes(256, 7, 10**9) == 256 * 9 + 256 * 7 * 4
    # the probes cannot need more than the whole bitset: 320 words
    assert probe_bytes(256, 7, 10_240) == 256 * 9 + 320 * 4
    assert merge_bytes(1000) == 24_000
    assert roofline_pct(819e6, 0.001, 819e9) == pytest.approx(100.0)
    assert roofline_pct(0, 1.0, 819e9) is None
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")


def recorded():
    return tm.load(str(DATA), host_names=[
        "window", "multi_get", "put", "entry:bloom_probe_filter",
        "entry:merge_runs_tiled"])


def test_recorded_trace_reduction():
    t = recorded()
    assert len(t.modules) == 1 and 0 < t.busy_s < t.window_s
    names = [n for n, _ in t.top_programs()]
    assert {"jit_bloom_probe", "jit_bitonic_merge_pallas"} <= set(names)
    gaps = t.idle_gaps()
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in t.idle_gaps(10**6)) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)
    probes = t.spans_with_programs("entry:bloom_probe_filter",
                                   "jit_bloom_probe")
    assert probes and all(len(ev) == 1 for _, ev in probes)
    assert all(s.args["keys"] > 0 for s, _ in probes)
    merges = t.spans_with_programs("entry:merge_runs_tiled",
                                   "jit_bitonic_merge_pallas")
    assert merges and all(len(ev) == 1 for _, ev in merges)


def test_recorded_trace_metrics():
    from bench.cell import Context
    from bench.spec import Spec

    ctx = Context(stats={}, window_s=0.0, drain_stats={}, drain_s=0.0,
                  lat_ns={}, trace=recorded(),
                  peaks=peaks("TPU v5 lite"))
    spec = Spec()
    for name in ("probe_roofline_pct", "merge_roofline_pct",
                 "device_idle_pct"):
        value = spec.reader(name).read(ctx)
        assert value is not None and 0 < value < 100, (name, value)
