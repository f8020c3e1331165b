"""The sharded facade's per-layer metrics on a made-up ``Context``: each
value from its counters, ``None`` where the window made no sharded
``multi_get``, and ``None`` on a program that lacks the counters."""
import pytest

from bench.cell import Context
from bench.spec import Spec

# 10 calls over 4 shards asking 2,560 keys, the largest shard's parts
# summing to 1,280 keys, 400 us of 2 ms spent routing
STATS = {"sharded_multi_gets": 10, "sharded_multi_get_ns": 2_000_000,
         "route_ns": 400_000, "routed_keys": 2_560, "hot_shard_keys": 1_280,
         "route_shards": 40, "shard_calls": 36}
IDLE = dict(STATS, sharded_multi_gets=0, sharded_multi_get_ns=0, route_ns=0,
            routed_keys=0, hot_shard_keys=0, route_shards=0, shard_calls=0)
# what a program without the counters reports (the parent of this change)
OLD_STATS = {"multi_get_ns": 20_000, "probe_ns": 11_000, "point_reads": 10}
CELL = "ycsb-b-multiget-4shard"


def ctx(stats):
    return Context(stats=stats, window_s=40.0, drain_stats={}, drain_s=1.0,
                   lat_ns={}, trace=None, peaks=None)


@pytest.mark.parametrize("metric,value", [("route_pct", 20.0),
                                          ("shard_skew", 2.0)])
def test_reader(metric, value):
    reader = Spec().reader(metric)
    assert reader.read(ctx(STATS)) == pytest.approx(value)
    assert reader.read(ctx(IDLE)) is None
    assert reader.read(ctx(OLD_STATS)) is None


@pytest.mark.parametrize("hot,skew", [(640, 1.0), (2_560, 4.0)])
def test_shard_skew_ends(hot, skew):
    """An even split reads 1, every batch on one shard reads the shard
    count."""
    reader = Spec().reader("shard_skew")
    assert reader.read(ctx(dict(STATS, hot_shard_keys=hot))) == \
        pytest.approx(skew)


@pytest.mark.parametrize("metric", ["route_pct", "shard_skew"])
def test_declared(metric):
    entry, = [m for m in Spec().data["per_layer"] if m["name"] == metric]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "sharded facade"
    assert entry["moves"] == "ops_per_s" and entry["workloads"] == [CELL]
