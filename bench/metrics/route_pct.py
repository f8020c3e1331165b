"""Share of the sharded facade's ``multi_get`` wall time that the facade
spends itself, splitting the batch by shard, fanning it out and gathering
the answers, outside its shards' own ``multi_get`` calls (``IOStats``
delta: ``100 * route_ns / sharded_multi_get_ns``).  Layer: the sharded
facade, ``core/sharded.py`` ``ShardedLSMStore.multi_get``."""


def read(ctx):
    s = ctx.stats
    if not s.get("sharded_multi_get_ns"):
        return None
    return 100.0 * s["route_ns"] / s["sharded_multi_get_ns"]
