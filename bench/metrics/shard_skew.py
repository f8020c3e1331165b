"""How unevenly the sharded facade's ``multi_get`` batches split over its
shards: the largest shard's part of each batch, summed, over the keys
asked for, times the shard count (``IOStats`` delta: ``route_shards /
sharded_multi_gets * hot_shard_keys / routed_keys``).  1 when every batch
splits evenly, the shard count when every batch goes to one shard.
Layer: the sharded facade, ``core/sharded.py`` routing and rebalancing."""


def read(ctx):
    s = ctx.stats
    if not s.get("routed_keys"):
        return None
    shards = s["route_shards"] / s["sharded_multi_gets"]
    return shards * s["hot_shard_keys"] / s["routed_keys"]
