"""Memtables the client left queued at its last call: those the
background flushed in the drain (``IOStats.bg_flushes`` delta over the
drain).  Layer: the write path, memtable rotation and the scheduler's
triggers, which bound the queue."""


def read(ctx):
    return ctx.drain_stats["bg_flushes"]
