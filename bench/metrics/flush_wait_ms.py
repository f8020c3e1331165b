"""Milliseconds a rotated memtable waited for its background flush to
start, averaged over the flushes of the window (``IOStats`` delta:
``flush_queue_ns / bg_flushes``).  Layer: the write path, memtable
rotation and the scheduler's queue."""


def read(ctx):
    s = ctx.stats
    if s.get("flush_queue_ns") is None or not s["bg_flushes"]:
        return None
    return s["flush_queue_ns"] / s["bg_flushes"] / 1e6
