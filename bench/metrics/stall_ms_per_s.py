"""Milliseconds the writer spent stalled or slowed on write pressure, per
second of window (``IOStats.stall_ns`` delta).  Layer: the write path,
memtable rotation and the scheduler's triggers."""


def read(ctx):
    return ctx.stats["stall_ns"] / 1e6 / ctx.window_s
