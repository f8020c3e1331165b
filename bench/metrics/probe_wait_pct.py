"""Share of ``multi_get``'s wall time spent inside the device bloom-probe
calls: bitset upload, launches and read-back, over the window (``IOStats``
delta: ``100 * probe_ns / multi_get_ns``).  The rest is the host's part of
the call: memtable probe, fence search, block reads and value copies.
Layer: the read path, ``core/engine.py`` ``multi_get`` over
``core/run.py`` ``point_get_batch``."""


def read(ctx):
    s = ctx.stats
    if not s.get("multi_get_ns"):
        return None
    return 100.0 * s["probe_ns"] / s["multi_get_ns"]
