"""Share of the wall time of flushes and compactions in which their thread
ran on a CPU (``IOStats`` delta: ``100 * (flush_cpu_ns +
compaction_cpu_ns) / (flush_ns + compaction_ns)``); the rest it waited,
for the interpreter lock or the device.  Layer: compaction, the
scheduler's worker."""


def read(ctx):
    s = ctx.stats
    wall = s.get("flush_ns", 0) + s.get("compaction_ns", 0)
    if not wall:
        return None
    return 100.0 * (s["flush_cpu_ns"] + s["compaction_cpu_ns"]) / wall
