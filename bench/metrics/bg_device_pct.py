"""Share of the wall time of flushes and compactions spent inside the
device entries they call, the filter-build hash pass and the merge kernel,
host packing and read-back included (``IOStats`` delta: ``100 * entry_ns /
(flush_ns + compaction_ns)``).  Layer: compaction, ``core/run.py``
``merge_runs`` over ``kernels/ops.py``."""


def read(ctx):
    s = ctx.stats
    wall = s.get("flush_ns", 0) + s.get("compaction_ns", 0)
    if not wall:
        return None
    return 100.0 * s["entry_ns"] / wall
