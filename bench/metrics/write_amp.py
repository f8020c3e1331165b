"""Bytes written per byte flushed, over the window (``IOStats`` delta:
``(bytes_flushed + bytes_compacted) / bytes_flushed``).  Layer: compaction,
the Garnering policy, ``merge_runs`` and the scheduler."""


def read(ctx):
    s = ctx.stats
    if not s["bytes_flushed"]:
        return None
    return (s["bytes_flushed"] + s["bytes_compacted"]) / s["bytes_flushed"]
