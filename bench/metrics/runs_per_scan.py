"""Sorted runs positioned per range read, over the window (``IOStats``
delta: ``runs_touched_range / range_reads``).  Layer: the range-read path,
``core/iterator.py`` and ``core/view.py``."""


def read(ctx):
    s = ctx.stats
    return s["runs_touched_range"] / s["range_reads"] if s["range_reads"] \
        else None
