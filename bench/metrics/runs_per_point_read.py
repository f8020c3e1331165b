"""Sorted runs examined per point read, over the window (``IOStats``
delta: ``runs_touched_point / point_reads``).  Layer: the read path,
``core/engine.py`` ``get``/``multi_get`` over ``core/run.py`` runs."""


def read(ctx):
    s = ctx.stats
    return s["runs_touched_point"] / s["point_reads"] if s["point_reads"] \
        else None
