"""Share of ``scan``'s wall time spent positioning the merging iterator's
cursors, one seek per run, over the window (``IOStats`` delta: ``100 *
scan_seek_ns / scan_ns``); the rest builds the iterator and merges the
runs.  Layer: the range-read path, ``core/iterator.py``."""


def read(ctx):
    s = ctx.stats
    if not s.get("scan_ns"):
        return None
    return 100.0 * s["scan_seek_ns"] / s["scan_ns"]
