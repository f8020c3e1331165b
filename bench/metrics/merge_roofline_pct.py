"""Merge kernel's share of the HBM bandwidth roofline: the bytes the merge
needs for the real entries of each call, read and written
(``bench/roofline.py:merge_bytes``), over the device time of that call's
merge program, summed over the window's calls, at the chip's peak bytes/s.
Layer: the device entry ``kernels/ops.py:merge_runs_tiled`` (Mosaic
``bitonic_merge``)."""
from bench.roofline import merge_bytes, roofline_pct

ENTRY = ("repro.kernels.ops", "merge_runs_tiled")
PROGRAM = "jit_bitonic_merge_pallas"


def span_args(keys_a, keys_b, *_, **__):
    return {"entries": len(keys_a) + len(keys_b)}


def read(ctx):
    if ctx.trace is None:
        return None
    nbytes = device_ns = 0
    for span, events in ctx.trace.spans_with_programs(ctx.span_of(ENTRY),
                                                      PROGRAM):
        if not events:
            continue
        nbytes += merge_bytes(span.args["entries"])
        device_ns += sum(e.end - e.start for e in events)
    return roofline_pct(nbytes, device_ns / 1e9, ctx.peaks["hbm_bytes_per_s"])
