"""Bloom probe's share of the HBM bandwidth roofline: the bytes the probe
needs for the real, unpadded keys and filters of each call
(``bench/roofline.py:probe_bytes``) over the device time of that call's
probe program, summed over the window's calls, at the chip's peak bytes/s.
Layer: the device entry ``kernels/ops.py:bloom_probe_filter``."""
from bench.roofline import probe_bytes, roofline_pct

ENTRY = ("repro.kernels.ops", "bloom_probe_filter")
PROGRAM = "jit_bloom_probe"


def span_args(bf, keys, *_, **__):
    return {"keys": len(keys), "k": int(bf.k), "m_bits": int(bf.m_bits)}


def read(ctx):
    if ctx.trace is None:
        return None
    nbytes = device_ns = 0
    for span, events in ctx.trace.spans_with_programs(ctx.span_of(ENTRY),
                                                      PROGRAM):
        if not events:
            continue
        a = span.args
        nbytes += probe_bytes(a["keys"], a["k"], a["m_bits"])
        device_ns += sum(e.end - e.start for e in events)
    return roofline_pct(nbytes, device_ns / 1e9, ctx.peaks["hbm_bytes_per_s"])
