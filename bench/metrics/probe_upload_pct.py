"""Share of the device bloom-probe calls that had to upload their filter's
bitset, over the window (``IOStats`` delta: ``100 * probe_uploads /
probe_calls``).  A filter's bitset stays on the device after its first
probe, so this reads near 0 once every run has been probed.
Layer: the device entry ``kernels/ops.py:bloom_probe_filter``, counted in
the engine's probe route."""


def read(ctx):
    s = ctx.stats
    if not s.get("probe_calls") or "probe_uploads" not in s:
        return None
    return 100.0 * s["probe_uploads"] / s["probe_calls"]
