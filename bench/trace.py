"""Reduce a profiler trace to what the per-layer metrics and the breakdown
read.

The harness records the measured window with ``jax.profiler`` and wraps its
own calls into the store in ``TraceAnnotation`` host spans (``window``,
``multi_get``, ``get``, ``put``, ``scan``, ``drain``, and one span per call
of a device entry that a metric records, carrying the call's sizes as
arguments).  The
profiler writes one ``.xplane.pb``; :func:`load` reads it with JAX alone.

- Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Modules``
  line holds one event per execution of a compiled program, named after the
  jitted function (``jit_bloom_probe(12)``: the number is dropped); their
  ``XLA Ops`` line holds the operations inside.
- Busy time is the union of the operation intervals of a device inside the
  window, averaged over the devices used; idle is the rest of the window.
- An idle gap is named after the host span of the harness that covers its
  midpoint, or ``client`` where none does (the client's own code between
  calls).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
NEST = 16     # host spans to look back over for the one covering a gap: a
              # call's span holds at most one entry span per sorted run
_RUN_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Span:
    name: str
    start: int        # ns, on the trace's clock
    end: int
    args: Dict[str, float] = dataclasses.field(default_factory=dict)


def program_name(event_name: str) -> str:
    """``jit_bloom_probe(12)`` -> ``jit_bloom_probe``."""
    return _RUN_SUFFIX.sub("", event_name)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _args(event) -> Dict[str, float]:
    out = {}
    for name, value in event.stats:
        try:
            out[name] = float(value)
        except (TypeError, ValueError):
            continue
    return out


class Trace:
    """Device programs, device operations and the harness's host spans of
    one traced run."""

    def __init__(self, modules: List[List[Span]], ops: List[List[Span]],
                 host: List[Span]):
        self.modules = modules        # per device, program executions
        self.ops = ops                # per device, operations
        self.host = sorted(host, key=lambda s: s.start)
        windows = [s for s in self.host if s.name == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"trace holds {len(windows)} '{WINDOW_SPAN}' "
                             f"spans, want 1")
        self.t0, self.t1 = windows[0].start, windows[0].end

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clip(self, spans: Sequence[Span]) -> List[Tuple[int, int]]:
        return [(max(s.start, self.t0), min(s.end, self.t1)) for s in spans
                if s.end > self.t0 and s.start < self.t1]

    def busy_intervals(self, device: int) -> List[Tuple[int, int]]:
        """Union of the device's operation intervals inside the window
        (its program executions where the trace has no operations)."""
        spans = self.ops[device] or self.modules[device]
        merged: List[Tuple[int, int]] = []
        for a, b in sorted(self._clip(spans)):
            if merged and a <= merged[-1][1]:
                if b > merged[-1][1]:
                    merged[-1] = (merged[-1][0], b)
            else:
                merged.append((a, b))
        return merged

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.modules:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in range(len(self.modules))) / len(self.modules) / 1e9

    def program_events(self, name: str) -> List[Span]:
        """Executions of one program inside the window, on every device."""
        return [s for dev in self.modules for s in dev
                if program_name(s.name) == name
                and s.start >= self.t0 and s.end <= self.t1]

    def top_programs(self, n: int = 10) -> List[list]:
        """The programs that took most device time in the window."""
        total: Dict[str, int] = {}
        for dev in self.modules:
            for s in dev:
                a, b = max(s.start, self.t0), min(s.end, self.t1)
                if b > a:
                    key = program_name(s.name)
                    total[key] = total.get(key, 0) + b - a
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10, device: int = 0) -> List[list]:
        """The longest idle gaps of a device in the window, each named after
        the harness's host span that covers its midpoint."""
        if device >= len(self.modules):
            return []
        gaps, at = [], self.t0
        for a, b in self.busy_intervals(device) + [(self.t1, self.t1)]:
            if a > at:
                gaps.append((at, a))
            at = max(at, b)
        gaps.sort(key=lambda g: g[0] - g[1])
        calls = [s for s in self.host if s.name != WINDOW_SPAN]
        starts = [s.start for s in calls]
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) // 2
            i = bisect.bisect_right(starts, mid)
            name = next((s.name for s in reversed(calls[max(0, i - NEST):i])
                         if s.end >= mid), "client")
            out.append([name, (b - a) / 1e9])
        return out

    def spans_with_programs(self, span_name: str, program: str
                            ) -> List[Tuple[Span, List[Span]]]:
        """Each host span ``span_name`` inside the window with the
        executions of ``program`` that ran within it.  The entries that a
        metric records block on their result, so their device work lies
        inside their span."""
        events = sorted(self.program_events(program), key=lambda s: s.start)
        starts = [e.start for e in events]
        out = []
        for s in self.host:
            if s.name != span_name or s.start < self.t0 or s.end > self.t1:
                continue
            i = bisect.bisect_right(starts, s.start)
            inside = []
            while i < len(events) and events[i].start < s.end:
                if events[i].end <= s.end:
                    inside.append(events[i])
                i += 1
            out.append((s, inside))
        return out


def _span(event, args=None) -> Span:
    start = int(event.start_ns)
    return Span(event.name, start, start + int(event.duration_ns),
                args or {})


def load(path: str, host_names: Optional[Sequence[str]] = None) -> Trace:
    """Read one ``.xplane.pb``.  ``host_names``: the host spans to keep
    (every event of the host planes whose name is listed); by default the
    harness's own."""
    from jax.profiler import ProfileData

    keep = set(host_names) if host_names is not None else None
    data = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, opl = [], []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    mods = [_span(e) for e in line.events]
                elif line.name == OPS_LINE:
                    opl = [_span(e) for e in line.events]
            modules.append(mods)
            ops.append(opl)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if keep is None or e.name in keep:
                        host.append(_span(e, _args(e)))
    return Trace(modules, ops, host)
