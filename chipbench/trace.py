"""The device trace of a window, and its reduction to intervals.

``capture`` runs the JAX profiler around the window and reads back the
``.xplane.pb`` it wrote. ``read_xspace`` keeps three things of it, on
the profiler's one clock:

* host spans: the harness's ``chipbench.*`` annotations (``probes.py``),
  the window's own among them;
* device ops: every event on a device plane's ``XLA Ops`` line;
* device programs: every event on its ``XLA Modules`` line.

The rest of this module reduces those intervals: busy time as the union
of op intervals, idle gaps between them, each gap labelled with the
innermost host span it fell in, and device time per program name.
"""
from __future__ import annotations

import contextlib
import glob
import os
import tempfile
from dataclasses import dataclass, field

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    """Intervals in ns: ``(name, start, end)``."""

    host: list = field(default_factory=list)
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)
    devices: int = 0

    @property
    def window(self) -> tuple[int, int] | None:
        w = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        return w[0] if w else None

    def spans(self, name: str) -> list:
        """Host spans called ``chipbench.<name>``."""
        full = SPAN_PREFIX + name
        return [(s, e) for n, s, e in self.host if n == full]


def read_xspace(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if device and any(line.name == OPS_LINE for line in plane.lines):
            tr.devices += 1
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                dest = tr.ops
            elif device and line.name == MODULES_LINE:
                dest = tr.modules
            elif not device:
                dest = tr.host
            else:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                s = int(ev.start_ns)
                dest.append((ev.name, s, s + int(ev.duration_ns)))
    return tr


@contextlib.contextmanager
def capture(out: dict):
    """Profile the body; ``out["trace"]`` holds its ``Trace`` after."""
    import jax
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                 recursive=True))
        out["trace"] = read_xspace(paths[-1]) if paths else Trace()


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(trace: Trace) -> int:
    """Union of device op intervals inside the window, averaged over the
    devices traced."""
    lo, hi = trace.window
    busy = sum(e - s for s, e in union(clip(
        [(s, e) for _, s, e in trace.ops], lo, hi)))
    return busy // max(1, trace.devices)


def idle_gaps(trace: Trace) -> list:
    """Gaps inside the window in which no device op ran, longest first."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in union(clip([(s, e) for _, s, e in trace.ops], lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def label(trace: Trace, t: int) -> str:
    """Innermost harness span open at ``t`` (the window if none other)."""
    best = None
    for name, s, e in trace.host:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return "none" if best is None else best[2][len(SPAN_PREFIX):]


def program_time_ns(trace: Trace, part: str, within=None) -> int:
    """Device time of the programs whose name contains ``part``, inside
    the window; with ``within`` (host spans), only programs that start
    inside one of them."""
    lo, hi = trace.window
    total = 0
    for name, s, e in trace.modules:
        if part not in name or not lo <= s < hi:
            continue
        if within is not None and not any(a <= s < b for a, b in within):
            continue
        total += e - s
    return total


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The programs that took most device time, and the longest idle
    gaps by what the host was doing, in seconds."""
    lo, hi = trace.window
    per: dict = {}
    for name, s, e in trace.modules:
        if lo <= s < hi:
            name = name.split("(")[0]          # drop the program's id
            per[name] = per.get(name, 0) + (e - s)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace)[:top]
    return {
        "device_ops": [[n, v / 1e9] for n, v in ops],
        "idle_gaps": [[label(trace, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps],
    }
