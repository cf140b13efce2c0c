"""The program's own spans and counters of a traced window.

The store records them itself (``repro.runtime.tracing``) while a
profiler session runs, and the traced run's session spans exactly the
window, so the records are the window's. Their clock is
``perf_counter_ns``; the profile's is that less a constant per session.
``window`` pairs the last ``service.submit`` record with the harness's
last ``submit`` span to find that constant, and keeps the records that
start inside the window (earlier runs in one process left theirs
outside it).

A program without the recorder, or a run without a device trace, gives
``None``: every reader then reports nothing.
"""
from __future__ import annotations

from dataclasses import dataclass

from chipbench.trace import clip, union

SUBMIT = "service.submit"
SLACK_NS = 1_000_000        # the pairing's error is microseconds


@dataclass
class Window:
    records: list            # repro.runtime.tracing.Record, in the window
    lo: int                  # window bounds on the records' clock
    hi: int

    def named(self, name: str) -> list:
        return [r for r in self.records if r.name == name]

    @property
    def submits(self) -> list:
        return [r for r in self.named(SUBMIT) if r.parent is None]

    def submits_of(self, kind: str) -> list:
        """Submits that carried keys of ``kind`` (``gets`` or ``puts``)."""
        return [r for r in self.submits if r.attrs.get(kind, 0) > 0]

    def ms_per_submit(self, name: str):
        subs = self.submits
        if not subs:
            return None
        return sum(r.ns for r in self.named(name)) / len(subs) / 1e6

    def total(self, counter: str) -> int:
        return sum(r.counts.get(counter, 0) for r in self.records)

    def per_submit(self, counter: str, kind: str | None = None):
        subs = self.submits if kind is None else self.submits_of(kind)
        return self.total(counter) / len(subs) if subs else None

    def pct(self, name: str) -> float:
        """Union of the span's intervals over the window, in %."""
        ivs = union(clip([(r.start, r.end) for r in self.named(name)],
                         self.lo, self.hi))
        return 100.0 * sum(e - s for s, e in ivs) / (self.hi - self.lo)


def window(ctx) -> Window | None:
    if ctx.trace is None or not ctx.trace.devices \
            or ctx.trace.window is None:
        return None
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    recs = [r for r in tracing.records() if r.end is not None]
    subs = [r for r in recs if r.name == SUBMIT]
    harness = ctx.trace.spans("submit")
    if not subs or not harness:
        return None
    off = subs[-1].end - max(e for _, e in harness)
    lo, hi = ctx.trace.window
    lo, hi = lo + off, hi + off
    return Window([r for r in recs if lo - SLACK_NS <= r.start < hi],
                  lo, hi)
