"""Spans and counters inside the store, under one small recorder.

``span(name, **attrs)`` is a context manager around one call of a layer
(the front door's submit, the memory component's search, a tick
segment, a merge fold); ``count(name, n)`` adds ``n`` to a counter (host
to device bytes, tables searched, pairwise merge steps). Both record
only while

* a JAX profiler session runs (``jax.profiler.TraceAnnotation.
  is_enabled()``), so a profile of the store carries them, or
* an explicit ``recording()`` block is open, for operators and tests.

Otherwise each call costs one ``is_enabled()`` check and returns a
shared no-op context. While recording, a span appends a ``Record`` (name,
start and end from ``time.perf_counter_ns()``, the index of its parent
span on the same thread, the ``StorageService`` submit it belongs to, the
thread and ``attrs``) and opens a ``jax.profiler.TraceAnnotation``
named ``repro.<name>``, so the span also lands in the profile, on the
device trace's clock (the profile's host times are ``perf_counter_ns``
less a constant per session). A count adds to the process-wide counter
and to the innermost open span's ``counts``.

JAX's compile events feed a ``compiles`` counter while recording, each
on the innermost open span. The listener stays on once a backend that
jits has asked for it (``listen_compiles``): ``compile_events()`` is the
per-thread count the execution backends read around their jitted calls.

Spans sit at call granularity, never in a per-key loop, and never wait
on the device: recording changes nothing the store computes.

``records()`` and ``summary()`` read what was recorded; ``clear()``
empties it (call it with no span open).
"""
from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PREFIX = "repro."


class Record:
    """One span: times in ``perf_counter_ns``; ``end`` is None while it
    is open; ``parent`` is an index into ``records()``."""

    __slots__ = ("name", "start", "end", "parent", "submit", "thread",
                 "attrs", "counts", "index")

    def __init__(self, name, parent, submit, attrs):
        self.name = name
        self.parent = parent
        self.submit = submit
        self.thread = threading.get_ident()
        self.attrs = attrs
        self.counts: dict = {}
        self.start = self.end = None
        self.index = -1

    @property
    def ns(self) -> int:
        return self.end - self.start


_records: list = []
_counters: collections.Counter = collections.Counter()
_lock = threading.Lock()
_tls = threading.local()
_explicit = 0                  # open ``recording()`` blocks
_annotation = None             # jax.profiler.TraceAnnotation, once imported
_listening = False


def _profiler():
    """``TraceAnnotation`` once jax is imported; before that no profiler
    session can run, and the store's numpy path stays jax-free."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def active() -> bool:
    """True while spans and counts record."""
    if _explicit:
        return True
    ta = _profiler()
    return ta is not None and ta.is_enabled()


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _Span:
    __slots__ = ("name", "submit", "attrs", "rec", "ann")

    def __init__(self, name, submit, attrs):
        self.name, self.submit, self.attrs = name, submit, attrs

    def __enter__(self):
        stack = _stack()
        parent = stack[-1] if stack else None
        if parent is not None and not (
                parent.index < len(_records)
                and _records[parent.index] is parent):
            parent = None                     # opened before a clear()
        submit = self.submit
        if submit is None and parent is not None:
            submit = parent.submit
        rec = self.rec = Record(self.name, None if parent is None
                                else parent.index, submit, self.attrs)
        ta = _profiler()
        self.ann = None if ta is None else ta(PREFIX + self.name)
        if self.ann is not None:
            self.ann.__enter__()
        with _lock:
            rec.index = len(_records)
            _records.append(rec)
        stack.append(rec)
        rec.start = time.perf_counter_ns()
        return rec

    def __exit__(self, *exc):
        self.rec.end = time.perf_counter_ns()
        _stack().pop()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


_OFF = contextlib.nullcontext()        # enters as None


def span(name: str, *, submit: int | None = None, **attrs):
    """A context manager recording one call named ``name`` (entered as
    its ``Record``, or None when not recording). ``submit`` is the
    front door's submit number; nested spans inherit it."""
    if not active():
        return _OFF
    return _Span(name, submit, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` and to the innermost open span."""
    if not active():
        return
    with _lock:
        _counters[name] += n
    stack = getattr(_tls, "stack", None)
    if stack:
        c = stack[-1].counts
        c[name] = c.get(name, 0) + n


@contextlib.contextmanager
def recording():
    """Record spans and counts inside the block, profiler or not."""
    global _explicit
    if "jax" in sys.modules:
        listen_compiles()
    with _lock:
        _explicit += 1
    try:
        yield
    finally:
        with _lock:
            _explicit -= 1


def clear() -> None:
    """Forget every record and counter."""
    with _lock:
        _records.clear()
        _counters.clear()


def records() -> list:
    """The records so far, in the order their spans opened."""
    with _lock:
        return list(_records)


def counters() -> dict:
    with _lock:
        return dict(_counters)


def summary() -> dict:
    """Per span name its ``calls``, ``total_ms`` and ``self_ms`` (total
    less the direct children's), over closed spans; plus ``counters``."""
    recs = records()
    child_ns = [0] * len(recs)
    for r in recs:
        if r.end is not None and r.parent is not None:
            child_ns[r.parent] += r.ns
    spans: dict = {}
    for i, r in enumerate(recs):
        if r.end is None:
            continue
        s = spans.setdefault(r.name, {"calls": 0, "total_ms": 0.0,
                                      "self_ms": 0.0})
        s["calls"] += 1
        s["total_ms"] += r.ns / 1e6
        s["self_ms"] += (r.ns - child_ns[i]) / 1e6
    return {"spans": spans, "counters": counters()}


# -- compile events ----------------------------------------------------------
def _on_event(event, duration, **_):
    if event != COMPILE_EVENT:
        return
    _tls.compiles = getattr(_tls, "compiles", 0) + 1
    count("compiles")


def listen_compiles() -> None:
    """Start counting JAX compile events (once per process; stays on)."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_event)


def compile_events() -> int:
    """Compile events this thread has raised since ``listen_compiles``."""
    return getattr(_tls, "compiles", 0)
