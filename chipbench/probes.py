"""What the harness reads from the program while a window runs.

* ``CompileCounter``: programs compiled or loaded from the persistent
  cache, from JAX's own compile events (``jax.monitoring``).
* ``counters``: the store's ``IOStats`` and ``DevicePagePool`` counts.
* ``Spans``: in a traced run only, ``jax.profiler.TraceAnnotation``s
  around calls into each layer (``chipbench.<layer>.<call>``), on the
  device trace's clock, plus the least bytes of each store probe and
  k-way merge the kernels ran (``roofline.py``). The wrappers are the
  harness's own: they go in before the window and come out after it.
"""
from __future__ import annotations

import collections
import functools
from dataclasses import asdict

import numpy as np

from . import roofline

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# (module, class, method, span name): the calls into each layer.
LAYER_CALLS = (
    ("repro.core.lsm.storage", "LSMStore", "read_batch", "store.read"),
    ("repro.core.lsm.storage", "LSMStore", "write_batch", "store.write"),
    ("repro.core.engine.scheduler", "SegmentedScheduler", "tick",
     "maint.tick"),
    ("repro.core.engine.pallas_backend", "PallasBackend",
     "lookup_store_fused", "backend.store_probe"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "prepare_store",
     "backend.prepare_store"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "lookup_fused",
     "backend.tier_probe"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "prepare_tier",
     "backend.prepare_tier"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "merge_runs",
     "backend.merge_runs"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "ingest_run",
     "backend.ingest"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "bloom_build",
     "backend.bloom_build"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "bloom_probe",
     "backend.bloom_probe"),
    ("repro.core.engine.pallas_backend", "PallasBackend", "lookup_batch",
     "backend.run_search"),
)


class CompileCounter:
    """Counts compile events while ``active``; a context manager that
    registers and removes its listener."""

    def __init__(self):
        self.active = False
        self.count = 0
        self.seconds = 0.0
        self.names: collections.Counter = collections.Counter()

    def _on(self, event, duration, fun_name="?", **_):
        if self.active and event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration
            self.names[fun_name] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def counters(svc) -> dict:
    store = svc.store
    out = {f"io.{k}": v for k, v in asdict(store.disk.stats).items()}
    out.update({f"pool.{k}": v
                for k, v in store.device_pool.stats().items()})
    out["backend.fallback_calls"] = store.backend.fallback_calls
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


class Spans:
    """Installs the layer wrappers; ``calls`` collects byte counts."""

    def __init__(self):
        self.calls = {"store_probe_bytes": [], "merge_bytes": []}
        self._saved: list = []

    def _count(self, span: str, args, out) -> None:
        if span == "backend.store_probe" and out is not None:
            view, queries = args[1], args[2]
            entries = [roofline.covered_entries(s, e, n, queries)
                       for s, e, n in zip(view.tier_starts, view.tier_ends,
                                          view.tier_lens)]
            self.calls["store_probe_bytes"].append(
                roofline.lookup_bytes(np.concatenate(entries))
                if entries else 0)
        elif span == "backend.merge_runs":
            lens = [len(k) for k, _ in args[1] if len(k)]
            if len(lens) >= 2:           # fewer runs never reach the device
                self.calls["merge_bytes"].append(
                    roofline.merge_bytes(lens, len(out[0])))

    def _wrap(self, fn, span: str):
        from jax.profiler import TraceAnnotation
        name = "chipbench." + span

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with TraceAnnotation(name):
                out = fn(*args, **kw)
            self._count(span, args, out)
            return out
        return wrapper

    def __enter__(self):
        import importlib
        for mod, cls, meth, span in LAYER_CALLS:
            klass = getattr(importlib.import_module(mod), cls)
            fn = klass.__dict__[meth]
            self._saved.append((klass, meth, fn))
            setattr(klass, meth, self._wrap(fn, span))
        return self

    def __exit__(self, *exc):
        for klass, meth, fn in reversed(self._saved):
            setattr(klass, meth, fn)
        self._saved.clear()
