"""The trace reduction, on a small trace whose answers are worked by
hand, and the reading of a profiler file recorded here."""
import glob
import os

import pytest

from chipbench import trace
from chipbench.trace import Trace

# A 100 ns window with two submits. Device ops: [10, 20), [15, 30) and
# [60, 70): busy 30 ns, idle gaps [0, 10), [30, 60), [70, 100).
HAND = Trace(
    host=[("chipbench.window", 0, 100),
          ("chipbench.submit", 5, 50), ("chipbench.store.read", 8, 40),
          ("chipbench.submit", 55, 95), ("chipbench.maint.tick", 72, 90),
          ("other", 0, 100)],
    ops=[("fusion", 10, 20), ("custom-call", 15, 30), ("fusion", 60, 70),
         ("fusion", 120, 130)],
    modules=[("jit__store_probe(1)", 10, 30), ("jit_merge_sorted_runs", 60,
                                                70),
             ("jit__store_probe(1)", 120, 130)],
    devices=1)


def test_busy_is_the_union_of_ops_in_the_window():
    assert trace.busy_ns(HAND) == 30


def test_idle_gaps_longest_first():
    assert trace.idle_gaps(HAND) == [(30, 60), (70, 100), (0, 10)]


def test_gaps_are_labelled_with_the_innermost_span():
    assert trace.label(HAND, 35) == "store.read"
    assert trace.label(HAND, 80) == "maint.tick"
    assert trace.label(HAND, 52) == "window"


def test_program_time_in_window_and_within_spans():
    assert trace.program_time_ns(HAND, "_store_probe") == 20
    assert trace.program_time_ns(HAND, "merge_sorted_runs",
                                 within=[(55, 65)]) == 10
    assert trace.program_time_ns(HAND, "merge_sorted_runs",
                                 within=[(0, 50)]) == 0


def test_breakdown():
    b = trace.breakdown(HAND)
    assert b["device_ops"] == [["jit__store_probe", 20e-9],
                               ["jit_merge_sorted_runs", 10e-9]]
    assert b["idle_gaps"][0] == ["submit", 30e-9]


def test_metric_readers_on_the_hand_trace():
    from chipbench.metrics import (device_idle_pct, maint_stall_pct,
                                   service_self_ms)
    from chipbench.run import Context
    ctx = Context(trace=HAND, counts={}, compiles=0, calls={}, peaks={})
    assert device_idle_pct.read(ctx) == pytest.approx(70.0)
    assert maint_stall_pct.read(ctx) == pytest.approx(18.0)
    # Submits of 45 and 40 ns less children of 32 and 18 ns.
    assert service_self_ms.read(ctx) == pytest.approx((13 + 22) / 2 / 1e6)


def test_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(1024)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    tr = trace.read_xspace(path)
    names = [n for n, _, _ in tr.host]
    assert names.count("chipbench.window") == 1
    assert names.count("chipbench.submit") == 1
    lo, hi = tr.window
    (s, e), = tr.spans("submit")
    assert lo <= s < e <= hi
