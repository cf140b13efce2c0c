"""Shared set-up of the harness's own checks: ``python -m pytest
chipbench/tests`` from the repository root, on the CPU (the Pallas
kernels run in interpret mode there)."""
from __future__ import annotations

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELLS = ("lookup.gpulsm-16m-log256m", "update.gpulsm-16m-log256m")

# A store of 8-byte records small enough for interpret mode that still
# flushes, merges and keeps two disk levels under every cell's traffic.
# Its log, like the configuration's, never fills within a run, so every
# flush is memory-triggered.
TINY_STORE = dict(total_memory_bytes=64 << 10, write_memory_bytes=8 << 10,
                  sim_cache_bytes=4 << 10, page_bytes=512,
                  active_sstable_bytes=1 << 10, sstable_bytes=2 << 10,
                  max_log_bytes=256 << 20, device_pool_bytes=64 << 20)


def tiny_cell(name: str):
    """``name`` as BENCHMARK.json defines it, cut to a rehearsal's size."""
    from chipbench import spec
    cell = spec.load_cell(name)
    cfg = cell.config
    cfg["recordcount"] = 8192 // int(cfg["trees"])
    cfg["warmup_updates"] = 2048
    cfg["store"].update(TINY_STORE)
    cell.mix["batch"] = 128
    return cell


@pytest.fixture(scope="session", autouse=True)
def _compile_cache_apart(tmp_path_factory):
    """Keep rehearsal compiles out of the checkout's cache directory."""
    from chipbench import run
    saved = run.CACHE_DIR
    run.CACHE_DIR = tmp_path_factory.mktemp("jax_cache")
    yield
    run.CACHE_DIR = saved
