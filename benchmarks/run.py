"""Run reduced versions of every paper-figure benchmark.

Prints ``name,value,derived`` CSV (one line per measured point). All
drivers go through the ``StorageService`` front door (typed request plans,
sessions, governor-owned tuning). Full-size figures: run each module
directly, e.g. ``python -m benchmarks.fig07_single_tree``. ``--smoke``
runs a tiny-ops subset (single-tree schemes, TPC-C transaction plans,
governor-driven tuner, LSM hot-key skew, the shuffled mixed-op
``service_mixed`` scenario and the sharded hot-shard scenario) as a CI
wiring check for the service layer, the sharded data plane, the batched
write path and the maintenance scheduler.

``--json`` additionally writes ``BENCH_<module>.json`` next to the cwd:
one structured record per measured row ({name, value, scheme?, shards?,
throughput?, stalls?, derived{...}}), so the performance trajectory of the
repo is recorded run-over-run (CI uploads these as artifacts). Every
record carries run metadata -- ``seed`` (``--seed N``, default 0, offsets
every driver's rng coherently), ``git_sha``, ``backend`` (the resolved
``REPRO_LSM_BACKEND``) and ``medium`` (the storage medium the row ran
on) -- so rows from different machines/checkouts stay attributable.
JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when set, else in ``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def run_metadata(seed: int) -> dict:
    """Provenance stamped onto every JSON row."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    return {"seed": seed, "git_sha": sha,
            "backend": os.environ.get("REPRO_LSM_BACKEND", "numpy")}


def parse_row(row: str) -> dict:
    """``name,value,derived`` -> a structured record. ``derived`` is a
    ``k=v;k=v`` string; numeric values are coerced, and the well-known
    keys (scheme, shards, stalls) are lifted to the top level."""
    name, value, derived = row.split(",", 2)
    rec: dict = {"name": name, "value": float(value)}
    fields: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            fields[k] = int(v)
        except ValueError:
            try:
                fields[k] = float(v)
            except ValueError:
                fields[k] = v
    rec["derived"] = fields
    for k in ("scheme", "shards", "stalls"):
        if k in fields:
            rec[k] = fields[k]
    if "throughput" not in fields and name.startswith("kv_serving/"):
        rec["throughput"] = rec["value"]
    return rec


def main() -> None:
    from . import (fig07_single_tree, fig08_memory_merge_overhead,
                   fig09_flush_heuristics, fig10_grouped_l0,
                   fig11_dynamic_levels, fig12_multi_primary,
                   fig13_secondary, fig14_tpcc, fig15_tuner_ycsb,
                   fig16_tuner_accuracy, fig17_tuner_responsiveness,
                   kv_serving, recovery)
    from repro.compile_cache import use_compile_cache
    use_compile_cache(os.path.join(os.path.dirname(__file__), os.pardir))
    full = "--full" in sys.argv
    smoke = "--smoke" in sys.argv
    json_out = "--json" in sys.argv
    seed = 0
    if "--seed" in sys.argv:
        seed = int(sys.argv[sys.argv.index("--seed") + 1])
        from .common import set_run_seed
        set_run_seed(seed)
    meta = run_metadata(seed)
    if smoke:
        modules = [fig07_single_tree, fig14_tpcc, fig15_tuner_ycsb,
                   kv_serving, recovery]
    else:
        modules = [fig07_single_tree, fig08_memory_merge_overhead,
                   fig09_flush_heuristics, fig10_grouped_l0,
                   fig11_dynamic_levels, fig12_multi_primary, fig13_secondary,
                   fig14_tpcc, fig15_tuner_ycsb, fig16_tuner_accuracy,
                   fig17_tuner_responsiveness, kv_serving, recovery]
    print("name,value,derived")
    for mod in modules:
        t0 = time.time()
        rows = list(mod.run(full=False, smoke=True) if smoke
                    else mod.run(full=full))
        for row in rows:
            print(row)
        elapsed = time.time() - t0
        print(f"# {mod.__name__}: {elapsed:.1f}s", file=sys.stderr)
        if json_out:
            short = mod.__name__.rsplit(".", 1)[-1]
            records = [parse_row(r) for r in rows]
            for rec in records:
                rec["preset"] = ("smoke" if smoke
                                 else "full" if full else "default")
                rec.update(meta)
                # rows name their medium when they ran on files; the
                # default engine configuration is the in-memory medium
                rec["medium"] = rec["derived"].get("medium", "memory")
            path = f"BENCH_{short}.json"
            with open(path, "w") as f:
                json.dump(records, f, indent=1)
            print(f"# wrote {path} ({len(records)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
