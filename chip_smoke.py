"""Chip smoke test: the LSM store's Pallas path, end to end, on one TPU.

Loads ``--records`` (default 4,194,304) key-value records through
``StorageService.submit`` ``Put`` batches into a default-configuration
store (1 KB entries, 2 MB SSTables, 128 MB write memory) on the Pallas
backend with the device page pool sized to hold every lookup tier. It
then serves ``Get`` batches (hits, misses and just-overwritten keys) and
a batch of ``Scan`` requests, checking every answer against a plain dict
oracle of the same operations, and checks the kernels against the numpy
reference backend on SSTables of the loaded store.

The run fails -- non-zero exit, no result line -- if JAX finds no TPU,
if the backend would interpret its kernels, if any backend call fell
back to the numpy reference, if the fused store read never ran, or if
any phase raises. On success the last line of standard output is::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

Run from the repository root: ``python chip_smoke.py``. The persistent
compilation cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

TREE = "kv"
GET_BATCH = 4096
PUT_BATCH = 8192
KEY_SPACE = 2**31 - 1            # the kernels' int32 key domain


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _device_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats.get("bytes_in_use", 0))


def _distinct_keys(rng, n: int, exclude=None) -> np.ndarray:
    """``n`` distinct keys in the int32 domain, in random order, none of
    them in the sorted array ``exclude``."""
    out = np.empty(0, np.int64)
    while len(out) < n:
        draw = rng.integers(0, KEY_SPACE, size=2 * (n - len(out)) + 16)
        out = np.unique(np.concatenate([out, draw]))
        if exclude is not None and len(exclude):
            out = out[~np.isin(out, exclude, assume_unique=True)]
    return rng.permutation(out)[:n]


def _values(rng, n: int) -> np.ndarray:
    # Positive int32 payloads: never the (negative) tombstone.
    return rng.integers(1, 2**31 - 1, size=n, dtype=np.int64)


def _put(svc, oracle: dict, keys, vals) -> None:
    from repro.core.service import Put, WriteAck
    cfg = svc.store.cfg
    # A batch stays well inside the write-memory admission slack.
    batch = min(PUT_BATCH, cfg.write_memory_bytes // cfg.entry_bytes // 4)
    for a in range(0, len(keys), batch):
        k, v = keys[a:a + batch], vals[a:a + batch]
        (ack,) = svc.submit_strict([Put(TREE, k, v)])
        _check(isinstance(ack, WriteAck) and ack.n == len(k),
               f"Put of {len(k)} keys acknowledged as {ack!r}")
        oracle.update(zip(k.tolist(), v.tolist()))


def _get(svc, oracle: dict, keys) -> None:
    from repro.core.service import Get
    for a in range(0, len(keys), GET_BATCH):
        k = keys[a:a + GET_BATCH]
        (res,) = svc.submit([Get(TREE, k)])
        want = [oracle.get(x) for x in k.tolist()]
        found = np.array([w is not None for w in want])
        vals = np.array([0 if w is None else w for w in want], np.int64)
        bad = np.flatnonzero((res.found != found)
                             | (res.found & (res.vals != vals)))
        _check(not len(bad),
               f"{len(bad)} of {len(k)} Get answers disagree with the "
               f"oracle, e.g. key {int(k[bad[0]]) if len(bad) else None}")


def _scan(svc, oracle_keys: np.ndarray, los, width: int) -> None:
    from repro.core.service import Scan
    res = svc.submit([Scan(TREE, int(lo), width) for lo in los])
    want = (np.searchsorted(oracle_keys, los + width)
            - np.searchsorted(oracle_keys, los))
    got = np.array([r.count for r in res])
    _check(np.array_equal(got, want),
           f"Scan counts {got.tolist()} != oracle {want.tolist()}")


def _kernel_parity(store, rng) -> int:
    """Bloom build/probe and run merge of the Pallas backend against the
    numpy reference on SSTables of the loaded store, false positives
    included. Returns the number of tables compared."""
    from repro.core.engine import NumpyBackend
    be, ref = store.backend, NumpyBackend(k_hashes=store.backend.k_hashes)
    tree = store.trees[TREE]
    tables = [t for tier in tree.levels.lookup_tiers() for t in tier]
    _check(len(tables) >= 2, "fewer than two disk SSTables to compare")
    pick = [tables[i] for i in rng.choice(len(tables), 4, replace=False)] \
        if len(tables) >= 4 else tables
    probes = np.concatenate([pick[0].keys[:512],
                             rng.integers(0, KEY_SPACE, 3584)])
    for t in pick:
        kind, bits = be.bloom_build(t.keys)
        _check(kind == "pallas", f"bloom_build of {t.sst_id} fell back")
        want = ref.bloom_build(t.keys)
        _check(np.array_equal(bits.reshape(-1), want != 0),
               f"Bloom filter of SSTable {t.sst_id} differs from numpy")
        _check(np.array_equal(be.bloom_probe((kind, bits), probes),
                              ref.bloom_probe(want, probes)),
               f"Bloom probe of SSTable {t.sst_id} differs from numpy")
    runs = [(t.keys, t.vals) for t in pick[:2]]
    got, want = be.merge_runs(runs), ref.merge_runs(runs)
    _check(all(np.array_equal(a, b) for a, b in zip(got, want)),
           "merge_runs differs from numpy")
    return len(pick)


def run(n_records: int, *, seed: int = 0, log=print, **store_kw) -> dict:
    """Drive the store's Pallas path once at ``n_records`` and check every
    answer. ``store_kw`` overrides ``StoreConfig`` sizes, so a small run
    still flushes and forms levels. Raises ``SmokeFailure`` on any
    disagreement or fallback; returns the counters it printed."""
    from repro.core import StoreConfig
    from repro.core.service import StorageService

    rng = np.random.default_rng(seed)
    entry_bytes = StoreConfig.entry_bytes
    # Page-pool budget (accounting bytes): every page of every table plus
    # one Bloom unit each, with room for the merges in flight.
    cfg = StoreConfig(backend="pallas",
                      device_pool_bytes=4 * n_records * entry_bytes,
                      **store_kw)
    svc = StorageService.open(cfg)
    store = svc.store
    be = store.backend
    _check(be.name == "pallas", f"backend resolved to {be.name!r}")
    log(f"backend: pallas, interpret={be.interpret}, device={be.device}")
    fallback0, compiles0 = be.fallback_calls, be.jit_compiles
    svc.create_tree(TREE)
    oracle: dict = {}
    phases: dict = {}

    t = time.perf_counter()
    keys = _distinct_keys(rng, n_records)
    vals = _values(rng, n_records)
    _put(svc, oracle, keys, vals)
    svc.drain()
    phases["load"] = time.perf_counter() - t
    tree = store.trees[TREE]
    levels = tree.levels.lookup_tiers()
    n_tables = sum(len(tier) for tier in tree.l0.lookup_tiers() + levels)
    log(f"loaded {n_records} records: {len(levels)} disk levels, "
        f"{tree.l0.num_groups} L0 groups, {n_tables} SSTables")

    t = time.perf_counter()
    hits = rng.choice(keys, size=min(len(keys), 4 * GET_BATCH),
                      replace=False)
    _get(svc, oracle, hits)              # cold pool: admits, then staged
    _get(svc, oracle, hits)              # resident: fused store path
    misses = _distinct_keys(rng, 2 * GET_BATCH, np.sort(keys))
    _get(svc, oracle, misses)
    phases["get"] = time.perf_counter() - t

    t = time.perf_counter()
    over = rng.choice(keys, size=min(len(keys), GET_BATCH), replace=False)
    _put(svc, oracle, over, _values(rng, len(over)))
    _get(svc, oracle, np.concatenate([over, hits[:GET_BATCH]]))
    phases["overwrite"] = time.perf_counter() - t

    t = time.perf_counter()
    oracle_keys = np.sort(np.fromiter(oracle, np.int64, len(oracle)))
    width = max(1, int(KEY_SPACE // max(1, n_records)) * 64)
    _scan(svc, oracle_keys, rng.choice(oracle_keys, 8, replace=False),
          width)
    phases["scan"] = time.perf_counter() - t

    t = time.perf_counter()
    n_cmp = _kernel_parity(store, rng)
    phases["parity"] = time.perf_counter() - t

    st, pool = store.disk.stats, store.device_pool.stats()
    out = {
        "records": n_records,
        "disk_levels": len(levels),
        "sstables": n_tables,
        "parity_tables": n_cmp,
        "interpret": be.interpret,
        "fallback_calls": be.fallback_calls - fallback0,
        "fused_launches": st.fused_launches,
        "pool_store_hits": pool["store_hits"],
        "jit_compiles": be.jit_compiles - compiles0,
        "device_bytes_in_use": _device_bytes(),
        "seconds": {k: round(v, 3) for k, v in phases.items()},
    }
    for k, v in out.items():
        log(f"{k}: {v}")
    _check(out["fallback_calls"] == 0,
           f"{out['fallback_calls']} backend calls fell back to numpy")
    _check(out["fused_launches"] > 0, "the fused store read never ran")
    _check(out["pool_store_hits"] > 0, "the device pool served no store")
    _check(len(levels) >= 2, f"only {len(levels)} disk levels formed")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--records", type=int, default=1 << 22)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache(ROOT)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f", compile cache {cache}")
    from repro.core import get_backend
    if get_backend("pallas").interpret:
        raise SmokeFailure("the Pallas backend would interpret its kernels")
    run(args.records, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
