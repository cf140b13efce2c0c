"""Execution-backend interface for the LSM engine's hot loops.

A backend supplies the engine's data-parallel primitives:

  * ``merge_runs(runs)``     -- k-way newest-wins merge (compaction)
  * ``ingest_run(keys, vals)`` -- sort+dedup of one write batch (ingest)
  * ``bloom_build(keys)``    -- per-SSTable Bloom filter construction
  * ``bloom_probe(f, keys)`` -- batched membership probes
  * ``lookup_batch(sorted_keys, queries)`` -- batched binary search in a run
  * ``prepare_run(sorted_keys)`` / ``search_run(run, queries)`` -- the
    same search against a run kept resident between calls (a memory
    level of the partitioned memory component), so that each search
    moves only its queries
  * ``prepare_tier(tables, bloom_fn)`` / ``lookup_fused(view, queries)``
    -- the device-resident read hot path: one fused Bloom-probe +
    sorted-probe pipeline over a whole disjoint tier of SSTables, replacing
    the per-SSTable ``bloom_probe`` + ``lookup_batch`` staging
  * ``prepare_store(tiers, bloom_fn)`` / ``lookup_store_fused(view,
    queries)`` -- the cross-tier extension: every lookup tier of a tree
    stacked into one ragged device layout, probed (Bloom + ranged search
    + newest-wins tier argmin) in ONE device launch per lookup batch

``NumpyBackend`` carries the reference semantics; ``PallasBackend`` routes
the same primitives through the Pallas TPU kernels (interpret mode on CPU,
compiled on TPU). Both backends use the *same* Bloom geometry (hash family,
slot count, size bucketing) so their probe results -- including false
positives -- are bit-identical, which the parity suite relies on.

Selection: ``get_backend(name)`` resolves, in order, the explicit
``name`` (``StoreConfig.backend``), the ``REPRO_LSM_BACKEND``
environment variable, then the ``"numpy"`` default — so the env var
flips every store that does not pin a backend (e.g. the stock
benchmarks) without silently overriding code that chose one.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

from ...kernels.sizing import next_pow2, slots_for  # jax-free module
from ...runtime import tracing

ENV_VAR = "REPRO_LSM_BACKEND"

# Shared Bloom geometry (matches kernels/bloom: 10 bits/key, 7 hashes).
BLOOM_BITS_PER_KEY = 10
BLOOM_K_HASHES = 7


def bloom_sizing(n_keys: int, bits_per_key: int = BLOOM_BITS_PER_KEY):
    """(padded_key_count, n_slots) for a filter over ``n_keys`` keys.

    Both backends size filters from the *bucketed* key count so a filter
    built by one backend has the same geometry (and false-positive set) as
    one built by the other.
    """
    n_pad = next_pow2(max(1, n_keys), lo=256)
    return n_pad, slots_for(n_pad, bits_per_key)


@dataclass
class TierView:
    """One disjoint, min_key-sorted tier of SSTables prepared for fused
    probing (built by ``ExecutionBackend.prepare_tier``).

    The host-side metadata is backend-independent; ``payload`` carries the
    backend's resident representation of the tier's key/val/Bloom pages
    (numpy concatenations for the reference backend, device arrays for the
    Pallas backend -- the part a ``DevicePagePool`` keeps HBM-resident).
    """

    backend: str
    sst_ids: tuple                 # view identity (pool cache key)
    starts: np.ndarray             # int64 [T] per-table min_key
    ends: np.ndarray               # int64 [T] per-table max_key
    offs: np.ndarray               # int64 [T] entry offset of each table
    lens: np.ndarray               # int64 [T] entries per table
    payload: object                # backend-owned resident arrays

    @property
    def num_tables(self) -> int:
        return len(self.sst_ids)

    @property
    def num_entries(self) -> int:
        return int(self.offs[-1] + self.lens[-1]) if len(self.lens) else 0


@dataclass
class SortedRun:
    """One sorted unique run kept for repeated searches (built by
    ``ExecutionBackend.prepare_run``): its host keys, plus the backend's
    resident copy, or None where the backend searches on the host."""

    keys: np.ndarray
    payload: object = None


@dataclass
class FusedLookup:
    """Per-query results of one fused tier probe, shaped so the caller can
    replicate the staged path's page-pin accounting exactly:

      ti/ok     -- table assignment (``assign_queries`` semantics);
      positive  -- Bloom membership of each query against its table's
                   filter (valid where ``ok``);
      pos/hit   -- binary-search insertion position *relative to the
                   table's run* and whether it is an exact match (valid
                   where ``ok & positive``);
      vals      -- the matched payload (valid where ``hit``).
    """

    ti: np.ndarray                 # int64 [K]
    ok: np.ndarray                 # bool  [K]
    positive: np.ndarray           # bool  [K]
    pos: np.ndarray                # int64 [K]
    hit: np.ndarray                # bool  [K]
    vals: np.ndarray               # int64 [K]


@dataclass
class StoreView:
    """Every lookup tier of one tree (newest-first: L0 groups, then disk
    levels top-down) prepared for a single fused probe (built by
    ``ExecutionBackend.prepare_store``).

    Per-tier metadata mirrors ``TierView`` -- tuples indexed by tier rank
    ``r`` -- except that ``tier_offs`` are offsets into the *store-wide*
    key/val concatenation (tier-major, table order within a tier).
    ``payload`` is the backend's resident representation of the whole
    stack; the ``DevicePagePool`` accounts its pages exactly like a
    per-tier view's.
    """

    backend: str
    key: tuple                     # tuple of per-tier sst_id tuples
    tier_starts: tuple             # per tier: int64 [T_r] min_key
    tier_ends: tuple               # per tier: int64 [T_r] max_key
    tier_offs: tuple               # per tier: int64 [T_r] GLOBAL offsets
    tier_lens: tuple               # per tier: int64 [T_r] entries/table
    payload: object                # backend-owned resident arrays

    @property
    def num_tiers(self) -> int:
        return len(self.key)

    @property
    def num_tables(self) -> int:
        return sum(len(k) for k in self.key)


@dataclass
class StoreLookup:
    """Per-(tier, query) results of one fused store probe. Every [R, K]
    field carries, for tier rank ``r``, exactly what a per-tier
    ``FusedLookup`` would have carried for that tier (``ti`` is
    tier-local), so the caller can replay the staged path's pin sequence
    tier by tier. ``win`` is the on-device newest-wins resolution: the
    first (newest) tier rank whose probe hit, -1 when no tier did."""

    ti: np.ndarray                 # int64 [R, K] tier-local table index
    ok: np.ndarray                 # bool  [R, K]
    positive: np.ndarray           # bool  [R, K]
    pos: np.ndarray                # int64 [R, K] relative to the table's run
    hit: np.ndarray                # bool  [R, K]
    vals: np.ndarray               # int64 [R, K]
    win: np.ndarray                # int64 [K] first tier rank with a hit


def assign_bounds(starts, ends, qkeys):
    """Array-level twin of ``sstable.assign_queries``: map each query to
    the covering table of a disjoint, min_key-sorted tier described by its
    bound arrays. Shared by both backends' fused paths so assignment is
    bit-identical to the staged probe."""
    ti = np.searchsorted(starts, qkeys, side="right") - 1
    ok = ti >= 0
    ti = np.clip(ti, 0, len(starts) - 1)
    ok &= qkeys <= ends[ti]
    return ti.astype(np.int64), ok


class _JitCall:
    """Marks one jitted backend call: the JAX compile events the calling
    thread raises inside it count as compiles, and a call that raised
    none as a cache hit."""

    __slots__ = ("backend", "c0")

    def __init__(self, backend):
        self.backend = backend

    def __enter__(self):
        self.c0 = tracing.compile_events()

    def __exit__(self, *exc):
        n = tracing.compile_events() - self.c0
        if n:
            self.backend.jit_compiles += n
        else:
            self.backend.jit_cache_hits += 1
        return False


class ExecutionBackend:
    """Interface of the engine's batched primitives.

    Backends also keep compile counters (``jit_compiles`` /
    ``jit_cache_hits``): every jitted entry point runs its device work
    inside ``with self._note_jit():``, which counts the JAX compile
    events (programs built or loaded from a persistent cache) raised
    inside it, or one cache hit for a call that raised none. The
    reference backend jits nothing, so its counters stay zero;
    benchmarks surface the deltas so recompile churn (e.g. the fused
    read path's tier stacks) is observable in ``BENCH_*.json`` rows.
    """

    name: str = "abstract"

    def __init__(self):
        self.jit_compiles = 0
        self.jit_cache_hits = 0

    def _note_jit(self) -> _JitCall:
        """Mark one jitted call (a context manager around it)."""
        return _JitCall(self)

    def jit_stats(self) -> dict:
        return {"jit_compiles": self.jit_compiles,
                "jit_cache_hits": self.jit_cache_hits}

    def merge_runs(self, runs):
        """Merge sorted (keys, vals) runs, ordered newest-first, into one
        sorted unique run with newest-wins reconciliation.

        Callers may pass one run per table: the Pallas backend's fold
        merges each group of consecutive key-disjoint runs (a level's
        tables) into the accumulated run once, piecewise, rather than
        once per run (``kernels.merge.ops.merge_runs_device``).

        Returns (keys, vals) as int64 numpy arrays.
        """
        raise NotImplementedError

    def ingest_run(self, keys, vals):
        """Sort an *unsorted* write batch into one sorted unique run with
        last-occurrence-wins dedup (the write-ingest mirror of
        ``merge_runs``).

        Returns (keys, vals, src) as int64 numpy arrays: the sorted unique
        keys, the value of each key's newest occurrence, and ``src`` -- the
        original batch position of that occurrence (callers derive exact
        per-entry LSNs from it).
        """
        raise NotImplementedError

    def bloom_build(self, keys):
        """Build a Bloom filter over ``keys``; returns an opaque filter."""
        raise NotImplementedError

    def bloom_probe(self, filt, keys):
        """Probe ``filt`` for ``keys``; returns a bool membership mask
        (no false negatives)."""
        raise NotImplementedError

    def lookup_batch(self, sorted_keys, queries):
        """Batched binary search of ``queries`` in a sorted unique run.

        Returns (pos, found): the insertion position of each query (int64)
        and whether ``sorted_keys[pos] == query`` (bool).
        """
        raise NotImplementedError

    def prepare_run(self, sorted_keys) -> SortedRun:
        """Keep a sorted unique run resident for ``search_run``."""
        raise NotImplementedError

    def search_run(self, run: SortedRun, queries):
        """``lookup_batch(run.keys, queries)`` against a run prepared
        once by ``prepare_run``: same contract, same results."""
        raise NotImplementedError

    def prepare_tier(self, tables, bloom_fn):
        """Build a resident ``TierView`` over one disjoint, min_key-sorted
        tier of SSTables. ``bloom_fn(sst)`` returns the backend's (cached)
        Bloom filter of a table. Returns ``None`` when the tier cannot be
        made resident (e.g. keys/values outside the kernel domain); the
        caller then stays on the staged path."""
        raise NotImplementedError

    def lookup_fused(self, view: TierView, queries):
        """Fused tier probe: Bloom probe + per-table sorted probe of every
        query against the whole tier in one (or few) device invocations.

        Must be bit-identical -- assignment, Bloom membership (including
        false positives), insertion positions, matches, values -- to the
        staged loop of per-table ``bloom_probe`` + ``lookup_batch`` calls.
        Returns a ``FusedLookup``, or ``None`` when the queries fall
        outside the backend's domain (caller falls back to staged)."""
        raise NotImplementedError

    def prepare_store(self, tiers, bloom_fn):
        """Build a resident ``StoreView`` over every non-empty lookup tier
        of one tree, ordered newest-first. Each element of ``tiers`` is a
        disjoint, min_key-sorted table list (what ``prepare_tier`` takes).
        Returns ``None`` when the stack cannot be made resident (any tier
        outside the kernel domain); the caller then falls back to the
        per-tier fused path, and from there to staged."""
        raise NotImplementedError

    def lookup_store_fused(self, view: StoreView, queries):
        """Fused cross-tier probe: every query against every tier of the
        store in ONE device launch -- stacked Bloom probe, ranged sorted
        probe over the store-wide concatenation, and the newest-wins tier
        argmin, composed in a single jitted invocation.

        Field-for-field per tier, results must be bit-identical to R
        independent ``lookup_fused`` calls (which are themselves
        bit-identical to the staged loop). Returns a ``StoreLookup``, or
        ``None`` when the queries fall outside the backend's domain."""
        raise NotImplementedError


_FACTORIES: dict = {}
_INSTANCES: dict = {}


def register_backend(name: str, factory) -> None:
    _FACTORIES[name] = factory


def available_backends() -> tuple:
    """Registered backend names (the registry is the source of truth)."""
    return tuple(sorted(_FACTORIES))


def get_backend(name: str | None = None) -> ExecutionBackend:
    """Resolve a backend by name: explicit name > env var > "numpy".

    Instances are cached (backends are stateless apart from jit caches).
    """
    resolved = name or os.environ.get(ENV_VAR) or "numpy"
    if resolved not in _FACTORIES:
        raise ValueError(
            f"unknown LSM backend {resolved!r}; expected one of "
            f"{sorted(_FACTORIES)}")
    if resolved not in _INSTANCES:
        _INSTANCES[resolved] = _FACTORIES[resolved]()
    return _INSTANCES[resolved]
