"""Reference execution backend: pure numpy, no jax imports.

Carries the engine's original semantics (the k-way merge extracted from
``sstable.merge_runs``) plus a real double-hashed Bloom filter whose hash
math mirrors ``kernels/bloom/ref.py`` exactly (same Knuth multipliers, same
int32 wraparound, same slot layout) so probe results match the Pallas
backend bit-for-bit.
"""
from __future__ import annotations

import numpy as np

from .backend import (BLOOM_K_HASHES, ExecutionBackend, FusedLookup,
                      SortedRun, StoreLookup, StoreView, TierView,
                      assign_bounds, bloom_sizing, register_backend)

# Same int32 constants as kernels/bloom/ref.py (golden-ratio multipliers).
C1 = np.int32(0x9E3779B1 - 2**32)
C2 = np.int32(0x85EBCA77 - 2**32)


def merge_runs_numpy(runs):
    """Merge sorted (keys, vals) runs with newest-wins reconciliation.

    ``runs`` is ordered newest-first. Returns a single sorted, unique run.
    """
    runs = [r for r in runs if len(r[0])]
    if not runs:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    if len(runs) == 1:
        return runs[0]
    keys = np.concatenate([r[0] for r in runs])
    vals = np.concatenate([r[1] for r in runs])
    # Stable sort by key keeps the newest occurrence first within equal keys
    # because runs are concatenated newest-first.
    order = np.argsort(keys, kind="stable")
    keys, vals = keys[order], vals[order]
    keep = np.ones(len(keys), bool)
    keep[1:] = keys[1:] != keys[:-1]
    return keys[keep], vals[keep]


def ingest_order(keys) -> np.ndarray:
    """Canonical ingest ordering of a write batch: positions sorted by key,
    newest (highest batch position) first among equal keys.

    Shared by both backends so the pre-kernel ordering -- and therefore
    which duplicate survives -- is identical everywhere.
    """
    n = len(keys)
    rev = np.argsort(keys[::-1], kind="stable")
    return (n - 1) - rev


def _bloom_slots(keys, n_slots: int, k_hashes: int) -> np.ndarray:
    """[K, k] slot indices; int32 wraparound arithmetic matches the jnp
    oracle in kernels/bloom/ref.py."""
    k32 = np.asarray(keys).astype(np.int32)
    h1 = (k32 * C1) % np.int32(n_slots)
    h2 = ((k32 * C2) | np.int32(1)) % np.int32(n_slots)
    j = np.arange(k_hashes, dtype=np.int64)
    return (h1.astype(np.int64)[:, None] + j[None, :]
            * h2.astype(np.int64)[:, None]) % n_slots


def lower_bound_ranged(concat_keys, lo, hi, queries):
    """Vectorized per-query lower-bound binary search of ``queries[i]``
    within ``concat_keys[lo[i]:hi[i]]`` (each slice sorted). Returns the
    *absolute* insertion positions -- exactly ``lo[i] +
    searchsorted(concat_keys[lo[i]:hi[i]], queries[i])``.

    The reference semantics of the fused sorted probe, shared with the
    device route (``kernels.merge.ops.lookup_runs_device``)."""
    lo = lo.astype(np.int64).copy()
    hi = hi.astype(np.int64).copy()
    n = len(concat_keys)
    while True:
        open_ = lo < hi
        if not open_.any():
            break
        mid = (lo + hi) >> 1
        less = np.zeros(len(queries), bool)
        idx = np.minimum(mid[open_], max(n - 1, 0))
        less[open_] = concat_keys[idx] < queries[open_]
        lo = np.where(open_ & less, mid + 1, lo)
        hi = np.where(open_ & ~less, mid, hi)
    return lo


class NumpyBackend(ExecutionBackend):
    name = "numpy"

    def __init__(self, *, k_hashes: int = BLOOM_K_HASHES):
        super().__init__()
        self.k_hashes = k_hashes

    def merge_runs(self, runs):
        return merge_runs_numpy(runs)

    def ingest_run(self, keys, vals):
        keys = np.asarray(keys, np.int64)
        vals = np.asarray(vals, np.int64)
        n = len(keys)
        if n == 0:
            return keys, vals, np.empty(0, np.int64)
        src = ingest_order(keys)
        ks = keys[src]
        keep = np.ones(n, bool)
        keep[1:] = ks[1:] != ks[:-1]        # newest-first: keep the first
        src = src[keep]
        return ks[keep], vals[src], src

    def bloom_build(self, keys):
        # Membership bits only (bool, not counts): filters are cached per
        # SSTable for the table's lifetime, so resident size matters.
        _, n_slots = bloom_sizing(len(keys))
        slots = _bloom_slots(keys, n_slots, self.k_hashes).reshape(-1)
        filt = np.zeros(n_slots, bool)
        filt[slots] = True
        return filt

    def bloom_probe(self, filt, keys):
        if len(keys) == 0:
            return np.zeros(0, bool)
        slots = _bloom_slots(keys, filt.shape[0], self.k_hashes)
        return filt[slots].all(axis=-1)

    def lookup_batch(self, sorted_keys, queries):
        pos = np.searchsorted(sorted_keys, queries)
        inb = pos < len(sorted_keys)
        found = np.zeros(len(queries), bool)
        safe = np.minimum(pos, len(sorted_keys) - 1)
        found[inb] = sorted_keys[safe[inb]] == np.asarray(queries)[inb]
        return pos.astype(np.int64), found

    def prepare_run(self, sorted_keys):
        """The run stays on the host: nothing to copy."""
        return SortedRun(np.asarray(sorted_keys))

    def search_run(self, run, queries):
        return self.lookup_batch(run.keys, queries)

    # -- fused tier probe ----------------------------------------------------
    def prepare_tier(self, tables, bloom_fn):
        """Host-resident tier view: concatenated key/val runs plus the
        tier's flat Bloom bits. Never refuses (the reference path has no
        domain limits)."""
        filts = [np.asarray(bloom_fn(t)) for t in tables]
        f_lens = np.array([len(f) for f in filts], np.int64)
        f_offs = np.concatenate([[0], np.cumsum(f_lens)[:-1]])
        lens = np.array([t.num_entries for t in tables], np.int64)
        offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
        payload = {
            "keys": np.concatenate([t.keys for t in tables]),
            "vals": np.concatenate([t.vals for t in tables]),
            "fbits": np.concatenate(filts),
            "f_offs": f_offs,
            "nslots": f_lens,
        }
        return TierView(
            backend=self.name,
            sst_ids=tuple(t.sst_id for t in tables),
            starts=np.array([t.min_key for t in tables], np.int64),
            ends=np.array([t.max_key for t in tables], np.int64),
            offs=offs, lens=lens, payload=payload)

    def lookup_fused(self, view, queries):
        """One vectorized pass over the whole tier: per-query table
        assignment, Bloom probe against each query's own table filter
        (bit-identical hash math to ``bloom_probe``, per-table slot
        counts applied element-wise), ranged lower-bound search in the
        concatenated runs, and payload gather."""
        q = np.asarray(queries, np.int64)
        p = view.payload
        ti, ok = assign_bounds(view.starts, view.ends, q)
        # Bloom: same double-hash int32 wraparound as _bloom_slots, with
        # each query's modulus taken from its assigned table's filter.
        n64 = p["nslots"][ti]
        n32 = n64.astype(np.int32)
        k32 = q.astype(np.int32)
        h1 = (k32 * C1) % n32
        h2 = ((k32 * C2) | np.int32(1)) % n32
        j = np.arange(self.k_hashes, dtype=np.int64)
        slots = (h1.astype(np.int64)[:, None]
                 + j[None, :] * h2.astype(np.int64)[:, None]) % n64[:, None]
        positive = p["fbits"][p["f_offs"][ti][:, None] + slots].all(axis=-1)
        # Sorted probe, confined to each query's table slice. The tier's
        # tables are disjoint and min_key-ordered, so the concatenation is
        # globally sorted: one C-level searchsorted clipped to the slice
        # is exactly ``lower_bound_ranged`` (inside the slice both agree;
        # outside, the ranged search clamps to the bound it clipped to).
        lo = view.offs[ti]
        lens = view.lens[ti]
        abs_pos = np.clip(np.searchsorted(p["keys"], q), lo, lo + lens)
        pos = abs_pos - lo
        inb = pos < lens
        safe = np.minimum(abs_pos, len(p["keys"]) - 1)
        hit = np.zeros(len(q), bool)
        hit[inb] = p["keys"][safe[inb]] == q[inb]
        vals = np.where(hit, p["vals"][safe], 0).astype(np.int64)
        return FusedLookup(ti=ti, ok=ok, positive=positive,
                           pos=pos.astype(np.int64), hit=hit, vals=vals)

    # -- fused store (cross-tier) probe --------------------------------------
    def prepare_store(self, tiers, bloom_fn):
        """Host-resident cross-tier view: the whole store's key/val runs
        and Bloom bits in one tier-major concatenation. Never refuses."""
        tables = [t for tier in tiers for t in tier]
        filts = [np.asarray(bloom_fn(t)) for t in tables]
        f_lens = np.array([len(f) for f in filts], np.int64)
        f_offs = np.cumsum(f_lens) - f_lens
        lens = np.array([t.num_entries for t in tables], np.int64)
        offs = np.cumsum(lens) - lens
        counts = np.array([len(tier) for tier in tiers], np.int64)
        t_off = np.cumsum(counts) - counts
        cat = lambda arrs, dt: (np.concatenate(arrs) if arrs  # noqa: E731
                                else np.zeros(0, dt))
        payload = {
            "keys": cat([t.keys for t in tables], np.int64),
            "vals": cat([t.vals for t in tables], np.int64),
            "fbits": cat(filts, bool),
            "f_offs": f_offs,
            "nslots": f_lens,
            "t_off": t_off,           # tier rank -> first global table index
        }
        return StoreView(
            backend=self.name,
            key=tuple(tuple(t.sst_id for t in tier) for tier in tiers),
            tier_starts=tuple(np.array([t.min_key for t in tier], np.int64)
                              for tier in tiers),
            tier_ends=tuple(np.array([t.max_key for t in tier], np.int64)
                            for tier in tiers),
            tier_offs=tuple(offs[t_off[r]:t_off[r] + counts[r]]
                            for r in range(len(tiers))),
            tier_lens=tuple(lens[t_off[r]:t_off[r] + counts[r]]
                            for r in range(len(tiers))),
            payload=payload)

    def lookup_store_fused(self, view, queries):
        """One vectorized pass over the whole store: per-tier table
        assignment (same ``assign_bounds`` as the per-tier path), one
        [R, K] Bloom gather, ONE ranged lower-bound search over the
        store-wide concatenation, and the newest-wins tier argmin --
        field-for-field identical to R independent ``lookup_fused``
        calls."""
        q = np.asarray(queries, np.int64)
        p = view.payload
        R, K = view.num_tiers, len(q)
        if R == 0:
            return StoreLookup(
                ti=np.zeros((0, K), np.int64), ok=np.zeros((0, K), bool),
                positive=np.zeros((0, K), bool),
                pos=np.zeros((0, K), np.int64), hit=np.zeros((0, K), bool),
                vals=np.zeros((0, K), np.int64),
                win=np.full(K, -1, np.int64))
        ti = np.empty((R, K), np.int64)
        ok = np.empty((R, K), bool)
        for r in range(R):
            ti[r], ok[r] = assign_bounds(view.tier_starts[r],
                                         view.tier_ends[r], q)
        gti = p["t_off"][:, None] + ti              # global table index [R,K]
        # Bloom: identical hash math to lookup_fused, flattened over (r, k).
        n64 = p["nslots"][gti]
        n32 = n64.astype(np.int32)
        k32 = np.broadcast_to(q.astype(np.int32), (R, K))
        h1 = (k32 * C1) % n32
        h2 = ((k32 * C2) | np.int32(1)) % n32
        j = np.arange(self.k_hashes, dtype=np.int64)
        slots = (h1.astype(np.int64)[..., None]
                 + j * h2.astype(np.int64)[..., None]) % n64[..., None]
        positive = p["fbits"][p["f_offs"][gti][..., None]
                              + slots].all(axis=-1)
        # Sorted probe per tier: each tier's segment of the store-wide
        # concatenation is itself globally sorted (disjoint,
        # min_key-ordered tables), so one C-level searchsorted per tier
        # clipped to each query's table slice is exactly the ranged lower
        # bound ``lower_bound_ranged`` computes (inside the slice both
        # agree; outside, the ranged search clamps to the clipped bound).
        abs_pos = np.empty((R, K), np.int64)
        for r in range(R):
            s0 = int(view.tier_offs[r][0])
            s1 = s0 + int(view.tier_lens[r].sum())
            abs_pos[r] = s0 + np.searchsorted(p["keys"][s0:s1], q)
        lo = np.stack([view.tier_offs[r][ti[r]] for r in range(R)])
        lens = np.stack([view.tier_lens[r][ti[r]] for r in range(R)])
        np.clip(abs_pos, lo, lo + lens, out=abs_pos)
        pos = abs_pos - lo
        inb = pos < lens
        safe = np.minimum(abs_pos, len(p["keys"]) - 1)
        hit = np.zeros((R, K), bool)
        qb = np.broadcast_to(q, (R, K))
        hit[inb] = p["keys"][safe[inb]] == qb[inb]
        vals = np.where(hit, p["vals"][safe], 0).astype(np.int64)
        # Newest-wins: first (lowest-rank) tier with a hit; a query can
        # match at most one table per tier (tiers are disjoint).
        win = np.where(hit.any(axis=0),
                       np.argmax(hit, axis=0), -1).astype(np.int64)
        return StoreLookup(ti=ti, ok=ok, positive=positive,
                           pos=pos.astype(np.int64), hit=hit, vals=vals,
                           win=win)


register_backend("numpy", NumpyBackend)
