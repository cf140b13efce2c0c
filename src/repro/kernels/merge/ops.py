"""jit'd wrappers: full sorted-run merge composed from kernel tile merges.

``merge_sorted_runs`` merges two sorted non-negative int32 runs:
merge-path *diagonal* splits (vectorized binary search, one per output
tile) bound every tile's work to exactly ``tile`` outputs, then the Pallas
kernel merges each co-tile pair in VMEM. Ties resolve toward run A (the
newer run); the global keep-mask drops duplicate keys (reconciliation).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...runtime import tracing
from ..bloom.ref import C1, C2
from ..sizing import next_pow2
from ..transfer import padded, to_device, to_host
from .merge import merge_tiles
from .ref import merge_tiles_ref

INT_MAX = np.int32(2**31 - 1)
# Largest tile whose merge kernel fits the TPU's default scoped VMEM
# (16 MiB): at 512 the [tile, 2*tile] one-hot scatter needs about 18 MiB.
MERGE_TILE = 256


def _diag_splits(ka, kb, diags):
    """For each output diagonal d, the largest ai with ka[ai-1] <= kb[d-ai]
    (run-A priority). Vectorized binary search (max-true)."""
    na, nb = ka.shape[0], kb.shape[0]
    lo = jnp.maximum(0, diags - nb)
    hi = jnp.minimum(diags, na)

    int_min = np.int32(-2**31)

    def a_at(i):        # ka[i-1], -inf sentinel below the run
        return jnp.where(i <= 0, int_min, ka[jnp.clip(i - 1, 0, na - 1)])

    def b_at(i):        # kb[i], +inf sentinel past the run
        return jnp.where(i >= nb, INT_MAX, kb[jnp.clip(i, 0,
                                               max(nb - 1, 0))])

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        ok = a_at(mid) <= b_at(diags - mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return lo


def _gather_window(x, starts, lens, width, fill):
    idx = starts[:, None] + jnp.arange(width)[None, :]
    valid = jnp.arange(width)[None, :] < lens[:, None]
    safe = jnp.clip(idx, 0, max(x.shape[0] - 1, 0))
    return jnp.where(valid, x[safe], fill)


@partial(jax.jit, static_argnames=("tile", "use_kernel", "interpret"))
def merge_sorted_runs(ka, va, kb, vb, *, tile: int = MERGE_TILE,
                      use_kernel: bool = True, interpret: bool = True):
    """Merge two sorted non-negative int32 runs with newest-wins dedup.

    Returns (keys [ceil((Na+Nb)/tile)*tile], vals, keep); padding slots
    carry key=INT_MAX and keep=False.
    """
    na, nb = ka.shape[0], kb.shape[0]
    if na == 0 or nb == 0:                  # degenerate: copy the other run
        keys = jnp.concatenate([ka, kb])
        vals = jnp.concatenate([va, vb])
        g0 = max(1, -(-keys.shape[0] // tile))
        pad = g0 * tile - keys.shape[0]
        keys = jnp.pad(keys, (0, pad), constant_values=INT_MAX)
        vals = jnp.pad(vals, (0, pad))
        return keys, vals, keys != INT_MAX
    n = na + nb
    g = -(-n // tile)
    diags = jnp.minimum(jnp.arange(g + 1) * tile, n)
    ai = _diag_splits(ka, kb, diags)
    bi = diags - ai
    a_len, b_len = jnp.diff(ai), jnp.diff(bi)
    ka_t = _gather_window(ka, ai[:-1], a_len, tile, INT_MAX)
    va_t = _gather_window(va, ai[:-1], a_len, tile, 0)
    kb_t = _gather_window(kb, bi[:-1], b_len, tile, INT_MAX)
    vb_t = _gather_window(vb, bi[:-1], b_len, tile, 0)
    if use_kernel:
        keys2, vals2, _ = merge_tiles(ka_t, va_t, kb_t, vb_t,
                                      interpret=interpret)
    else:
        keys2, vals2, _ = merge_tiles_ref(ka_t, va_t, kb_t, vb_t)
    keys = keys2[:, :tile].reshape(-1)      # first `tile` outputs are real
    vals = vals2[:, :tile].reshape(-1)
    with jax.named_scope("keep_mask"):
        prev = jnp.concatenate([keys[:1] - 1, keys[:-1]])
        keep = (keys != prev) & (keys != INT_MAX)
    return keys, vals, keep


def merge_runs_dedup(ka, va, kb, vb, **kw):
    """Host-friendly wrapper returning dense deduped numpy arrays."""
    keys, vals, keep = merge_sorted_runs(to_device(ka, jnp.int32),
                                         to_device(va, jnp.int32),
                                         to_device(kb, jnp.int32),
                                         to_device(vb, jnp.int32), **kw)
    keys, vals, keep = map(to_host, (keys, vals, keep))
    return keys[keep], vals[keep]


def _pad_run(k, v, n):
    pad = n - k.shape[0]
    if pad:
        k = np.concatenate([k, np.full(pad, INT_MAX, np.int32)])
        v = np.concatenate([v, np.zeros(pad, np.int32)])
    return k, v


def ingest_run(keys, src, *, tile: int = MERGE_TILE, use_kernel: bool = True,
               interpret: bool = True):
    """Run-sized write-ingest entry point: dedup a pre-ordered write batch
    through the tile-merge kernel.

    ``keys`` (int32, >= 2 entries, in [0, INT_MAX)) is sorted ascending
    with the newest occurrence of each key *first* among equals; ``src``
    carries each entry's original batch position. The batch is split at
    its midpoint into two sorted halves (any contiguous slice of a sorted
    run is sorted) and merged by the Pallas kernel: run-A tie priority
    plus the global keep-mask keep exactly the first -- i.e. newest --
    occurrence of every key, whether its duplicates sit inside one half
    or span the split. Operands are padded to power-of-two lengths with
    INT_MAX sentinels (same size bucketing as the read path) so the jit
    compiles once per batch-size bucket.

    Returns dense int32 (unique_keys, surviving_src).
    """
    keys = np.asarray(keys, np.int32)
    src = np.asarray(src, np.int32)
    h = keys.shape[0] // 2
    ka, va = _pad_run(keys[:h], src[:h], next_pow2(h))
    kb, vb = _pad_run(keys[h:], src[h:], next_pow2(keys.shape[0] - h))
    return merge_runs_dedup(ka, va, kb, vb, tile=tile,
                            use_kernel=use_kernel, interpret=interpret)


@jax.jit
def search_sorted_run(keys, queries):
    """Insertion position of each query in one sorted run: the memory
    component's search of one level, and the staged read path's search
    of one table."""
    return jnp.searchsorted(keys, queries)


@partial(jax.jit, static_argnames=("steps",))
def _ranged_lookup(keys, vals, lo, hi, q, steps: int = 32):
    """Per-query lower-bound binary search of q[i] in keys[lo[i]:hi[i]]
    (each slice sorted), plus the hit test and payload gather -- one
    fused device invocation for a whole tier of concatenated runs.
    Same vectorized open-interval scheme as ``_diag_splits``. Each step
    at least halves every open range, so ``steps`` halvings settle a
    range of up to 2**steps - 1 entries; the default 32 covers any
    int32 span."""
    n = keys.shape[0]

    def body(_, lohi):
        lo, hi = lohi
        open_ = lo < hi
        mid = (lo + hi) // 2
        less = keys[jnp.clip(mid, 0, max(n - 1, 0))] < q
        return (jnp.where(open_ & less, mid + 1, lo),
                jnp.where(open_ & ~less, mid, hi))

    pos, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    safe = jnp.clip(pos, 0, max(n - 1, 0))
    hit = (pos < hi) & (keys[safe] == q)   # hi: the original range end
    return pos, hit, jnp.where(hit, vals[safe], 0)


def lookup_runs_device(keys, vals, lo, hi, queries):
    """Run-sized fused sorted probe: ``queries[i]`` against the sorted
    slice ``keys[lo[i]:hi[i]]`` of a tier's concatenated runs (device
    arrays, INT_MAX-padded). Queries are bucketed to a power of two
    (>= 256) with empty ranges, padded on the host, so tiers sharing the
    (N, K-bucket) shape share the compiled search. Returns numpy
    (abs_pos, hit, val)."""
    n = len(queries)
    m = next_pow2(max(1, n), lo=256)
    q, lo, hi = (to_device(padded(a, (m,))) for a in (queries, lo, hi))
    pos, hit, val = map(to_host, _ranged_lookup(keys, vals, lo, hi, q))
    return (pos[:n].astype(np.int64), hit[:n].astype(bool),
            val[:n].astype(np.int64))


def search_steps(max_entries: int) -> int:
    """Halvings the ranged search needs for tables of up to
    ``max_entries`` entries, the length bucketed to a power of two so
    views of like size share one compiled store probe."""
    return next_pow2(int(max_entries), lo=1).bit_length()


def _filter_bits(fslots, q, gti, ns, k_hashes):
    """Bloom membership of each query in table ``gti`` (-1: none, never
    a member): the double-hash slots of ``kernels/bloom/ref.py``, each
    hash one gather from row ``gti`` of the slot-ordered stack. Indexing
    by slot keeps the per-query ``// w`` and ``% w`` of the [128, W]
    layout out of the gathers' indices: with them, compiling the store
    probe for a v5e took about 24 s instead of under 2 s."""
    h1 = (q * C1) % ns
    h2 = ((q * C2) | 1) % ns
    row = jnp.maximum(gti, 0)
    member = gti >= 0
    for j in range(k_hashes):
        member &= fslots[row, (h1 + j * h2) % ns]
    return member


@partial(jax.jit, static_argnames=("k_hashes", "steps"))
def _store_probe(fslots, keys, vals, q, gti, ns, lo, hi, *, k_hashes,
                 steps):
    """The whole cross-tier read in ONE jitted invocation, each
    (tier, query) pair doing only the work of its covering table: the
    k filter bits of that table gathered from the resident stack, the
    ranged sorted probe of its run in the store-wide concatenation
    (``steps`` halvings, static), and the newest-wins tier argmin. Per
    tier, results are exactly what the per-tier fused pair
    (``probe_filters_multi`` + ``_ranged_lookup``) would produce.

    ``fslots`` [Tg, S] holds table t's filter bits in slot order (slot
    = row * W_t + col of its [128, W_t] filter), zero past its slots.
    ``gti``/``ns``/``lo``/``hi`` are per (tier, query) [R, kpad]
    operands, so the program depends only on the sizes (padded tables,
    slots, tiers, padded entries, padded queries, ``steps``), and trees
    and layouts that bucket alike share it."""
    r, kpad = lo.shape
    qf = jnp.broadcast_to(q[None, :], (r, kpad)).reshape(-1)
    with jax.named_scope("bloom_probe"):
        member = _filter_bits(fslots, qf, gti.reshape(-1), ns.reshape(-1),
                              k_hashes).reshape(r, kpad)
    with jax.named_scope("ranged_search"):
        pos, hit, val = _ranged_lookup(keys, vals, lo.reshape(-1),
                                       hi.reshape(-1), qf, steps=steps)
        pos = pos.reshape(r, kpad)
        hit = hit.reshape(r, kpad)
        val = val.reshape(r, kpad)
    # Newest-wins: the smallest tier rank whose probe hit, -1 when none
    # did (a hit implies a covering table, so ranking `hit` alone is the
    # staged path's first-resolving-tier order).
    with jax.named_scope("newest_wins"):
        ridx = jax.lax.broadcasted_iota(jnp.int32, (r, kpad), 0)
        win = jnp.min(jnp.where(hit, ridx, r), axis=0)
        win = jnp.where(win < r, win, -1)
    return member, pos, hit, val, win


def store_probe_operands(queries, gti, ns, lo, hi):
    """The host half of the store-sized fused cross-tier probe:
    ``queries`` against every lookup tier of a tree in a single device
    launch (``run_store_probe``).

    Per (tier, query) metadata is [R, K]: ``gti`` the GLOBAL
    covering-table index (clipped, as ``assign_bounds`` leaves it),
    ``ns`` that table's filter slot count, ``lo``/``hi`` its run's span
    in the store-wide concatenation. Queries bucket to a power of two
    (>= 256); a padding query probes nothing (gti=-1) and searches
    nothing (lo=hi=0). All of it is padded on the host and uploaded.
    Returns (device operands, real query count)."""
    n = len(queries)
    m = next_pow2(max(1, n), lo=256)
    r = len(gti)
    ops = (padded(queries, (m,)), padded(gti, (r, m), -1),
           padded(ns, (r, m), 128), padded(lo, (r, m)), padded(hi, (r, m)))
    return tuple(map(to_device, ops)), n


def run_store_probe(fslots, keys, vals, operands, n, *, steps: int,
                    k_hashes: int = 7):
    """Dispatch ``_store_probe`` on ``store_probe_operands``' uploads and
    pull its answers back (the ``read.probe_pull`` span), cut on the
    host to the ``n`` real queries.

    ``fslots`` [Tg, S] bool holds all tables of all tiers tier-major,
    each table's filter in slot order; ``keys``/``vals`` are the
    store-wide INT_MAX-padded concatenation; ``steps`` is
    ``search_steps`` of its longest table. Returns numpy (member [R,K]
    bool, abs_pos [R,K], hit [R,K], val [R,K], win [K]) with ``win`` the
    newest-wins tier rank (-1 = miss)."""
    out = _store_probe(fslots, keys, vals, *operands, k_hashes=k_hashes,
                       steps=steps)
    with tracing.span("read.probe_pull"):
        member, pos, hit, val, win = map(to_host, out)
    return (member[:, :n].astype(bool), pos[:, :n].astype(np.int64),
            hit[:, :n].astype(bool), val[:, :n].astype(np.int64),
            win[:n].astype(np.int64))


def merge_runs_device(runs, *, tile: int = MERGE_TILE, use_kernel: bool = True,
                      interpret: bool = True):
    """Run-sized engine entry point: fold k sorted runs (ordered newest
    first, keys in [0, INT_MAX)) into one deduped run with newest-wins
    reconciliation.

    Each operand is padded to a power-of-two length with INT_MAX sentinels
    -- dropped by the kernel's keep-mask -- so the jitted tile composition
    compiles once per size bucket rather than once per exact run length.
    Returns dense int32 numpy (keys, vals).
    """
    rs = [(np.asarray(k, np.int32), np.asarray(v, np.int32))
          for k, v in runs if len(k)]
    if not rs:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    ka, va = rs[0]
    with tracing.span("merge.fold"):
        tracing.count("merge.steps", len(rs) - 1)
        for kb, vb in rs[1:]:
            ka_p, va_p = _pad_run(ka, va, next_pow2(ka.shape[0]))
            kb_p, vb_p = _pad_run(kb, vb, next_pow2(kb.shape[0]))
            ka, va = merge_runs_dedup(ka_p, va_p, kb_p, vb_p, tile=tile,
                                      use_kernel=use_kernel,
                                      interpret=interpret)
    return ka, va
