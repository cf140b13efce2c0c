"""Pallas execution backend: routes the engine's primitives through the
TPU kernels in ``repro.kernels.{merge,bloom}``.

Kernels run compiled when the backend's arrays land on a TPU and in
interpret mode anywhere else (``PallasBackend.interpret`` and ``.device``
say which). All entry points bucket their operand sizes to powers of
two (sentinel padding) so the jitted kernels compile once per size bucket
instead of once per exact run length.

jax is imported lazily (on first instantiation), keeping the default numpy
path jax-free. Keys/values outside the kernels' int32 domain (negative
keys, magnitudes at/above 2**31 - 1) fall back to the numpy reference per
call; the engine never produces such keys in normal operation, but
correctness must not depend on that.
"""
from __future__ import annotations

import numpy as np

from ...runtime import tracing
from .backend import (BLOOM_K_HASHES, ExecutionBackend, FusedLookup,
                      SortedRun, StoreLookup, StoreView, TierView,
                      assign_bounds, bloom_sizing, next_pow2,
                      register_backend)
from .numpy_backend import NumpyBackend, ingest_order

_INT32_MAX = 2**31 - 1
# A store view's filter stack holds a multiple of this many tables (zero
# filters past the last), so stores whose table counts round alike share
# one compiled store probe.
STORE_TABLE_STEP = 64


def _filter_stack(filts, tables: int, wmax: int) -> np.ndarray:
    """The bool [tables*128, wmax] stack of the [128, W_t] filters
    ``filts``, each zero-padded to ``wmax`` columns, zero rows past the
    last filter."""
    fstack = np.zeros((tables * 128, wmax), bool)
    for i, f in enumerate(filts):
        fstack[i * 128:(i + 1) * 128, :f.shape[1]] = f
    return fstack


def _slot_stack(filts, tables: int) -> np.ndarray:
    """The bool [tables, max slots] stack of the [128, W_t] filters
    ``filts``, each flattened to slot order (slot = row * W_t + col, the
    numpy backend's flat filter), zero past its slots and in the rows
    past the last filter."""
    fslots = np.zeros((tables, max((f.size for f in filts), default=128)),
                      bool)
    for i, f in enumerate(filts):
        fslots[i, :f.size] = f.reshape(-1)
    return fslots


def _int32_safe_keys(arrs) -> bool:
    return all(len(a) == 0 or (int(a.min()) >= 0
                               and int(a.max()) < _INT32_MAX)
               for a in arrs)


def _int32_safe_sorted(a) -> bool:
    """O(1) domain check for a sorted run: the endpoints bound the rest."""
    return len(a) == 0 or (int(a[0]) >= 0 and int(a[-1]) < _INT32_MAX)


def _int32_safe_vals(arrs) -> bool:
    return all(len(a) == 0 or (int(a.min()) > -_INT32_MAX - 1
                               and int(a.max()) <= _INT32_MAX)
               for a in arrs)


class PallasBackend(ExecutionBackend):
    name = "pallas"

    def __init__(self, *, interpret: bool | None = None,
                 k_hashes: int = BLOOM_K_HASHES, fused_wmax: int = 1024):
        super().__init__()
        import jax.numpy as jnp

        from repro.kernels import transfer
        from repro.kernels.bloom import ops as bloom_ops
        from repro.kernels.merge import ops as merge_ops
        self._bloom_ops = bloom_ops
        self._merge_ops = merge_ops
        self._transfer = transfer
        tracing.listen_compiles()
        # The device every operand is placed on decides how the kernels
        # run: compiled on a TPU, interpreted anywhere else. Asking for
        # compiled kernels off a TPU is an error, never a quiet fallback.
        self.device = next(iter(jnp.zeros(()).devices()))
        on_tpu = self.device.platform == "tpu"
        if interpret is None:
            interpret = not on_tpu
        elif not interpret and not on_tpu:
            raise ValueError(
                f"compiled Pallas kernels need a TPU, but arrays land on "
                f"{self.device.platform!r}; pass interpret=True or None")
        self.interpret = interpret
        self.k_hashes = k_hashes
        # Widest per-table filter (columns) the fused probe will take
        # resident: bounds the kernel's one-hot working set to VMEM scale.
        self.fused_wmax = fused_wmax
        self._fallback = NumpyBackend(k_hashes=k_hashes)
        # Calls sent off the device path: out-of-int32-domain operands,
        # numpy-built filters, and tiers too wide for the fused probe.
        self.fallback_calls = 0

    # -- merge ---------------------------------------------------------------
    def merge_runs(self, runs):
        runs = [(np.asarray(k), np.asarray(v)) for k, v in runs if len(k)]
        if len(runs) <= 1:
            return self._fallback.merge_runs(runs)
        if not (all(_int32_safe_sorted(k) for k, _ in runs)
                and _int32_safe_vals([v for _, v in runs])):
            self.fallback_calls += 1
            return self._fallback.merge_runs(runs)
        with self._note_jit():
            keys, vals = self._merge_ops.merge_runs_device(
                runs, interpret=self.interpret)
        return keys.astype(np.int64), vals.astype(np.int64)

    # -- write ingest --------------------------------------------------------
    def ingest_run(self, keys, vals):
        """Batch sort+dedup through the tile-merge kernel.

        The canonical ingest ordering (shared with the numpy reference) is
        computed on the host; the kernel then merges the two sorted halves
        of the ordered batch, carrying batch *positions* through its value
        channel -- values and LSNs are gathered host-side from the
        surviving positions, so arbitrarily wide payloads ride a fixed
        int32 kernel.
        """
        keys = np.asarray(keys, np.int64)
        vals = np.asarray(vals, np.int64)
        n = len(keys)
        if n < 2:
            return self._fallback.ingest_run(keys, vals)
        if not _int32_safe_keys([keys]):
            self.fallback_calls += 1
            return self._fallback.ingest_run(keys, vals)
        order = ingest_order(keys)
        with self._note_jit():
            ks, src = self._merge_ops.ingest_run(
                keys[order].astype(np.int32), order.astype(np.int32),
                interpret=self.interpret)
        src = src.astype(np.int64)
        return ks.astype(np.int64), vals[src], src

    # -- bloom ---------------------------------------------------------------
    def bloom_build(self, keys):
        keys = np.asarray(keys)          # an SSTable's keys: sorted run
        n_pad, n_slots = bloom_sizing(len(keys))
        if not _int32_safe_sorted(keys):
            self.fallback_calls += 1
            return ("numpy", self._fallback.bloom_build(keys))
        with self._note_jit():
            filt = self._bloom_ops.bloom_build_run(
                keys, n_keys_padded=n_pad, n_slots=n_slots,
                k_hashes=self.k_hashes, interpret=self.interpret)
        # Cache membership bits on the host, not the kernel's int32 counts:
        # filters live as long as their SSTable, so resident size matters
        # (bool is 4x smaller; re-widened to int32 at probe time).
        return ("pallas", self._transfer.to_host(filt) != 0)

    def bloom_probe(self, filt, keys):
        keys = np.asarray(keys)
        kind, f = filt
        if kind == "numpy":
            return self._fallback.bloom_probe(f, keys)
        if len(keys) == 0:
            return np.zeros(0, bool)
        if not ((keys >= 0) & (keys < _INT32_MAX)).all():
            # Out-of-int32-domain queries: probe through the host hash path
            # on the flattened membership bits. The kernel's [128, W] layout
            # flattens to exactly the numpy backend's flat filter (slot =
            # row*W + col), and both hash via the same int32 wraparound, so
            # results -- including aliasing false positives -- stay
            # bit-identical across backends and false negatives remain
            # impossible for keys that were inserted via the same wrap.
            self.fallback_calls += 1
            return self._fallback.bloom_probe(f.reshape(-1), keys)
        with self._note_jit():
            out = self._bloom_ops.bloom_probe_run(
                f, keys, k_hashes=self.k_hashes, interpret=self.interpret)
        return out

    # -- point lookups -------------------------------------------------------
    def lookup_batch(self, sorted_keys, queries):
        sorted_keys = np.asarray(sorted_keys)
        queries = np.asarray(queries)
        if len(queries) == 0:
            return np.zeros(0, np.int64), np.zeros(0, bool)
        if not (_int32_safe_sorted(sorted_keys)
                and _int32_safe_keys([queries])):
            self.fallback_calls += 1
            return self._fallback.lookup_batch(sorted_keys, queries)
        return self.search_run(self.prepare_run(sorted_keys), queries)

    def prepare_run(self, sorted_keys):
        """Pad the run once to a power of two with an INT_MAX sentinel
        (never matched -- keys are int32-safe) and upload it. A run
        outside the int32 domain stays on the host: ``search_run`` then
        takes the numpy fallback and counts it."""
        sorted_keys = np.asarray(sorted_keys)
        if not _int32_safe_sorted(sorted_keys):
            return SortedRun(sorted_keys)
        n = len(sorted_keys)
        sk = np.full(next_pow2(n), _INT32_MAX, np.int32)
        sk[:n] = sorted_keys
        return SortedRun(sorted_keys, self._transfer.to_device(sk))

    def search_run(self, run, queries):
        queries = np.asarray(queries)
        if len(queries) == 0:
            return np.zeros(0, np.int64), np.zeros(0, bool)
        if run.payload is None or not _int32_safe_keys([queries]):
            self.fallback_calls += 1
            return self._fallback.lookup_batch(run.keys, queries)
        # Queries pad to a power of two by repeating their last element
        # (results discarded), so the jitted searchsorted compiles once
        # per (run, batch) size bucket.
        n, q = len(run.keys), len(queries)
        qk = np.pad(queries.astype(np.int32),
                    (0, next_pow2(q) - q), mode="edge")
        tr = self._transfer
        with self._note_jit():
            pos = tr.to_host(self._merge_ops.search_sorted_run(
                run.payload, tr.to_device(qk)))[:q]
        pos = np.minimum(pos.astype(np.int64), n)
        inb = pos < n
        found = np.zeros(q, bool)
        safe = np.minimum(pos, n - 1)
        found[inb] = run.keys[safe[inb]] == queries[inb]
        return pos, found

    # -- fused tier probe ----------------------------------------------------
    def prepare_tier(self, tables, bloom_fn):
        """Device-resident tier view: the tier's key/val runs live on
        device as one INT_MAX-padded int32 concatenation, its Bloom
        filters as one stacked [T*128, Wmax] array, T the tier's table
        count rounded up to a power of two so tiers of like size share
        one compiled probe (the HBM pages a ``DevicePagePool`` accounts
        for). Refuses (``None``) when any run is outside the int32
        kernel domain, when a table's filter came from the numpy
        fallback, or when the widest filter would blow the fused
        kernel's VMEM working set."""
        keys_list = [t.keys for t in tables]
        if not (all(_int32_safe_sorted(k) for k in keys_list)
                and _int32_safe_vals([t.vals for t in tables])):
            self.fallback_calls += 1
            return None
        filts = []
        for t in tables:
            kind, f = bloom_fn(t)
            if kind != "pallas":
                self.fallback_calls += 1
                return None
            filts.append(f)                      # bool [128, W_t]
        wmax = max(f.shape[1] for f in filts)
        if wmax > self.fused_wmax:
            self.fallback_calls += 1
            return None
        fstack = _filter_stack(filts, next_pow2(len(tables), lo=1), wmax)
        lens = np.array([t.num_entries for t in tables], np.int64)
        offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
        total = int(lens.sum())
        npad = next_pow2(max(1, total))
        ck = np.full(npad, _INT32_MAX, np.int32)
        cv = np.zeros(npad, np.int32)
        ck[:total] = np.concatenate(keys_list)
        cv[:total] = np.concatenate([t.vals for t in tables])
        up = self._transfer.to_device
        payload = {
            "keys": up(ck),
            "vals": up(cv),
            "fstack": up(fstack),
            "nslots_t": np.array([128 * f.shape[1] for f in filts],
                                 np.int32),
            "w_t": np.array([f.shape[1] for f in filts], np.int32),
            "npad": npad,
        }
        return TierView(
            backend=self.name,
            sst_ids=tuple(t.sst_id for t in tables),
            starts=np.array([t.min_key for t in tables], np.int64),
            ends=np.array([t.max_key for t in tables], np.int64),
            offs=offs, lens=lens, payload=payload)

    def lookup_fused(self, view, queries):
        """Two device invocations for the whole tier -- the fused Bloom
        multi-probe and the fused ranged sorted probe -- in place of the
        staged path's two invocations *per SSTable*."""
        q = np.asarray(queries)
        if not _int32_safe_keys([q]):
            self.fallback_calls += 1
            return None
        p = view.payload
        ti, ok = assign_bounds(view.starts, view.ends, q.astype(np.int64))
        with self._note_jit():
            positive = self._bloom_ops.bloom_probe_multi(
                p["fstack"], q.astype(np.int32), ti.astype(np.int32),
                p["nslots_t"][ti], p["w_t"][ti],
                k_hashes=self.k_hashes, interpret=self.interpret)
        lo = view.offs[ti].astype(np.int32)
        hi = (view.offs[ti] + view.lens[ti]).astype(np.int32)
        with self._note_jit():
            abs_pos, hit, vals = self._merge_ops.lookup_runs_device(
                p["keys"], p["vals"], lo, hi, q.astype(np.int32))
        return FusedLookup(ti=ti, ok=ok, positive=positive,
                           pos=(abs_pos - view.offs[ti]).astype(np.int64),
                           hit=hit, vals=vals.astype(np.int64))

    # -- fused store (cross-tier) probe --------------------------------------
    def prepare_store(self, tiers, bloom_fn):
        """Device-resident view of EVERY lookup tier of one tree: all
        tables' key/val runs as one INT_MAX-padded int32 concatenation
        (tier-major), all Bloom filters as one bool [Tg, slots] stack in
        slot order, plus the ranged search's halvings for the longest
        table. The stack is padded to a multiple of ``STORE_TABLE_STEP``
        tables, so stores of like size and tier count share one compiled
        probe (tiers are not padded: the probe runs for every tier row
        of every query). Refusal conditions are the per-tier ones,
        applied across the whole stack."""
        tables = [t for tier in tiers for t in tier]
        if not (all(_int32_safe_sorted(t.keys) for t in tables)
                and _int32_safe_vals([t.vals for t in tables])):
            self.fallback_calls += 1
            return None
        filts = []
        for t in tables:
            kind, f = bloom_fn(t)
            if kind != "pallas":
                self.fallback_calls += 1
                return None
            filts.append(f)                      # bool [128, W_t]
        wmax = max((f.shape[1] for f in filts), default=1)
        if wmax > self.fused_wmax:
            self.fallback_calls += 1
            return None
        t_pad = -(-max(1, len(tables)) // STORE_TABLE_STEP) \
            * STORE_TABLE_STEP
        lens = np.array([t.num_entries for t in tables], np.int64)
        offs = (np.concatenate([[0], np.cumsum(lens)[:-1]])
                if len(tables) else np.zeros(0, np.int64))
        counts = np.array([len(tier) for tier in tiers], np.int64)
        t_off = (np.concatenate([[0], np.cumsum(counts)[:-1]])
                 if len(tiers) else np.zeros(0, np.int64))
        total = int(lens.sum())
        npad = next_pow2(max(1, total))
        ck = np.full(npad, _INT32_MAX, np.int32)
        cv = np.zeros(npad, np.int32)
        if total:
            ck[:total] = np.concatenate([t.keys for t in tables])
            cv[:total] = np.concatenate([t.vals for t in tables])
        up = self._transfer.to_device
        payload = {
            "keys": up(ck),
            "vals": up(cv),
            "fslots": up(_slot_stack(filts, t_pad)),
            "nslots_t": np.array([f.size for f in filts], np.int32),
            "t_off": t_off,
            "steps": self._merge_ops.search_steps(lens.max(initial=1)),
            "npad": npad,
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
        """ONE device launch for the whole store: the composed
        ``_store_probe`` jit fuses the Bloom probe and the ranged sorted
        probe of each (tier, query)'s covering table, and the
        newest-wins tier argmin, in place of the per-tier fused path's
        two launches *per tier*. The host work before the launch is the
        ``read.probe_prep`` span; ``read.filter_probes`` counts the
        (tier, query) pairs with a covering table."""
        q = np.asarray(queries)
        if not _int32_safe_keys([q]):
            self.fallback_calls += 1
            return None
        p = view.payload
        R, K = view.num_tiers, len(q)
        if R == 0:
            return StoreLookup(
                ti=np.zeros((0, K), np.int64), ok=np.zeros((0, K), bool),
                positive=np.zeros((0, K), bool),
                pos=np.zeros((0, K), np.int64), hit=np.zeros((0, K), bool),
                vals=np.zeros((0, K), np.int64),
                win=np.full(K, -1, np.int64))
        ops = self._merge_ops
        with tracing.span("read.probe_prep"):
            q64 = q.astype(np.int64)
            ti = np.empty((R, K), np.int64)
            ok = np.empty((R, K), bool)
            lo = np.empty((R, K), np.int64)
            hi = np.empty((R, K), np.int64)
            for r in range(R):
                ti[r], ok[r] = assign_bounds(view.tier_starts[r],
                                             view.tier_ends[r], q64)
                lo[r] = view.tier_offs[r][ti[r]]
                hi[r] = lo[r] + view.tier_lens[r][ti[r]]
            tracing.count("read.filter_probes", int(ok.sum()))
            gti = p["t_off"][:, None] + ti
            operands, n = ops.store_probe_operands(
                q.astype(np.int32), gti, p["nslots_t"][gti], lo, hi)
        with self._note_jit():
            member, abs_pos, hit, vals, win = ops.run_store_probe(
                p["fslots"], p["keys"], p["vals"], operands, n,
                steps=p["steps"], k_hashes=self.k_hashes)
        return StoreLookup(ti=ti, ok=ok, positive=member,
                           pos=(abs_pos - lo).astype(np.int64),
                           hit=hit, vals=vals, win=win)


register_backend("pallas", PallasBackend)
