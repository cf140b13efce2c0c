"""Merge kernel: Pallas (interpret mode) vs pure-jnp oracle vs numpy,
sweeping shapes and skews (hypothesis for the run-level composition),
and the grouped k-run fold against the numpy reference."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.engine.numpy_backend import merge_runs_numpy
from repro.core.lsm.sstable import TOMBSTONE
from repro.kernels.merge import ops
from repro.kernels.merge.merge import merge_tiles
from repro.kernels.merge.ops import merge_runs_dedup, merge_runs_device
from repro.kernels.merge.ref import merge_tiles_ref
from repro.kernels.sizing import next_pow2
from repro.runtime import tracing


def sorted_unique(rng, n, hi=2**30):
    return np.sort(rng.choice(hi, size=n, replace=False)).astype(np.int32)


@pytest.mark.parametrize("g,ba,bb", [(1, 128, 128), (4, 256, 128),
                                     (2, 512, 512), (3, 128, 384)])
def test_tile_merge_matches_ref(g, ba, bb):
    rng = np.random.default_rng(ba * bb + g)
    ka = np.stack([sorted_unique(rng, ba) for _ in range(g)])
    kb = np.stack([sorted_unique(rng, bb) for _ in range(g)])
    va = rng.integers(0, 2**30, (g, ba)).astype(np.int32)
    vb = rng.integers(0, 2**30, (g, bb)).astype(np.int32)
    got = merge_tiles(*map(jnp.asarray, (ka, va, kb, vb)), interpret=True)
    ref = merge_tiles_ref(*map(jnp.asarray, (ka, va, kb, vb)))
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b).astype(a.dtype))


def test_tile_merge_tie_prefers_run_a():
    ka = jnp.array([[5, 10, 20, 2**30 - 1]], jnp.int32)
    kb = jnp.array([[5, 10, 30, 2**30 - 1]], jnp.int32)
    va = jnp.array([[1, 2, 3, 4]], jnp.int32)
    vb = jnp.array([[-0, 9, 8, 7]], jnp.int32)
    keys, vals, keep = merge_tiles(ka, va, kb, vb, interpret=True)
    keys, vals, keep = map(np.asarray, (keys, vals, keep))
    # first occurrence of duplicate key carries run A's value
    for dup in (5, 10, 2**30 - 1):
        i = int(np.argmax(keys[0] == dup))
        assert keep[0][i] == 1
        assert vals[0][i] in (1, 2, 3, 4)
        assert keep[0][i + 1] == 0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 3000), st.integers(0, 3000), st.integers(0, 2**31 - 1),
       st.sampled_from([128, 512]))
def test_run_merge_matches_numpy(na, nb, seed, tile):
    rng = np.random.default_rng(seed)
    ka = sorted_unique(rng, na) if na else np.zeros(0, np.int32)
    kb = sorted_unique(rng, nb) if nb else np.zeros(0, np.int32)
    va = np.arange(na, dtype=np.int32)
    vb = np.arange(nb, dtype=np.int32) + 10**6
    if na + nb == 0:
        return
    keys, vals = merge_runs_dedup(ka, va, kb, vb, tile=tile,
                                  use_kernel=False)
    # numpy oracle: newest (a) wins
    d = {int(k): int(v) for k, v in zip(kb, vb)}
    d.update({int(k): int(v) for k, v in zip(ka, va)})
    exp_keys = np.array(sorted(d), np.int32)
    np.testing.assert_array_equal(keys, exp_keys)
    np.testing.assert_array_equal(vals, np.array([d[int(k)] for k in exp_keys],
                                                 np.int32))


def test_run_merge_kernel_path_matches_ref_path():
    rng = np.random.default_rng(0)
    ka, kb = sorted_unique(rng, 1500), sorted_unique(rng, 700)
    va = np.arange(1500, dtype=np.int32)
    vb = np.arange(700, dtype=np.int32)
    k1, v1 = merge_runs_dedup(ka, va, kb, vb, tile=256, use_kernel=True,
                              interpret=True)
    k2, v2 = merge_runs_dedup(ka, va, kb, vb, tile=256, use_kernel=False)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(v1, v2)


# -- merge_runs_device: the grouped fold ------------------------------------

def _run(keys, rng, tomb_share=0.0):
    keys = np.asarray(keys, np.int64)
    vals = rng.integers(0, 2**30, len(keys))
    vals[rng.random(len(keys)) < tomb_share] = TOMBSTONE
    return keys, vals


def _level(rng, lo, hi, n, tables):
    """``tables`` key-disjoint sorted runs of ``n`` keys in [lo, hi)."""
    return [_run(k, rng) for k in
            np.array_split(np.sort(rng.choice(np.arange(lo, hi), n,
                                              replace=False)), tables)]


def _shuffled(runs, order):
    return [runs[i] for i in order]


def _spread(rng, lo, hi, n, tomb_share=0.0):
    return _run(np.sort(rng.choice(np.arange(lo, hi), n, replace=False)),
                rng, tomb_share)


# Each case: runs newest first, and the fold's (groups, device merges).
FOLDS = {
    "all_disjoint": lambda rng: (
        _shuffled(_level(rng, 0, 4_000, 600, 4), (2, 0, 3, 1)), (1, 0)),
    "newer_over_level": lambda rng: (
        [_spread(rng, 0, 4_000, 150)] + _level(rng, 0, 4_000, 700, 5),
        (2, 5)),
    "groups_descending": lambda rng: (
        [_spread(rng, 0, 4_000, 150)]
        + _level(rng, 0, 4_000, 700, 5)[::-1], (2, 5)),
    "acc_outside_tables": lambda rng: (
        [_run(np.r_[0:40, 3_900:3_950], rng)]
        + _level(rng, 1_000, 3_000, 400, 3), (2, 2)),
    "table_without_acc_keys": lambda rng: (
        [_spread(rng, 0, 200, 60)] + [_run(np.arange(100, 300, 2), rng),
                                      _run(np.arange(400, 700, 3), rng),
                                      _run(np.arange(800, 900), rng)],
        (2, 1)),
    "duplicates_newest_wins": lambda rng: (
        [_spread(rng, 0, 1_000, 300), _spread(rng, 0, 1_000, 300)]
        + _level(rng, 0, 1_000, 500, 4), (3, 5)),
    "overlapping": lambda rng: (
        [_spread(rng, 0, 2_000, n) for n in (100, 300, 200, 50)], (4, 3)),
    "touching_ranges": lambda rng: (
        [_run(np.arange(0, 501, 5), rng), _run(np.arange(500, 1_000, 5), rng)],
        (2, 1)),
    "tombstones": lambda rng: (
        [_spread(rng, 0, 3_000, 200, 0.3), _spread(rng, 0, 3_000, 500, 0.3)]
        + _level(rng, 0, 3_000, 600, 3), (3, 4)),
}


@pytest.fixture
def device_merges(monkeypatch):
    """The (A, B) operand lengths of every ``merge_sorted_runs`` call."""
    calls = []
    real = ops.merge_sorted_runs

    def recorded(ka, va, kb, vb, **kw):
        calls.append((ka.shape[0], kb.shape[0]))
        return real(ka, va, kb, vb, **kw)
    monkeypatch.setattr(ops, "merge_sorted_runs", recorded)
    return calls


@pytest.mark.parametrize("case", sorted(FOLDS))
def test_grouped_fold_matches_numpy(case, device_merges):
    runs, (groups, steps) = FOLDS[case](np.random.default_rng(
        sorted(FOLDS).index(case)))
    tracing.clear()
    with tracing.recording():
        keys, vals = merge_runs_device(runs, interpret=True)
    (fold,) = [r for r in tracing.records() if r.name == "merge.fold"]
    tracing.clear()
    exp_keys, exp_vals = merge_runs_numpy(runs)
    np.testing.assert_array_equal(keys, exp_keys)
    np.testing.assert_array_equal(vals, exp_vals)
    assert fold.counts["merge.groups"] == groups
    assert fold.counts["merge.steps"] == steps == len(device_merges)


@pytest.mark.parametrize("case", sorted(FOLDS))
def test_grouped_fold_operands_stay_in_the_pairwise_envelope(case,
                                                             device_merges):
    """A piece is a slice of the accumulated run and its partner one
    input run: B is one run's padded length, A at most the padded total,
    the shape pairs a pairwise fold (and its warm-up) compiles."""
    runs, _ = FOLDS[case](np.random.default_rng(7))
    merge_runs_device(runs, use_kernel=False)
    run_lens = {next_pow2(len(k)) for k, _ in runs}
    total = next_pow2(sum(len(k) for k, _ in runs))
    for a, b in device_merges:
        assert b in run_lens
        assert a == next_pow2(a) <= total
