"""The store's kernels compile for a TPU v5e, at the sizes the default
``StoreConfig`` produces.

Nothing runs: each test lowers a jitted kernel entry point against a
described (not attached) v5e chip with ``interpret=False`` and compiles it
with the chip's own compiler, which refuses what interpret mode accepts
(blocks off the (8, 128) tiling, kernels over the VMEM limit, vector ops
Mosaic cannot lower). A default store holds 2 MB SSTables of 1 KB
entries: 2048 keys per table, Bloom filters of 2048 * 10 slots (W=160),
with the smallest tables at W=20.

The topology is described only inside the module fixture: loading the
TPU compiler takes a process-wide lock, so it must happen in the one
worker that runs this file and never at import or collection.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine.backend import bloom_sizing
from repro.kernels.bloom.bloom import (build_filter, probe_filter,
                                       probe_filters_multi)
from repro.kernels.merge.ops import (_store_probe, merge_sorted_runs,
                                     search_steps)
from repro.kernels.sizing import next_pow2

SST_KEYS = 2048                          # 2 MB SSTable / 1 KB entries


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes, kernel=True):
    """Compile ``fn`` for the described chip; ``kernel``: the program
    must hold a Pallas kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    if kernel:
        assert "tpu_custom_call" in text, "the Pallas kernel became plain XLA"


I32 = jnp.int32


def test_merge_compiles(one_chip):
    _compile(lambda ka, va, kb, vb: merge_sorted_runs(ka, va, kb, vb,
                                                      interpret=False),
             one_chip, *[((SST_KEYS,), I32)] * 4)


@pytest.mark.parametrize("batch", [4096, 8192])
def test_ingest_merge_compiles(one_chip, batch):
    # ingest_run merges the two sorted halves of an ordered write batch,
    # each padded to a power of two.
    h = next_pow2(batch // 2)
    _compile(lambda ka, va, kb, vb: merge_sorted_runs(ka, va, kb, vb,
                                                      interpret=False),
             one_chip, *[((h,), I32)] * 4)


@pytest.mark.parametrize("n_keys", [256, SST_KEYS])
def test_bloom_build_compiles(one_chip, n_keys):
    n_pad, n_slots = bloom_sizing(n_keys)
    _compile(lambda k: build_filter(k, n_slots=n_slots, interpret=False),
             one_chip, ((n_pad,), I32))


@pytest.mark.parametrize("n_keys", [256, SST_KEYS])
def test_bloom_probe_compiles(one_chip, n_keys):
    _, n_slots = bloom_sizing(n_keys)
    _compile(lambda f, k: probe_filter(f, k, interpret=False), one_chip,
             ((128, n_slots // 128), I32), ((4096,), I32))


def test_bloom_probe_multi_compiles(one_chip):
    w = bloom_sizing(SST_KEYS)[1] // 128
    _compile(lambda f, k, t, n, w_: probe_filters_multi(f, k, t, n, w_,
                                                        interpret=False),
             one_chip, ((16 * 128, w), I32), *[((256,), I32)] * 4)


def test_store_probe_compiles(one_chip):
    """The one-launch cross-tier read behind ``run_store_probe``: 64
    tables in 4 tiers, a 256-query batch, per-tier operands and the
    ranged search's halvings for tables of ``SST_KEYS`` entries. Its
    Bloom half is a gather from the slot-ordered stack, not a kernel."""
    tiers, tables, k = 4, 64, 256
    slots = bloom_sizing(SST_KEYS)[1]
    npad = next_pow2(tables * SST_KEYS)
    _compile(lambda f, ks, vs, q, g, n, lo, hi: _store_probe(
                 f, ks, vs, q, g, n, lo, hi,
                 k_hashes=7, steps=search_steps(SST_KEYS)),
             one_chip, ((tables, slots), jnp.bool_), ((npad,), I32),
             ((npad,), I32), ((k,), I32), *[((tiers, k), I32)] * 4,
             kernel=False)
