"""jit'd wrappers for the Bloom kernels, with padding and a numpy facade:
the ``*_run`` / ``bloom_probe_multi`` entry points are what the Pallas
execution backend (``StoreConfig(backend="pallas")``) calls."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..sizing import next_pow2, slots_for  # noqa: F401  (re-exported)
from ..transfer import padded, to_device, to_host
from .bloom import build_filter, probe_filter, probe_filters_multi
from .ref import build_ref, probe_multi_ref, probe_ref


def bloom_build(keys, *, bits_per_key: int = 10, k_hashes: int = 7,
                use_kernel: bool = True, interpret: bool = True):
    keys = to_device(keys, jnp.int32)
    n_slots = slots_for(keys.shape[0], bits_per_key)
    tile = 256
    pad = (-keys.shape[0]) % tile
    if pad:
        # pad by repeating the first key (idempotent for membership)
        keys = jnp.concatenate([keys, jnp.broadcast_to(keys[:1], (pad,))])
    if use_kernel:
        return build_filter(keys, n_slots=n_slots, k_hashes=k_hashes,
                            interpret=interpret)
    return build_ref(keys, n_slots, k_hashes)


def bloom_build_run(keys, *, n_keys_padded: int | None = None,
                    n_slots: int | None = None, bits_per_key: int = 10,
                    k_hashes: int = 7, use_kernel: bool = True,
                    interpret: bool = True):
    """Run-sized engine entry point: build a filter over one SSTable's keys.

    Pads the key set to ``n_keys_padded`` (default: next power of two) by
    repeating the first key -- idempotent for membership -- and sizes the
    filter at exactly ``n_slots``, so an engine that buckets run sizes
    reuses compiled kernels across SSTables of similar size.
    """
    keys = np.asarray(keys, np.int32)
    n = keys.shape[0]
    assert n >= 1, "empty key set"
    if n_keys_padded is None:
        n_keys_padded = next_pow2(n, lo=256)
    if n_slots is None:
        n_slots = slots_for(n_keys_padded, bits_per_key)
    tile = 256
    total = -(-max(n_keys_padded, n) // tile) * tile
    # Padded on the host, so only the bucketed shape reaches the device.
    keys = to_device(np.pad(keys, (0, total - n), constant_values=keys[0]))
    if use_kernel:
        return build_filter(keys, n_slots=n_slots, k_hashes=k_hashes,
                            interpret=interpret)
    return build_ref(keys, n_slots, k_hashes)


def bloom_probe_run(filt, keys, *, k_hashes: int = 7,
                    use_kernel: bool = True, interpret: bool = True):
    """Run-sized probe: bucket the query batch to a power of two (>= 256)
    so per-batch probes against many SSTables share compiled kernels.

    ``filt`` may be any integer/bool dtype (engines cache membership bits
    as bool to cut resident size); it is widened to the kernel's int32
    on-device, so only the 1-byte representation crosses the host boundary.
    """
    filt = to_device(filt).astype(jnp.int32)
    n = len(keys)
    m = next_pow2(max(1, n), lo=256)
    keys = to_device(padded(keys, (m,)))
    if use_kernel:
        out = probe_filter(filt, keys, k_hashes=k_hashes,
                           interpret=interpret)
    else:
        out = probe_ref(filt, keys, k_hashes)
    return to_host(out)[:n].astype(bool)


def bloom_probe_multi(fstack, keys, ti, nslots, w, *, k_hashes: int = 7,
                      use_kernel: bool = True, interpret: bool = True):
    """Run-sized fused probe: each key against its assigned table's filter
    inside a stacked [T*128, Wmax] tier filter, one device invocation for
    the whole tier. Queries are bucketed to a power of two (>= 256) and
    padded with ti=-1 (never a member), so fused probes across tiers of
    the same (T, Wmax, K-bucket) share compiled kernels.
    """
    fstack = to_device(fstack).astype(jnp.int32)
    n = len(keys)
    m = next_pow2(max(1, n), lo=256)
    keys, ti, nslots, w = (to_device(padded(a, (m,), fill)) for a, fill in
                           ((keys, 0), (ti, -1), (nslots, 128), (w, 1)))
    if use_kernel:
        out = probe_filters_multi(fstack, keys, ti, nslots, w,
                                  k_hashes=k_hashes, interpret=interpret)
    else:
        out = probe_multi_ref(fstack, keys, ti, nslots, w, k_hashes)
    return to_host(out)[:n].astype(bool)


def bloom_probe(filt, keys, *, k_hashes: int = 7, use_kernel: bool = True,
                interpret: bool = True):
    keys = to_device(keys, jnp.int32)
    n = keys.shape[0]
    tile = 256
    pad = (-n) % tile
    if pad:
        keys = jnp.concatenate([keys, jnp.zeros((pad,), jnp.int32)])
    if use_kernel:
        out = probe_filter(filt, keys, k_hashes=k_hashes,
                           interpret=interpret)
    else:
        out = probe_ref(filt, keys, k_hashes)
    return to_host(out[:n]).astype(bool)
