"""The least bytes each kernel's algorithm has to move, whatever
implements it. A roofline share is these bytes over the chip's HBM
bandwidth (``peaks.py``), divided by the device time the trace gives the
kernel's programs; both algorithms are bound by memory, not operations.
"""
from __future__ import annotations

import numpy as np

WORD = 4                  # int32 keys, values and filter words
FILTER_WORDS = 7          # one filter word per hash: k = 7 (10 bits/key)
ENTRY = 8                 # one int32 key and one int32 value


def covered_entries(starts, ends, lens, queries) -> np.ndarray:
    """For one disjoint, sorted tier (per-table ``starts``/``ends``/
    ``lens``): the entry count of the table covering each query, for the
    queries some table covers."""
    q = np.asarray(queries, np.int64)
    ti = np.searchsorted(np.asarray(starts), q, side="right") - 1
    ok = ti >= 0
    ti = np.clip(ti, 0, len(starts) - 1)
    ok &= q <= np.asarray(ends)[ti]
    return np.asarray(lens)[ti[ok]]


def lookup_bytes(entries) -> int:
    """A point lookup's least bytes: for each (query, tier) pair with a
    covering table of ``entries`` keys, the k filter words, a binary
    search's ceil(log2 entries) key reads and one value."""
    n = np.maximum(np.asarray(entries, np.float64), 2.0)
    reads = np.ceil(np.log2(n))
    return int(np.sum(FILTER_WORDS * WORD + reads * WORD + WORD))


def merge_bytes(input_entries, output_entries: int) -> int:
    """A k-way merge's least bytes: read every input entry once, write
    every output entry once."""
    return ENTRY * (int(np.sum(input_entries)) + int(output_entries))


def roofline_pct(nbytes: float, device_s: float, bytes_per_s: float):
    """Share of the roofline: least time over measured device time, in
    percent; None where the trace gave the kernel no time."""
    if device_s <= 0:
        return None
    return 100.0 * (nbytes / bytes_per_s) / device_s
