"""The roofline byte functions on hand-worked shapes."""
import pytest

from chipbench import roofline
from chipbench.peaks import peaks


def test_lookup_bytes_hand_worked():
    # One (query, tier) pair on a 2,048-entry table: 7 filter words,
    # 11 key reads and one value, 4 B each = 76 B.
    assert roofline.lookup_bytes([2048]) == 76
    # 1,000 entries: ceil(log2 1000) = 10 reads -> 72 B; plus the first.
    assert roofline.lookup_bytes([2048, 1000]) == 76 + 72


def test_covered_entries_skips_uncovered_queries():
    starts, ends, lens = [0, 100], [50, 200], [10, 20]
    got = roofline.covered_entries(starts, ends, lens, [5, 60, 150, 300])
    assert got.tolist() == [10, 20]


def test_merge_bytes_hand_worked():
    # Three runs of 2, 3 and 4 entries into 7: (9 + 7) * 8 B.
    assert roofline.merge_bytes([2, 3, 4], 7) == 128


def test_roofline_pct():
    bw = peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert roofline.roofline_pct(819e6, 2e-3, bw) == pytest.approx(50.0)
    assert roofline.roofline_pct(1, 0.0, bw) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v99")
