"""The roofline's byte count and the table of peaks."""
import pytest

import work


def test_bytes_hand_counted_flat_1024_banks():
    # colibri, 256 cores, 1024 banks: 5 core lanes x 256, 4 bank-state
    # rows read and written x 1024, 4 outcome lanes x 1024, 64 bins + 3
    lanes = 5 * 256 + 2 * 4 * 1024 + 4 * 1024 + 64 + 3
    assert lanes == 13635
    assert work.bytes_per_cycle("colibri", 256, 1024) == 4 * 13635


def test_bytes_hand_counted_hier_4_banks():
    # colibri_hier, 1024 cores, 4 banks, 4 groups: 3 rows per group and
    # 8 per bank = 20 bank-state rows
    lanes = 5 * 1024 + 2 * 20 * 4 + 4 * 4 + 64 + 3
    assert lanes == 5363
    assert work.bytes_per_cycle("colibri_hier", 1024, 4, 4) == 4 * 5363


def test_least_time_is_bytes_over_hbm_bandwidth():
    t = work.least_seconds_per_cycle("colibri", 256, 1024, "TPU v5 lite")
    assert t == pytest.approx(4 * 13635 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        work.bank_rows("no_such_protocol", 256)
