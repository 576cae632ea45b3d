"""Logical bytes behind the roofline shares, and the peaks table."""
import pytest

from bench import work

ATM = 1800 * 3600


def test_detect_bytes_count_field_labels_and_ranks():
    # 4 B field in, 2-bit labels + 4 B ranks out per point
    assert work.detect_bytes(ATM) == 8.25 * ATM
    assert work.detect_bytes(0) == 0


def test_restore_bytes_count_base_labels_ranks_and_output():
    assert work.restore_bytes(ATM) == 12.25 * ATM


def test_hbm_share_of_the_v5e_peak():
    # 819 GB in one second is the whole peak
    assert work.hbm_share(819e9, 1.0, "TPU v5 lite") == pytest.approx(100.0)
    assert work.hbm_share(work.detect_bytes(ATM), 0.5, "TPU v5 lite") \
        == pytest.approx(100 * 8.25 * ATM / 819e9 / 0.5)
    with pytest.raises(ValueError):
        work.hbm_share(1.0, 0.0, "TPU v5 lite")


def test_peaks_table_has_its_source_and_refuses_unknown_kinds():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v4")
