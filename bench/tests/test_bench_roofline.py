"""Operation and byte counts of the tile kernel, and the peak table."""
from __future__ import annotations

import pytest

from bench.roofline import l2_tile_cost, least_seconds, peaks


def test_l2_tile_cost_counts():
    ops, moved = l2_tile_cost(256, 512, 128)
    assert ops == 2 * 256 * 512 * 128
    assert moved == 4 * (256 + 512) * 128 + 256 * 512 / 8 + 4 * 256


def test_sift_tile_is_compute_bound_on_v5e():
    peak = peaks("TPU v5 lite")
    ops, moved = l2_tile_cost(2**17, 2**17, 128)
    t, bound = least_seconds(ops, moved, peak)
    assert bound == "compute"
    assert t == pytest.approx(ops / 197e12)
    # a tile of width 1 moves more than it computes
    assert least_seconds(*l2_tile_cost(2**17, 2**17, 1), peak)[1] == "memory"


def test_peak_table_cites_its_source():
    peak = peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
