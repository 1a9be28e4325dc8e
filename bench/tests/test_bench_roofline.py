"""Bytes and operations of a fused solve at the configurations' shapes,
and the peak table."""
import json

import pytest

import _paths  # noqa: F401
from reference import roofline

PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def test_sparco_lasso_round_reads_its_twenty_panels_once():
    # n=4096, d=65536, K=20 blocks, one round: 20 * 4096 * 128 * 4 B of A
    b, f = roofline.solve_cost(4096, 65536, 20, 1)
    panels = 20 * 4096 * 128 * 4
    assert b == panels + 4 * (2 * 4096 + 65536)
    assert f == 20 * 4096 * 128 * 4
    t, bound = roofline.least_time_s(panels, f, PEAK)
    assert bound == "hbm"
    assert t == pytest.approx(51.2e-6, rel=1e-3)   # 41.9 MB at 819 GB/s


def test_sci_lasso_round_reads_its_twenty_panels_once():
    # n=32768, d=65536, K=20 blocks of 16 MiB: 320 MiB of A a round
    b, f = roofline.solve_cost(32768, 65536, 20, 640)
    panels = 20 * 32768 * 128 * 4
    assert panels == 320 * 2 ** 20
    assert b == 640 * panels + 4 * (2 * 32768 + 65536)
    per_round, bound = roofline.least_time_s(panels, 20 * 32768 * 128 * 4,
                                             PEAK)
    assert bound == "hbm"
    assert per_round == pytest.approx(409.6e-6, rel=1e-3)


def test_spc_lasso_pads_n_to_whole_sample_tiles():
    # n=4770 is laid out as 10 tiles of 512 samples; d=16384 is 128 blocks
    assert roofline.padded(4770, 16384) == (5120, 16384)
    b, _ = roofline.solve_cost(4770, 16384, 8, 1)
    assert b == 8 * 5120 * 128 * 4 + 4 * (2 * 5120 + 16384)


def test_zeta_logreg_pads_d_and_counts_newton_work():
    # d=2000 is laid out as 16 blocks (2048 columns); K=8; 160 rounds
    assert roofline.padded(24064, 2000) == (24064, 2048)
    b, f = roofline.solve_cost(24064, 2000, 8, 160, newton=True)
    panel = 24064 * 128
    assert b == 160 * 8 * panel * 4 + 4 * (2 * 24064 + 2048)
    assert f == 160 * 8 * panel * 7
    per_round, bound = roofline.least_time_s(8 * panel * 4, 8 * panel * 7,
                                             PEAK)
    assert bound == "hbm"
    assert per_round == pytest.approx(120.3e-6, rel=1e-3)


def test_peaks_by_device_kind_and_unknown_device_fails(tmp_path):
    row = roofline.peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["flops_per_s"] == 197e12
    assert row["memory_bytes"] == 16e9
    src = json.loads(roofline.PEAKS.read_text())["source"]
    assert "TPU v5e" in src
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_roofline_reader_uses_the_benchmark_count_and_the_trace():
    import harness
    read = harness.reader("fused_kernel_roofline")
    cfg = {"n": 4096, "d": 65536, "P": 2560, "rounds": 640}
    b, f = roofline.solve_cost(4096, 65536, 20, 640)
    least = b * 3 / 819e9
    trace = {"chips": 1, "ops": [[0, "fused_shotgun_rounds", 0.0, least * 2e9],
                                 [0, "fusion.1", 1.0, 5e6]]}
    ctx = {"trace": trace, "window_ns": (0.0, 1e12), "config": cfg,
           "units": 3, "peak": PEAK}
    assert read(ctx) == pytest.approx(50.0)
    trace["ops"] = trace["ops"][1:]
    assert read(ctx) is None
