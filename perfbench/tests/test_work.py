"""The work functions and the peak table, checked by hand arithmetic."""

import pytest

from perfbench.lib import peaks, work

# w8a: d = 301, 142 clients x 348 samples, k = 8d = 2408
N, NI, D, K = 142, 348, 301, 2408
T = 301 * 302 // 2  # 45,451


def test_triu_size():
    assert work.triu_size(D) == T == 45451


def test_w8a_syrk_flops():
    # 348 multiply-adds for each of the 45,451 packed entries, plus the
    # scaling of the 348 x 301 samples, for each of 142 clients
    per_client = 2 * 348 * 45451 + 348 * 301
    assert per_client == 31_738_644
    assert work.oracle_syrk(N, NI, D).flops == 142 * 31_738_644 == 4_506_887_448


def test_w8a_margins_and_gradient_flops():
    # z x and z^T r: 2 * 348 * 301 each per client
    assert work.oracle_margins_grad(N, NI, D).flops == 142 * 4 * 348 * 301 == 59_496_864


def test_w8a_master_solve_flops():
    # Cholesky d^3 / 3 and two triangular solves of 2 d^2 each
    assert work.master_solve(D).flops == pytest.approx(301**3 / 3 + 4 * 301**2)
    assert work.master_solve(D).flops == pytest.approx(9_090_300.333 + 362_404, rel=1e-9)


def test_w8a_selection_is_bytes_only():
    s = work.selection(N, D, K)
    assert s.flops == 0
    assert s.bytes == 142 * (16 * 45451 + 4 * 2408)


def test_w8a_round_total():
    agg = 142 * (6 * T + D + 1) + T
    total = 4_506_887_448 + 59_496_864 + (301**3 / 3 + 4 * 301**2) + agg
    assert work.round_work(N, NI, D, K).flops == pytest.approx(total, rel=1e-12)
    assert 4.6e9 < total < 4.62e9


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite").flops == 197e12
    assert peaks.peaks_for("TPU v5e").hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")
