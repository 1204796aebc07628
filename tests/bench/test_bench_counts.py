"""The benchmark's operation and byte counts against hand counts for the
three cells' shapes, and its table of peaks."""
import json

import pytest

import bench_small  # noqa: F401  (puts the repository root on the path)
from bench import counts

LASSO = counts.Shape(d=10_000, n=1_000, k=16, kappa=8, problem="lasso")
EPSILON = counts.Shape(d=400_000, n=2_000, k=16, kappa=1,
                       problem="logistic_l2")
V5E = "TPU v5 lite"


def test_lasso_round_by_hand():
    # n_k = 63 (1,000 over 16, padded), 504 steps: the mixing 2*16*16*1e4,
    # A_k^T grad and A_k dx 2*16*1e4*63 each, the steps 16*504*(2*63+10),
    # grad 16*1e4 and the v update 2*16*1e4
    assert (LASSO.n_k, LASSO.steps) == (63, 504)
    flops = 5.12e6 + 2 * 20.16e6 + 16 * 504 * 136 + 0.16e6 + 0.32e6
    # two passes over a_parts (16*1e4*63 floats), v read and written by
    # the mixing and the update, the Gram blocks once
    nbytes = 2 * 40.32e6 + 2 * 1.28e6 + 16 * 63 * 63 * 4
    cost = counts.round_cost(LASSO)
    assert cost.flops == pytest.approx(flops, rel=1e-12)
    assert cost.bytes == pytest.approx(nbytes, rel=1e-12)
    # the issue's figures: about 47 MFLOP and 80 MB of A a round
    assert cost.flops == pytest.approx(47e6, rel=0.01)
    assert 2 * LASSO.blocks_bytes == pytest.approx(80.6e6, rel=0.001)


def test_epsilon_round_and_record_by_hand():
    assert (EPSILON.n_k, EPSILON.steps) == (125, 125)
    cost = counts.round_cost(EPSILON)
    # v read and written by the mixing and by the update: 2 x 51.2 MB
    assert cost.bytes == pytest.approx(2 * 3.2e9 + 2 * 51.2e6 + 1e6,
                                       rel=1e-12)
    assert cost.flops == pytest.approx(
        204.8e6 + 2 * 1.6e9 + 16 * 125 * 260 + 25.6e6 + 12.8e6, rel=1e-12)
    gap = counts.record_cost(EPSILON, "gap")
    assert gap.bytes == pytest.approx(6.4e9 + 25.6e6, rel=1e-12)
    assert gap.flops == pytest.approx(3.2e9 + 76.8e6, rel=1e-12)
    env = counts.env_build(EPSILON)
    assert env.flops == pytest.approx(2 * 16 * 400_000 * 125 ** 2)  # 0.2 TF
    assert env.bytes == pytest.approx(3 * 3.2e9)


def test_certificate_record_reads_a_parts_twice():
    cost = counts.record_cost(LASSO, "certificate")
    assert cost.bytes == pytest.approx(2 * 40.32e6 + 0.64e6, rel=1e-12)
    both = counts.record_cost(LASSO, "gap+certificate")
    assert both == counts.record_cost(LASSO, "gap") + cost


def test_residual_path_counts_steps_over_d():
    res = counts.Shape(d=10_000, n=1_000, k=16, kappa=8, problem="lasso",
                       cd_path="residual")
    assert counts.local_solve(res).flops == 16 * 504 * (4 * 10_000 + 10)
    assert counts.local_solve(res).bytes == 8 * res.blocks_bytes


@pytest.mark.parametrize("shape,chips,seconds", [
    (LASSO, 1, 83_454_016 / 819e9),
    (EPSILON, 1, 6_503_400_000 / 819e9),
    (EPSILON, 4, 6_503_400_000 / (4 * 819e9)),
])
def test_rounds_are_bound_by_bytes(shape, chips, seconds):
    secs, bound = counts.roofline_seconds(counts.round_cost(shape),
                                          counts.peaks(V5E), chips)
    assert bound == "bytes"
    assert secs == pytest.approx(seconds, rel=1e-12)


def test_window_sums_rounds_and_records():
    one = counts.window_rounds(EPSILON, 500, 50, "gap")
    assert one == 500 * counts.round_cost(EPSILON) + 50 * counts.gap_record(
        EPSILON)


def test_shape_of_every_configuration():
    root = counts.PEAKS_FILE.parents[1]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        config = json.loads((root / entry["file"]).read_text())
        assert counts.Shape.of(config).k == config["solver"]["nodes"]


def test_peaks_of_v5e_and_unknown_kind():
    peak = counts.peaks(V5E)
    assert peak["flops_bf16"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        counts.peaks("TPU v9 imaginary")
