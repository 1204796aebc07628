"""The four-chip cell ``eps_plan4_rounds``: a small copy through the whole
harness on four virtual devices, its five per-layer readers, and the ring
count behind ``exchange_roofline.plan4``."""
import json
import os
import subprocess
import sys

import pytest

from bench_small import ROOT

from bench import exchange, harness, trace_reduce
from bench.layer_metrics import _scopes

READERS = ("collective_share.rounds", "exchange_share.plan4",
           "exchange_roofline.plan4", "round_roofline.plan4",
           "env_build_share.plan4")
CONFIG = json.loads((ROOT / "bench/configs/epsilon_logreg_2x2.json")
                    .read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/fixed_rounds.json").read_text())

SMALL_RUN = """
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/bench"]
from bench_small import ROOT, small_cell
from bench import harness

out = harness.run_cell(
    "eps_plan4_rounds", 2**31 + 17, 0.05, False, root=ROOT, allow_cpu=True,
    resolved=small_cell("eps_plan4_rounds", chips=4))
print(json.dumps(out))
"""


def test_small_cell_on_four_devices():
    """The cell as ``BENCHMARK.json`` resolves it, cut to test size: the
    2x2 configuration through ``run_dist_cola(comm="plan")``, correct
    against the reference, with ``rounds_per_s`` among its metrics."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c",
                           SMALL_RUN.format(root=str(ROOT))],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["correct"] is True, out["check"]
    assert out["device"]["count"] == 4
    assert out["metrics"]["rounds_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"rounds_per_s", "peak_hbm_bytes",
                                   "setup_s"}


def _run(chips, trace, solves=()):
    return harness.Run(cell={"chips": chips}, config=CONFIG,
                       traffic=TRAFFIC, chips=chips,
                       device_kind="TPU v5 lite", solves=list(solves),
                       trace=trace)


MIX = "jit(run_block)/while/body/closed_call/shard_map/cola.mix/"
BUILD = "jit(_env_build)/shard_map/cola.env_build/"
SOLVE = "jit(run_block)/while/body/closed_call/shard_map/cola.local_solve/"


def _device(i, exchange_s):
    """A device whose op line covers 2 s of a 4 s window."""
    return _scopes.DeviceScopes(f"/device:TPU:{i}", 2.0, {
        MIX + "cola.exchange/collective-permute:": exchange_s,
        MIX + "dot_general:": 0.25,
        "jit(run_block)/while/body/cola.record/cola.exchange/add:": 0.5,
        BUILD + "dynamic-update-slice:": 0.1,
        SOLVE + "kdn,kd->kn/dot_general:": 0.75}, {})


def _summary(chips):
    devs = [trace_reduce.Device(
        name=f"/device:TPU:{i}", busy_s=1.5,
        op_s={"collective-permute-done.1": 0.2 * (i + 1), "fusion.3": 1.0},
        gaps=[], window=(0, 2_000_000_000)) for i in range(chips)]
    return trace_reduce.Summary(window=(0, 4_000_000_000), devices=devs,
                                host_spans=[])


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_on_one_chip_or_without_a_trace(monkeypatch,
                                                             name):
    monkeypatch.setattr(_scopes, "scopes_of",
                        lambda run: [_device(0, 0.25)])
    solves = [harness.Solve(2.0, 500, 51, None, False)]
    read = harness.reader("layer_metrics", name)
    assert read(_run(1, _summary(1), solves)) is None
    monkeypatch.undo()
    assert read(_run(4, None, solves)) is None


def test_readers_on_a_synthetic_trace(monkeypatch):
    devs = [_device(i, 0.25 * (i + 1)) for i in range(4)]
    monkeypatch.setattr(_scopes, "scopes_of", lambda run: devs)
    solves = [harness.Solve(2.0, 500, 51, None, False),
              harness.Solve(2.0, 500, 51, None, False)]
    run = _run(4, _summary(4), solves)
    read = {name: harness.reader("layer_metrics", name)(run)
            for name in READERS}
    # collective-permute ops over each device's 2 s, mean over devices
    assert read["collective_share.rounds"] == pytest.approx(
        100 * (0.2 + 0.4 + 0.6 + 0.8) / 4 / 2.0)
    # cola.exchange wherever it nests: under the mix and the record
    assert read["exchange_share.plan4"] == pytest.approx(
        100 * sum(0.25 * (i + 1) + 0.5 for i in range(4)) / 4 / 2.0)
    assert read["env_build_share.plan4"] == pytest.approx(100 * 0.1 / 2.0)
    # 1,000 mixing steps, half of them inside each device's op line, at
    # 400,000 floats over one 50 GB/s link each: 16 ms against the
    # exchange inside cola.mix only
    least = 500 * 400_000 * 4 / 50e9
    assert read["exchange_roofline.plan4"] == pytest.approx(
        100 * sum(least / (0.25 * (i + 1)) for i in range(4)) / 4)
    assert 0 < read["round_roofline.plan4"] < 100
    # a program that builds the env on one device reads None there
    monkeypatch.setattr(_scopes, "scopes_of", lambda run: [
        _scopes.DeviceScopes("/device:TPU:0", 2.0,
                             {SOLVE + "dot_general:": 1.0}, {})])
    assert harness.reader("layer_metrics", "env_build_share.plan4")(
        run) is None
    assert harness.reader("layer_metrics", "exchange_roofline.plan4")(
        run) is None


def test_ring_count_for_sixteen_nodes_on_four_chips():
    """Each chip receives one node's d-vector from each neighbour chip a
    mixing step: 2 d 4 bytes, 3.2 MB at epsilon's d, each half on its own
    link."""
    d = CONFIG["data"]["samples"]
    assert exchange.ring_inbound(16, 4) == {1: 1, 3: 1}
    assert exchange.ring_mixing_bytes(16, 4, d) == 2 * d * 4 == 3_200_000
    assert exchange.ring_mixing_seconds(16, 4, d, 50e9) == pytest.approx(
        d * 4 / 50e9)
    # two chips: both vectors come from the one neighbour
    assert exchange.ring_inbound(16, 2) == {1: 2}
    assert exchange.ring_mixing_bytes(16, 1, d) == 0
