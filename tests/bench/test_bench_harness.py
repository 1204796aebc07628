"""The harness finds every cell's files by name, takes new ones without an
edit, prints the result line the benchmark's contract fixes, and refuses to
run without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_small import ROOT, load_recorded, run_small

from bench import harness

SPEC = harness.load_spec(ROOT)
BASE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(workload):
    r = harness.resolve(SPEC, workload, ROOT)
    assert r["config"]["name"] == r["cell"]["config"]
    assert r["config"]["chips"] == r["cell"]["chips"]
    assert r["traffic"]["kind"] in ("certified_solves", "fixed_rounds")
    assert {m["name"] for m in r["end_to_end"]} >= {"setup_s",
                                                    "peak_hbm_bytes"}
    assert r["per_layer"]
    for kind, metrics in (("end_to_end", r["end_to_end"]),
                          ("layer_metrics", r["per_layer"])):
        for m in metrics:
            assert callable(harness.reader(kind, m["name"], ROOT))
    limits = r["config"]["limits"]
    assert "x_rel" in limits


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench/configs/fig1_lasso.json").read_text())
    config.update(name="fig1_lasso_k4")
    config["solver"]["kappa"] = 4
    (root / "bench/configs/fig1_lasso_k4.json").write_text(json.dumps(config))
    traffic = json.loads(
        (ROOT / "bench/traffic/certified_solves.json").read_text())
    traffic["eps"] = 0.5
    (root / "bench/traffic/tighter_solves.json").write_text(
        json.dumps(traffic))
    (root / "bench/layer_metrics/solves.cert.py").write_text(
        "def read(run):\n    return float(len(run.solves))\n")
    spec["configs"].append({"name": "fig1_lasso_k4", "source": "x",
                            "file": "bench/configs/fig1_lasso_k4.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "lasso_k4", "config": "fig1_lasso_k4",
                              "traffic": "tighter_solves", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "solves.cert", "unit": "solves",
                              "better": "higher", "source": "host_clock",
                              "layer": "certificate",
                              "moves": "time_to_cert_s",
                              "workloads": ["lasso_k4"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "lasso_cert" in m["workloads"]:
            m["workloads"].append("lasso_k4")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    r = harness.resolve(harness.load_spec(root), "lasso_k4", root)
    assert r["config"]["solver"]["kappa"] == 4
    assert r["traffic"]["eps"] == 0.5
    assert [m["name"] for m in r["per_layer"]] == ["solves.cert"]
    assert harness.reader("layer_metrics", "solves.cert", root)(
        harness.Run(cell={}, config={}, traffic={}, chips=1, device_kind="x",
                    solves=[1, 2])) == 2.0


@pytest.mark.parametrize("workload", ["lasso_cert", "eps_rounds"])
def test_result_line_keys(workload):
    out = run_small(workload)
    assert list(out) == BASE_KEYS + ["check"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    names = {m["name"] for m in harness.resolve(SPEC, workload,
                                                ROOT)["end_to_end"]}
    # peak memory reads 0 on the CPU backend, which reports none
    assert set(out["metrics"]) == names
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for entry in out["check"].values():
        assert set(entry) == {"value", "limit"}
        assert entry["value"] <= entry["limit"]
    json.dumps(out)


def test_traced_result_carries_breakdown(monkeypatch):
    from bench import counts, trace_reduce

    # the CPU backend's trace has no device plane: read a recorded TPU one,
    # against that chip's peaks
    monkeypatch.setattr(trace_reduce, "load", lambda _dir: load_recorded())
    v5e = counts.peaks("TPU v5 lite")
    monkeypatch.setattr(counts, "peaks", lambda kind: v5e)
    out = run_small("lasso_cert", trace=True)
    assert list(out) == BASE_KEYS + ["breakdown", "check"]
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert "rounds_to_cert.cert" in out["metrics"]


def _bench_run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lasso_cert",
         "--seed", "2147483653", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_chip():
    proc = _bench_run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests" / "bench", tmp_path / "tests" / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _bench_run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
