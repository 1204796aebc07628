"""repro.obs: counter totals vs the plan-contract ground truth, the
bitwise telemetry-off pin, the run registry + diff classifier, trace/cache
listeners, and the telemetry-carry lint.

The acceptance scenario from the issue rides `test_counters_match_contract`
and `test_gate_rejections_only_dishonest`: on the fig3 torus with an int8
wire, trim mixing and a seeded 2-node sign-flip attack, the wire-byte
counter equals the contract budget exactly and gate rejections land only on
`atk_dishonest` sender columns — while `test_telemetry_off_bitwise_sim`
pins the off-twin to today's histories.
"""
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro import attack, topo as topo_programs
from repro.core import executor as exec_engine, problems
from repro.core import schedule as schedule_lib, topology
from repro.core.cola import ColaConfig, run_cola
from repro.data import synthetic
from repro.obs import report as obs_report, trace as obs_trace
from repro.obs.cli import sparkline

ROUNDS = 10


@pytest.fixture(autouse=True)
def _registry_off(monkeypatch):
    # keep CI checkouts clean: no test run appends to .repro_runs unless it
    # points REPRO_RUNS_DIR at its own tmpdir
    monkeypatch.setenv(obs_report.ENV_DIR, "off")


@pytest.fixture(scope="module")
def prob():
    x, y, _ = synthetic.regression(120, 48, seed=1, sparsity_solution=0.2)
    return problems.lasso(jnp.asarray(x), jnp.asarray(y), 1e-3)


@pytest.fixture(scope="module")
def graph():
    return topo_programs.build("torus2d", 16)


def _byz():
    return [attack.Byzantine(nodes=(1, 6), mode="sign_flip", scale=10.0,
                             start=4)]


def _assert_history_equal(h_off, h_on):
    assert set(h_off) == set(h_on) - {"telemetry"}
    for key, val in h_off.items():
        got = h_on[key]
        if isinstance(val, (list, np.ndarray)):
            assert np.array_equal(np.asarray(val), np.asarray(got)), key
        else:
            assert val == got, key


@pytest.mark.parametrize("kw", [
    dict(),
    dict(wire="int8"),
    dict(wire="int8", robust="trim"),
])
def test_telemetry_off_bitwise_sim(prob, graph, kw):
    """Turning counters on must not change one bit of the computation."""
    attacks = _byz() if "robust" in kw else None
    runs = {}
    for tel in (False, True):
        cfg = ColaConfig(kappa=1.0, telemetry=tel, **kw)
        runs[tel] = run_cola(prob, graph, cfg, ROUNDS, attacks=attacks)
    assert np.array_equal(np.asarray(runs[False].state.x_parts),
                          np.asarray(runs[True].state.x_parts))
    _assert_history_equal(runs[False].history, runs[True].history)


@pytest.mark.parametrize("wire", ["fp32", "int8"])
def test_counters_match_contract(prob, graph, wire):
    """The byte/permute counters equal rounds x the SAME budget the static
    contract verifier holds the compiled HLO to — no independent model."""
    w = None if wire == "fp32" else wire
    contract = topo_programs.compile_plan(graph).contract(prob.d, wire=w)
    cfg = ColaConfig(kappa=1.0, wire=wire, telemetry=True)
    tel = run_cola(prob, graph, cfg, ROUNDS).history["telemetry"]
    assert tel["rounds"] == ROUNDS
    assert tel["wire_bytes"] == ROUNDS * contract.max_collective_permute_bytes
    assert tel["permutes"] == ROUNDS * contract.max_collective_permute_count
    assert tel["contract"] == contract.describe()
    if wire == "int8":
        assert 0.0 <= tel["saturation_mean"] < 1.0
        assert tel["ef_norm"] > 0.0


def test_gate_rejections_only_dishonest(prob, graph):
    cfg = ColaConfig(kappa=1.0, wire="int8", robust="trim", telemetry=True)
    tel = run_cola(prob, graph, cfg, ROUNDS,
                   attacks=_byz()).history["telemetry"]
    assert tel["dishonest_nodes"] == [1, 6]
    assert tel["gate_dishonest"] >= 1
    assert tel["gate_honest"] == 0
    gate = np.asarray(tel["gate_rejections"])
    assert gate.sum() == tel["gate_total"] == tel["gate_dishonest"]
    assert set(np.nonzero(gate)[0]) == {1, 6}
    # a clean run under the same defense rejects nobody
    clean = run_cola(prob, graph, cfg, ROUNDS).history["telemetry"]
    assert clean["gate_total"] == 0


def test_report_roundtrip_and_find(prob, graph, tmp_path, monkeypatch):
    monkeypatch.setenv(obs_report.ENV_DIR, str(tmp_path))
    cfg = ColaConfig(kappa=1.0, telemetry=True)
    run_cola(prob, graph, cfg, ROUNDS)
    run_cola(prob, graph, cfg, ROUNDS)
    reports = obs_report.load_reports()
    assert len(reports) == 2
    rep = obs_report.RunReport.from_dict(reports[-1])
    assert rep.driver == "run_cola"
    assert rep.rounds == ROUNDS
    assert rep.counters["wire_bytes"] > 0
    assert rep.series["round"] == list(range(ROUNDS))
    assert "block-first-dispatch" in rep.spans["spans"]
    # ref resolution: negative index and run_id prefix hit the same record
    assert obs_report.find_report("-1", reports) == reports[-1]
    assert obs_report.find_report(rep.run_id[:6], reports) == reports[-1]
    with pytest.raises(KeyError):
        obs_report.find_report("nope", reports)


def test_diff_only_telemetry(prob, graph, tmp_path, monkeypatch):
    """Two runs that computed the same thing diff to telemetry-only; a
    different wire does not."""
    monkeypatch.setenv(obs_report.ENV_DIR, str(tmp_path))
    run_cola(prob, graph, ColaConfig(kappa=1.0, telemetry=True), ROUNDS)
    run_cola(prob, graph, ColaConfig(kappa=1.0, telemetry=True), ROUNDS)
    run_cola(prob, graph,
             ColaConfig(kappa=1.0, wire="int8", telemetry=True), ROUNDS)
    reports = obs_report.load_reports()
    twin = obs_report.diff_reports(reports[0], reports[1])
    assert twin["only_telemetry"]
    assert twin["history"] == {}
    wired = obs_report.diff_reports(reports[0], reports[2])
    assert not wired["only_telemetry"]
    assert "wire" in wired["config"]
    # diffing is stable: same inputs, same structured delta
    assert obs_report.diff_reports(reports[0], reports[1]) == twin


def test_registry_retention_prunes_oldest_first(tmp_path, monkeypatch):
    """The JSONL registry is capped (REPRO_RUNS_KEEP, default 200): the
    append path prunes oldest-first, keeps order, and accounts the total
    pruned in the sidecar `obs list` reports."""
    monkeypatch.setenv(obs_report.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(obs_report.ENV_KEEP, "5")
    for i in range(9):
        obs_report.append_report({"run_id": f"run{i:02d}", "rounds": i})
    reports = obs_report.load_reports()
    assert [r["run_id"] for r in reports] == \
        [f"run{i:02d}" for i in range(4, 9)]
    assert obs_report.pruned_total() == 4
    # the cap is re-enforced on every append, not only at the threshold
    obs_report.append_report({"run_id": "run09", "rounds": 9})
    assert len(obs_report.load_reports()) == 5
    assert obs_report.pruned_total() == 5


def test_registry_retention_env_and_overrides(tmp_path, monkeypatch):
    monkeypatch.setenv(obs_report.ENV_DIR, str(tmp_path))
    monkeypatch.delenv(obs_report.ENV_KEEP, raising=False)
    assert obs_report.retention_limit() == obs_report.DEFAULT_KEEP
    assert obs_report.retention_limit(keep=7) == 7
    monkeypatch.setenv(obs_report.ENV_KEEP, "3")
    assert obs_report.retention_limit() == 3
    # keep= beats the env; <= 0 disables pruning entirely
    for i in range(6):
        obs_report.append_report({"run_id": f"r{i}"}, keep=0)
    assert len(obs_report.load_reports()) == 6
    assert obs_report.pruned_total() == 0
    obs_report.append_report({"run_id": "r6"})  # env cap=3 kicks in
    assert len(obs_report.load_reports()) == 3
    assert obs_report.pruned_total() == 4
    monkeypatch.setenv(obs_report.ENV_KEEP, "many")
    with pytest.raises(ValueError, match="REPRO_RUNS_KEEP"):
        obs_report.retention_limit()


def test_obs_list_reports_pruned_count(tmp_path, monkeypatch, capsys):
    from repro.obs import cli as obs_cli
    monkeypatch.setenv(obs_report.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(obs_report.ENV_KEEP, "2")
    for i in range(4):
        obs_report.append_report({"run_id": f"run{i}", "rounds": i})
    assert obs_cli.main(["--dir", str(tmp_path), "list"]) == 0
    out = capsys.readouterr().out
    assert "2 older run(s) pruned by retention" in out
    assert obs_report.ENV_KEEP in out


def test_cache_listener_nesting():
    outer, inner = [], []
    exec_engine.cached_driver(("obs-test", 0), lambda: (lambda: None))
    with exec_engine.cache_listener(lambda k, kind: outer.append(kind)):
        with exec_engine.cache_listener(lambda k, kind: inner.append(kind)):
            exec_engine.cached_driver(("obs-test", 0), lambda: (lambda: None))
        assert inner == ["hits"] and outer == ["hits"]
        exec_engine.cached_driver(("obs-test", 1), lambda: (lambda: None))
    assert inner == ["hits"]          # removed with its scope
    assert outer == ["hits", "misses"]
    exec_engine.cached_driver(("obs-test", 1), lambda: (lambda: None))
    assert outer == ["hits", "misses"]  # both scopes closed: no leak


# --- named phases: device scopes in the programs, host spans in the drivers

ROUND_SCOPES = {"cola.mix", "cola.grad", "cola.local_solve", "cola.update"}


@pytest.fixture(scope="module")
def box_lasso():
    """A lasso with a box, so the Prop.-1 certificate can stop it."""
    x, y, _ = synthetic.regression(48, 16, seed=2, sparsity_solution=0.2)
    return problems.lasso(jnp.asarray(x), jnp.asarray(y), 5e-2, box=5.0)


def _first_block_program(monkeypatch, run, lowered=False):
    """HLO of the first block program ``run()`` dispatches: compiled, or as
    lowered (the op names it hands to the compiler)."""
    texts = []
    real = exec_engine.block_program

    def spy(*args, **kwargs):
        program = real(*args, **kwargs)

        def dispatch(*xs):
            if not texts:
                low = program.lower(*xs)
                texts.append(low.as_text(debug_info=True) if lowered
                             else low.compile().as_text())
            return program(*xs)
        return dispatch

    monkeypatch.setattr(exec_engine, "block_program", spy)
    exec_engine.clear_driver_cache()
    run()
    return texts[0]


def _op_names(hlo: str) -> set:
    """HLO op_name metadata, or the named locations of a lowered module
    (a file location is followed by its line)."""
    return set(re.findall(r'(?:op_name=|loc\()"([^"]*)"(?!:)', hlo))


@pytest.mark.parametrize("program", ["gap", "certificate", "plan"])
def test_round_scopes_in_compiled_programs(program, prob, graph, box_lasso,
                                           monkeypatch):
    """Every phase scope lands in the op_name metadata of the programs
    that run it: the gap-recorder block, a certified streamed block with
    counters on, and the plan round on a one-device mesh, where
    ``cola.exchange`` nests in ``cola.mix``. On one device the exchange has
    nothing to send and XLA folds it away, so that case reads the names
    the lowered program hands to the compiler."""
    import jax

    if program == "gap":
        from repro.analysis import drivers
        hlo = drivers.sim_block_compiled(prob, graph, ColaConfig(kappa=1.0),
                                         4, device=jax.devices()[0]
                                         ).as_text()
        want = ROUND_SCOPES | {"cola.record"}
    elif program == "certificate":
        cfg = ColaConfig(kappa=1.0, telemetry=True,
                         participation=schedule_lib.SampleConfig(
                             k_active=4, mode="dense"))
        hlo = _first_block_program(monkeypatch, lambda: run_cola(
            box_lasso, topology.complete(8), cfg, 16,
            recorder="certificate", eps=1.0, record_every=4, block_size=8))
        want = ROUND_SCOPES | {"cola.record", "cola.schedule",
                               "obs.counters"}
    else:
        from repro.dist.runtime import run_dist_cola
        mesh = jax.make_mesh((1,), ("data",))
        hlo = _first_block_program(monkeypatch, lambda: run_dist_cola(
            prob, graph, ColaConfig(kappa=1.0), mesh, 4, comm="plan"),
            lowered=True)
        want = ROUND_SCOPES | {"cola.record", "cola.exchange"}
    paths = [name.split("/") for name in _op_names(hlo)]
    scopes = {c for path in paths for c in path
              if c.startswith(("cola.", "obs."))}
    assert scopes == want
    if program == "plan":
        assert any(path.index("cola.mix") < path.index("cola.exchange")
                   for path in paths if {"cola.mix", "cola.exchange"}
                   <= set(path))


def test_run_cola_spans(box_lasso):
    """A certified run opens each set-up span once, one stop-flag sync per
    dispatched block and one history fetch."""
    rounds, block = 400, 8
    with obs_trace.use(obs_trace.Tracer()) as tr:
        res = run_cola(box_lasso, topology.ring(8), ColaConfig(kappa=1.0),
                       rounds, recorder="certificate", eps=1.0,
                       record_every=4, block_size=block)
    spans = tr.summary()["spans"]
    for name in ("env-build", "recorder-setup", "schedule-build",
                 "history-fetch"):
        assert spans[name]["count"] == 1, name
    blocks = sum(spans[name]["count"] for name in
                 ("block-first-dispatch", "block-dispatch") if name in spans)
    assert spans["stop-sync"]["count"] == blocks
    # the certificate stopped the run before its budget
    assert blocks < rounds // block
    assert obs_trace.current() is None


def test_bare_span_records_nothing():
    """Outside any use() a span is a profiler annotation only: no tracer
    holds it."""
    tracer = obs_trace.Tracer()
    assert obs_trace.current() is None
    with obs_trace.span("outside"):
        pass
    with obs_trace.use(tracer):
        assert obs_trace.current() is tracer
        with obs_trace.span("inside"):
            pass
    with obs_trace.span("after"):
        pass
    assert obs_trace.current() is None
    assert tracer.summary()["spans"].keys() == {"inside"}
    assert tracer.summary()["spans"]["inside"]["count"] == 1


def test_telemetry_carry_pass():
    """The lint fires on counters captured as constants (seeded in
    analysis.selftest) and stays quiet when the counter genuinely extends
    the scan carry."""
    import jax
    from jax import lax
    from repro.analysis import passes
    from repro.analysis.selftest import seeded_telemetry_constant

    assert seeded_telemetry_constant(), \
        "telemetry-carry pass missed its seeded constant-counter violation"

    def run_off(x):
        return lax.scan(lambda c, _: (c + 1.0, None), x, None, length=4)[0]

    def run_on(x):
        def step(carry, _):
            c, wire_bytes = carry
            return (c + 1.0, wire_bytes + 64.0), None
        return lax.scan(step, (x, jnp.zeros(())), None, length=4)[0][0]

    off = jax.make_jaxpr(run_off)(jnp.float32(0.0))
    on = jax.make_jaxpr(run_on)(jnp.float32(0.0))
    assert passes.telemetry_carry(off, on, where="test:carried") == []


def test_sparkline():
    rising = sparkline([float(i) for i in range(32)], width=16)
    assert len(rising) == 16
    assert rising[-1] == "█"
    assert sparkline([1.0, 1.0, 1.0], width=8)  # constant series: no crash
    # short series are not padded: one cell per point
    assert len(sparkline([2.0, 4.0], width=8, log=True)) == 2


def test_telemetry_requires_block_executor(prob, graph):
    with pytest.raises(ValueError, match="telemetry"):
        run_cola(prob, graph, ColaConfig(kappa=1.0, telemetry=True),
                 ROUNDS, executor="loop")


# --- the shard_map runtime's counters on 1- and 4-device meshes, in a
# subprocess so the suite keeps the single real CPU device (dry-run rule)

DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_RUNS_DIR"] = "off"
    import jax, jax.numpy as jnp, numpy as np
    from repro import topo as topo_programs
    from repro.core import problems
    from repro.data import synthetic
    from repro.core.cola import ColaConfig
    from repro.dist.runtime import run_dist_cola

    x, y, _ = synthetic.regression(120, 48, seed=1, sparsity_solution=0.2)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), 1e-3)
    graph = topo_programs.build("torus2d", 16)
    rounds = 10
    for nd in (1, 4):
        mesh = jax.make_mesh((nd,), ("data",))
        for wire in ("fp32", "int8"):
            runs = {}
            for tel in (False, True):
                cfg = ColaConfig(kappa=1.0, wire=wire, telemetry=tel)
                runs[tel] = run_dist_cola(prob, graph, cfg, mesh, rounds,
                                          comm="plan")
            assert np.array_equal(
                np.asarray(runs[False].state.x_parts),
                np.asarray(runs[True].state.x_parts)), (nd, wire)
            tel = runs[True].history["telemetry"]
            w = None if wire == "fp32" else wire
            if nd == 1:
                # K=16 on one device: every edge is intra-block, no wire
                assert tel["wire_bytes"] == 0, (nd, wire, tel)
            else:
                bplan = topo_programs.compile_block_plan(graph, nd)
                c = bplan.contract(prob.d, wire=w)
                assert tel["wire_bytes"] == \\
                    rounds * c.max_collective_permute_bytes, (nd, wire, tel)
                assert tel["permutes"] == \\
                    rounds * c.max_collective_permute_count, (nd, wire, tel)
    print("OBS_DIST_OK")
""")


@pytest.mark.slow
def test_dist_counters_and_bitwise_off_twin():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", DIST_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "OBS_DIST_OK" in out.stdout, out.stdout + "\n" + out.stderr
