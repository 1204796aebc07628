"""The check that decides ``correct`` fails a broken timed path.

Each test drives a whole small run through the harness on the CPU backend,
with one fault planted in the program underneath: a round that returns its
state unchanged; half of the batch (the samples of every node's gradient)
left out, the mean taken over the rest; the gossip exchange left out; an
answer altered where it is produced. The sound run passes."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench_small import ROOT, run_small


def frozen_rounds(monkeypatch):
    from repro.core import cola
    real = cola._round_body

    def body(*args, **kwargs):
        real(*args, **kwargs)
        return lambda state, *rest, **kw: state
    monkeypatch.setattr(cola, "_round_body", body)


def half_batch(monkeypatch):
    from repro.core import cola
    real = cola.cd_solve_all

    def solve(problem, spec, a_parts, x_parts, grads, *rest, **kwargs):
        d = grads.shape[-1]
        kept = jnp.where(jnp.arange(d) < d // 2, 2.0, 0.0)
        return real(problem, spec, a_parts, x_parts, grads * kept, *rest,
                    **kwargs)
    monkeypatch.setattr(cola, "cd_solve_all", solve)


def no_exchange(monkeypatch):
    from repro.core import mixing
    monkeypatch.setattr(mixing, "mix_power", lambda w, v, steps: v)


def altered_answer(monkeypatch):
    from repro.core import cola
    real = cola.run_cola

    def run(*args, **kwargs):
        res = real(*args, **kwargs)
        x = res.state.x_parts.at[0, 0].add(0.1)
        return res._replace(state=res.state._replace(x_parts=x))
    monkeypatch.setattr(cola, "run_cola", run)


FAULTS = {"frozen_rounds": frozen_rounds, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_answer": altered_answer}


@pytest.mark.parametrize("workload", ["lasso_cert", "eps_rounds"])
@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_fault_fails_the_check(monkeypatch, workload, fault):
    if fault is not None:
        FAULTS[fault](monkeypatch)
    out = run_small(workload)
    assert out["correct"] is (fault is None), out["check"]


MESH_RUN = """
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/bench"]
import jax
from bench_small import ROOT, small_cell
from bench import harness

def run():
    return harness.run_cell(
        "eps_rounds", 13, 0.05, False, root=ROOT, allow_cpu=True,
        resolved=small_cell("eps_rounds", chips=4,
                            config="bench/configs/epsilon_logreg_2x2.json"))

sound = run()
jax.lax.ppermute = lambda x, axis_name, perm: x
dropped = run()
print(json.dumps([sound["correct"], dropped["correct"], dropped["check"]]))
"""


def test_mesh_exchange_left_out_fails_the_check():
    """The epsilon problem through ``run_dist_cola(comm="plan")`` on four
    virtual devices (the configuration of the four-chip cell that waits in
    PERF.md): sound, then with every collective-permute between devices
    returning its own input."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c",
                           MESH_RUN.format(root=str(ROOT))],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, dropped, check = json.loads(proc.stdout.splitlines()[-1])
    assert sound is True
    assert dropped is False, check
