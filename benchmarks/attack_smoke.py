"""Attack-harness smoke: the end-to-end robustness story in under a minute.

Three checks, CI-sized (K=16 torus, seeded 2-node sign-flip Byzantine):

1. undefended: the attacked run visibly breaks AND the honest-cohort
   certificate detects it (``violated_round`` is set) — lying participants
   cannot silently poison a run that claims a duality-gap guarantee;
2. ``robust="trim"`` neutralizes the same attack: the run converges within
   2x the clean round count and the certificate stays sound;
3. the distributed plan executor (``run_dist_cola(comm="plan")``) agrees
   with the simulator on the defended run — trim is bitwise on any mesh
   the host exposes (set XLA_FLAGS=--xla_force_host_platform_device_count=4
   to exercise a real multi-device mesh, as the dist-4dev CI job does).

Prints ``ATTACK_SMOKE_OK`` on success; any failure raises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import attack
from repro.core import problems, topology as topo
from repro.core.cola import ColaConfig, run_cola
from repro.data import synthetic
from repro.dist.runtime import run_dist_cola


def run() -> None:
    x, y, _ = synthetic.regression(48, 24, seed=0)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), 5e-2, box=5.0)
    graph = topo.torus_2d(4, 4)
    byz = attack.Byzantine(nodes=(0, 10), mode="sign_flip", scale=10.0,
                           start=5, seed=1)

    def sim(robust, atk):
        cfg = ColaConfig(kappa=2.0, robust=robust)
        return run_cola(prob, graph, cfg, rounds=2000, record_every=20,
                        recorder="gap+certificate", eps=1.0,
                        attacks=([atk] if atk else None))

    clean = sim(None, None)
    assert clean.history["stop_round"] is not None, \
        "clean run never certified the eps=1.0 gap"
    assert clean.history["violated_round"] is None

    undefended = sim(None, byz)
    assert undefended.history["violated_round"] is not None, \
        "undefended sign-flip attack went undetected by the certificate"
    print(f"attack_smoke,undefended,violated_round="
          f"{undefended.history['violated_round']}")

    trim = sim("trim", byz)
    assert trim.history["violated_round"] is None, \
        "trim-defended run tripped the honest-cohort certificate"
    assert trim.history["stop_round"] is not None and \
        trim.history["stop_round"] <= 2 * clean.history["stop_round"], \
        "trim defense did not converge within 2x the clean round count"
    print(f"attack_smoke,trim,stop_round={trim.history['stop_round']} "
          f"(clean {clean.history['stop_round']})")

    # defended run through the compiled topology-plan executor: bitwise
    # against the simulator on whatever mesh the host exposes
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("nodes",))
    cfg = ColaConfig(kappa=2.0, robust="trim")
    dist = run_dist_cola(prob, graph, cfg, mesh, rounds=2000, comm="plan",
                         record_every=20, recorder="gap+certificate",
                         eps=1.0, attacks=[byz])
    np.testing.assert_array_equal(
        np.asarray(trim.state.x_parts), np.asarray(dist.state.x_parts),
        err_msg="defended plan executor diverged bitwise from simulator")
    assert dist.history["violated_round"] is None
    assert dist.history["stop_round"] == trim.history["stop_round"]
    print(f"attack_smoke,dist_plan,devices={n_dev},bitwise=ok")
    print("ATTACK_SMOKE_OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
