"""Fig. 3 — CoLA across topologies, executed through the topology-program
compiler.

The sweep now runs on the ``repro.topo`` registry (ring / cycles / grid /
torus / expander / complete), static AND under a churn schedule, and for
each graph reports:

* ``beta`` — the mixing contraction governing Theorems 1/2;
* the compiled comm plan's cost model: edge-color count (= ppermutes per
  gossip step) and per-device bytes/round vs the dense all-gather;
* the BLOCK-mode cost model for the same K=16 graph quotiented onto 4
  devices (the CI mesh run_dist_cola actually executes on): block-level
  color count and per-device block-payload bytes — the column showing how
  the quotient collapses dense node-level colorings (complete: 15 -> 3);
* suboptimality after the round budget (static and churn runs);
* a plan-vs-dense oracle check: one compiled-plan gossip step must equal
  ``dense_mix`` on the same W (the property the dist runtime's plan path
  relies on) and one block-plan step must equal it BITWISE, asserted here
  for both the static W and a churn-reweighted round;
* convergence-vs-bytes (``fig3_wire`` rows): for each wire codec (fp32 /
  fp8 / int8, error feedback on and off) the rounds to reach the fp32
  run's final suboptimality and the total wire bytes per device spent
  getting there — the trade the quantized wire buys: EF runs land near the
  fp32 round count at a quarter of the bytes, while the no-EF runs hit
  their quantization noise floor and may never certify ("-").
"""
from __future__ import annotations

import numpy as np

from repro import topo as topo_programs
from repro.core import mixing, topology as topo
from repro.core.cola import ColaConfig, run_cola, solve_reference
from benchmarks.common import csv_row, make_ridge

SWEEP = ("ring", "cycle2", "cycle3", "grid", "torus2d", "expander",
         "complete")

#: (wire, error_feedback) columns of the convergence-vs-bytes table;
#: fp32 has no codec so EF is moot there
WIRE_SWEEP = (("fp32", True), ("fp8", True), ("fp8", False),
              ("int8", True), ("int8", False))


def _check_plan_oracle(graph: topo.Topology, w: np.ndarray, seed: int = 0,
                       atol: float = 1e-5, devices: int = 4) -> None:
    """Compiled-plan mixing == dense_mix on this graph (static + churn);
    the block-quotiented plan must match BITWISE."""
    import jax.numpy as jnp

    plan = topo_programs.compile_plan(graph)
    bplan = topo_programs.compile_block_plan(graph, devices)
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((graph.num_nodes, 8)).astype(np.float32)
    for w_t in (w, topo.reweight_for_active(
            graph, rng.random(graph.num_nodes) < 0.75)):
        got = np.asarray(topo_programs.mix_with_plan(plan, w_t, v))
        want = np.asarray(mixing.dense_mix(jnp.asarray(w_t, jnp.float32),
                                           jnp.asarray(v)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=atol)
        got_b = np.asarray(topo_programs.mix_with_block_plan(bplan, w_t, v))
        np.testing.assert_array_equal(got_b, want)


def run(fast: bool = True):
    prob, _ = make_ridge(lam=1e-5, seed=2)
    opt = solve_reference(prob, rounds=800, kappa=10)
    rounds = 50 if fast else 300
    k, m_dev, d, itemsize = 16, 4, prob.d, 4

    def churn(t, rng):
        return rng.random(k) < 0.8

    csv_row("fig", "topology", "beta", "colors", "bytes_per_dev",
            "blk4_colors", "blk4_bytes_per_dev", "dense_bytes", "rounds",
            "subopt_static", "subopt_churn")
    results = {}
    for name in SWEEP:
        g = topo_programs.build(name, k)
        w = topo.metropolis_weights(g)
        beta = topo.beta(w)
        plan = topo_programs.compile_plan(g)
        bplan = topo_programs.compile_block_plan(g, m_dev)
        _check_plan_oracle(g, w, devices=m_dev)
        static = run_cola(prob, g, ColaConfig(kappa=1.0), rounds=rounds,
                          record_every=rounds - 1)
        churned = run_cola(prob, g, ColaConfig(kappa=1.0), rounds=rounds,
                           record_every=rounds - 1, active_schedule=churn,
                           seed=7)
        sub_s = static.history["primal"][-1] - opt
        sub_c = churned.history["primal"][-1] - opt
        bytes_dev = plan.bytes_per_device_per_step(d, itemsize)
        blk_bytes_dev = bplan.bytes_per_device_per_step(d, itemsize)
        dense_dev = k * d * itemsize
        csv_row("fig3", name, f"{beta:.4f}", plan.num_colors, bytes_dev,
                bplan.num_colors, blk_bytes_dev, dense_dev, rounds,
                f"{sub_s:.6f}", f"{sub_c:.6f}")
        results[name] = {"beta": beta, "colors": plan.num_colors,
                         "bytes_per_device": bytes_dev,
                         "block4_colors": bplan.num_colors,
                         "block4_bytes_per_device": blk_bytes_dev,
                         "subopt_static": sub_s, "subopt_churn": sub_c}

    # -- convergence vs bytes: what the quantized wire actually buys ------
    csv_row("fig", "topology", "wire", "eps", "rounds_to_eps",
            "wire_bytes_per_dev_per_round", "wire_bytes_to_eps")
    for name in SWEEP:
        g = topo_programs.build(name, k)
        plan = topo_programs.compile_plan(g)
        subs = {}
        for wire, ef in WIRE_SWEEP:
            res = run_cola(prob, g,
                           ColaConfig(kappa=1.0, wire=wire,
                                      error_feedback=ef),
                           rounds=rounds, record_every=1)
            subs[(wire, ef)] = np.asarray(res.history["primal"]) - opt
        # target: the fp32 run's final suboptimality (+5% slack) — the
        # quality bar every codec column is racing to at its own byte rate
        eps = 1.05 * max(float(subs[("fp32", True)][-1]), 1e-7)
        wires = results[name]["wire"] = {}
        for (wire, ef), sub in subs.items():
            hit = np.nonzero(sub <= eps)[0]
            r2e = int(hit[0]) + 1 if hit.size else None
            per_round = plan.bytes_per_device_per_step(d, itemsize,
                                                       wire=wire)
            label = wire + ("" if wire == "fp32" else
                            ("+ef" if ef else "-ef"))
            csv_row("fig3_wire", name, label, f"{eps:.2e}",
                    "-" if r2e is None else r2e, per_round,
                    "-" if r2e is None else r2e * per_round)
            wires[label] = {"rounds_to_eps": r2e,
                            "bytes_per_round": per_round,
                            "bytes_to_eps":
                                None if r2e is None else r2e * per_round}
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
