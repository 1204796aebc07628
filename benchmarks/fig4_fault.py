"""Fig. 4 / Fig. 6 — fault tolerance: each node stays with probability p per
round; leavers freeze x_[k] (Fig. 4) or reset it (Fig. 6). Plus the attack
columns: Byzantine fraction x robust mixing mode -> suboptimality, the
fault model where participants LIE instead of leaving (repro.attack).

Schedules are pre-materialized host-side into (T, K) arrays and handed to
``run_cola`` directly (the same stacked-schedule path the attack transforms
ride), drawn from the same rng stream the old per-round closures consumed —
the fig4/fig6/def5 rows are bitwise what the closure path produced.
"""
from __future__ import annotations

import numpy as np

from repro import attack
from repro.core import topology as topo
from repro.core.cola import ColaConfig, run_cola, solve_reference
from benchmarks.common import csv_row, make_ridge


def _stay_masks(rounds: int, k: int, p_stay: float, seed: int = 0
                ) -> np.ndarray:
    """(T, K) bool: node k participates in round t with probability p_stay.
    One rng.random(k) draw per round — the exact stream the closure form
    ``lambda t, rng: rng.random(k) < p_stay`` consumed."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.random(k) < p_stay for _ in range(rounds)])


def _straggler_budgets(rounds: int, k: int, full: int, seed: int = 0
                       ) -> np.ndarray:
    """(T, K) int32: each round, each node straggles (quarter CD budget)
    with probability 1/2 — same draw order as the old budgets closure."""
    rng = np.random.default_rng(seed)
    out = np.full((rounds, k), full, np.int32)
    for t in range(rounds):
        out[t, rng.random(k) < 0.5] = max(full // 4, 1)
    return out


def run(fast: bool = True):
    prob, _ = make_ridge(lam=1e-4, seed=3)
    opt = solve_reference(prob, rounds=800, kappa=10)
    rounds = 80 if fast else 400
    k = 16
    graph = topo.connected_cycle(k, 2)

    csv_row("fig", "p_stay", "mode", "rounds", "suboptimality")
    results = {}
    for p in (0.5, 0.8, 0.9, 1.0):
        res = run_cola(prob, graph, ColaConfig(kappa=2.0), rounds=rounds,
                       record_every=rounds - 1,
                       active_schedule=(None if p == 1.0
                                        else _stay_masks(rounds, k, p)))
        sub = res.history["primal"][-1] - opt
        csv_row("fig4", p, "freeze", rounds, f"{sub:.6f}")
        results[("freeze", p)] = sub
    res = run_cola(prob, graph, ColaConfig(kappa=2.0), rounds=rounds,
                   record_every=rounds - 1,
                   active_schedule=_stay_masks(rounds, k, 0.8),
                   leave_mode="reset")
    csv_row("fig6", 0.8, "reset", rounds,
            f"{res.history['primal'][-1] - opt:.6f}")

    # §2 / Definition 5: heterogeneous Theta_k — half the nodes straggle at
    # a quarter of the CD budget every round
    full = int(2.0 * (prob.n // k + 1))
    res = run_cola(prob, graph, ColaConfig(kappa=2.0), rounds=rounds,
                   record_every=rounds - 1,
                   budget_schedule=_straggler_budgets(rounds, k, full))
    csv_row("def5", "half-nodes-1/4-budget", "straggle", rounds,
            f"{res.history['primal'][-1] - opt:.6f}")

    # Byzantine columns: a fraction of nodes sign-flip their wire payloads
    # (x10, warm onset at round 5 — see the repro.attack threat model) and
    # the mixing layer either trusts them (robust None) or aggregates
    # robustly. Suboptimality is the attack analogue of the churn columns.
    csv_row("fig", "byz_frac", "robust", "rounds", "suboptimality")
    for frac in (1 / k, 2 / k):
        byz = attack.Byzantine(fraction=frac, mode="sign_flip", scale=10.0,
                               start=5, seed=1)
        for robust in (None, "trim", "median"):
            res = run_cola(prob, graph, ColaConfig(kappa=2.0, robust=robust),
                           rounds=rounds, record_every=rounds - 1,
                           attacks=[byz])
            sub = res.history["primal"][-1] - opt
            csv_row("fig4atk", f"{frac:.4f}", robust or "none", rounds,
                    f"{sub:.6f}")
            results[("attack", frac, robust)] = sub
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
