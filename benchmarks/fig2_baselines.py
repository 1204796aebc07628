"""Fig. 2 — CoLA vs DIGing vs D-ADMM, ridge (strongly cvx) + lasso (general).

LIBSVM URL/webspam are not shippable offline; dense synthetic stand-ins with
the paper's regularization are used (DESIGN.md §8). DIGing's step is grid
searched (paper methodology); D-ADMM uses the Shi et al. rho with a CD budget
matched to CoLA's. Also logs the consensus-violation trajectory (Fig. 5)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import baselines as bl, problems, topology as topo
from repro.core.cola import ColaConfig, run_cola, solve_reference
from benchmarks.common import csv_row, make_lasso, make_ridge


def run(fast: bool = True):
    graph = topo.ring(16)
    rounds = 60 if fast else 400
    out = {}

    # --- Ridge (strongly convex): CoLA primal & dual mappings --------------
    prob, (x, y) = make_ridge(lam=1e-4)
    opt = solve_reference(prob, rounds=800, kappa=10)
    csv_row("fig", "method", "rounds", "final_suboptimality",
            "final_consensus_violation")
    for name, kwargs in [("cola_primal", {}),]:
        res = run_cola(prob, graph, ColaConfig(kappa=2.0), rounds=rounds,
                       record_every=max(rounds // 8, 1), **kwargs)
        csv_row("fig2_ridge", name, rounds,
                f"{res.history['primal'][-1] - opt:.6f}",
                f"{res.history['consensus_violation'][-1]:.3e}")
        out[name] = res.history
    dual = problems.ridge_dual(jnp.asarray(x), jnp.asarray(y), 1e-4)
    dopt = solve_reference(dual, rounds=800, kappa=10)
    res = run_cola(dual, graph, ColaConfig(kappa=2.0), rounds=rounds,
                   record_every=max(rounds // 8, 1))
    csv_row("fig2_ridge", "cola_dual", rounds,
            f"{res.history['primal'][-1] - dopt:.6f}",
            f"{res.history['consensus_violation'][-1]:.3e}")

    cons = bl.make_consensus_problem(x, y, 16, loss="square", reg="l2",
                                     lam=1e-4)
    w_opt = np.linalg.solve(x.T @ x + 1e-4 * np.eye(x.shape[1]), x.T @ y)
    f_opt = float(cons.objective(jnp.asarray(w_opt)))
    best, best_step = np.inf, None
    for step in (0.003, 0.01, 0.03, 0.1, 0.3):
        r = bl.run_diging(cons, graph, step=step, rounds=rounds // 2,
                          record_every=rounds // 2 - 1)
        v = r.history["objective"][-1] - f_opt
        if np.isfinite(v) and v < best:
            best, best_step = v, step
    csv_row("fig2_ridge", f"diging(step={best_step})", rounds // 2,
            f"{best:.6f}", "-")
    r = bl.run_dadmm(cons, graph, rho=1.0, rounds=rounds // 2,
                     inner_steps=10, record_every=rounds // 2 - 1)
    csv_row("fig2_ridge", "dadmm(rho=1)", rounds // 2,
            f"{r.history['objective'][-1] - f_opt:.6f}", "-")

    # --- Lasso (general convex) --------------------------------------------
    lprob, (lx, ly) = make_lasso(lam=1e-5)
    lopt = solve_reference(lprob, rounds=800, kappa=10)
    res = run_cola(lprob, graph, ColaConfig(kappa=2.0), rounds=rounds,
                   record_every=max(rounds // 8, 1))
    csv_row("fig2_lasso", "cola", rounds,
            f"{res.history['primal'][-1] - lopt:.6f}",
            f"{res.history['consensus_violation'][-1]:.3e}")
    lcons = bl.make_consensus_problem(lx, ly, 16, loss="square", reg="l1",
                                      lam=1e-5)
    # consensus-form lasso has the same optimal value as the CoLA mapping
    lbest = np.inf
    for step in (0.003, 0.01, 0.03, 0.1):
        r = bl.run_dgd(lcons, graph, step=step, rounds=rounds // 2,
                       record_every=rounds // 2 - 1, diminishing=True)
        v = r.history["objective"][-1] - lopt
        if np.isfinite(v):
            lbest = min(lbest, v)
    csv_row("fig2_lasso", "dgd(best)", rounds // 2, f"{lbest:.6f}", "-")

    # --- Fig. 5: consensus-violation trajectory -----------------------------
    traj = out["cola_primal"]["consensus_violation"]
    csv_row("fig5", "cola_primal_cv_trajectory",
            *[f"{v:.3e}" for v in traj])
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
