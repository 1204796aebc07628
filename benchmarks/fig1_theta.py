"""Fig. 1 — effect of the local approximation quality Theta (via kappa).

Ridge regression on a dense synthetic dataset, ring of K=16 nodes.
Reports suboptimality after a fixed round budget AND the wall-clock
communication/computation trade-off (Fig. 1b)."""
from __future__ import annotations

import time

from repro.core import topology as topo
from repro.core.cola import ColaConfig, run_cola, solve_reference
from benchmarks.common import csv_row, make_ridge


def run(fast: bool = True):
    prob, _ = make_ridge(*(2000, 400) if fast else (10000, 1000))
    opt = solve_reference(prob, rounds=600 if fast else 2000, kappa=10)
    rounds = 40 if fast else 200
    csv_row("fig", "kappa", "rounds", "suboptimality", "time_s")
    for kappa in (0.25, 0.5, 1.0, 2.0, 4.0):
        t0 = time.time()
        res = run_cola(prob, topo.ring(16), ColaConfig(kappa=kappa),
                       rounds=rounds, record_every=rounds - 1)
        csv_row("fig1", kappa, rounds,
                f"{res.history['primal'][-1] - opt:.6f}",
                f"{time.time() - t0:.2f}")
    return {"optimum": opt}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
