"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import problems
from repro.data import synthetic


def timed(fn, *args, **kwargs):
    t0 = time.time()
    out = fn(*args, **kwargs)
    return out, time.time() - t0


def timeit_rounds(runners, rounds, *, repeats=3, ready=None, label="bench"):
    """Best-of-``repeats`` rounds/sec of one runner — or of several, timed
    INTERLEAVED.

    One warmup call per runner owns compilation before any clock starts;
    scheduler noise slows individual runs, never speeds them, so max
    rounds/sec is the stable statistic for a regression gate. A sequence of
    runners is timed round-robin (repeat 1 of each, then repeat 2, ...) so
    a load spike hits every runner of a same-run ratio, not whichever
    happened to go second. Every timed repeat records a ``repro.obs.trace``
    span (``<label>-repeat``), so a scoped tracer around a bench collects
    the per-repeat wall-clock timeline alongside the returned best.

    Returns ``(best, last_result)`` for a single callable,
    ``(bests, last_results)`` lists for a sequence. ``ready`` blocks on the
    result (default: ``jax.block_until_ready(res.state.x_parts)``).
    """
    import jax

    from repro.obs import trace as obs_trace

    if ready is None:
        ready = lambda res: jax.block_until_ready(res.state.x_parts)
    single = callable(runners)
    runs = [runners] if single else list(runners)
    with obs_trace.span(f"{label}-warmup"):
        results = [r() for r in runs]
    bests = [0.0] * len(runs)
    for rep in range(repeats):
        for i, r in enumerate(runs):
            with obs_trace.span(f"{label}-repeat"):
                t0 = time.perf_counter()
                res = r()
                ready(res)
                dt = time.perf_counter() - t0
            bests[i] = max(bests[i], rounds / dt)
            results[i] = res
    if single:
        return bests[0], results[0]
    return bests, results


def make_ridge(n_samples=2000, n_features=400, lam=1e-4, seed=0):
    """Fig. 1 stand-in: dense synthetic normal regression (paper: 10000x1000).

    Reduced by default so the CPU container sweeps in minutes; pass the
    paper's sizes for the full reproduction."""
    x, y, _ = synthetic.regression(n_samples, n_features, seed=seed)
    return problems.ridge_primal(jnp.asarray(x), jnp.asarray(y), lam), (x, y)


def make_lasso(n_samples=2000, n_features=400, lam=1e-5, seed=1):
    """Webspam stand-in (paper: 350k x 16M sparse)."""
    x, y, _ = synthetic.regression(n_samples, n_features, seed=seed,
                                   sparsity_solution=0.1)
    return problems.lasso(jnp.asarray(x), jnp.asarray(y), lam), (x, y)


def csv_row(*cols):
    print(",".join(str(c) for c in cols), flush=True)
