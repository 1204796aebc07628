"""Bring-up check of COLA's round engine on a TPU, through its entry points.

    python chip_smoke.py [--seed N]            # one chip: phases (a), (b)
    python chip_smoke.py --chips 4 [--seed N]  # four chips: the mesh phase

(a) L2 logistic regression at the size of LIBSVM's ``epsilon`` (400,000
    samples x 2,000 features, dense f32, drawn from the seed by
    ``data.synthetic.classification``) on a K=16 ring through ``run_cola``:
    block executor, gap recorder, 20 rounds in blocks of 10. With
    n_k = 125 << d = 400,000 the local solver takes the Gram path. Checked
    against a float64 host recomputation from the returned state: F_A(x_T),
    the duality gap, and the Lemma-1 invariant (1/K) sum_k v_k = A x. The
    gap must also be non-negative and fall.
(b) The paper's Fig.-1 lasso (dense 10,000 x 1,000) run to the Prop.-1
    certificate, ``run_cola(..., recorder="gap+certificate", eps=...)``. It
    must stop at certification, and its ``x_parts`` must agree with the
    same rounds run on the host's CPU backend in this process.
(mesh) ``run_dist_cola(comm="plan")`` on problem (a): K=16 over a
    four-chip mesh (a BlockPlan, four nodes per chip), against ``run_cola``
    on one chip on the same data and rounds.

Earlier lines report what was measured; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, without that line, when JAX finds no TPU, when
the repository's ``src/`` is not beside it, or when any check fails. It
runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

import numpy as np

K = 16                                # nodes, on a ring
EPS_SHAPE = (400_000, 2_000)          # epsilon: samples (d) x features (n)
EPS_LAM = 1.0                         # L2 weight (columns have unit norm)
EPS_ROUNDS, EPS_BLOCK = 20, 10
LASSO_SHAPE = (10_000, 1_000)         # Fig. 1
LASSO_LAM, LASSO_BOX, LASSO_KAPPA = 5e-2, 5.0, 8.0
LASSO_EPS, LASSO_BUDGET, LASSO_RECORD = 1.0, 4000, 20

# Tolerances, set from float32 (unit roundoff 6e-8) before any chip run:
# a sum over 4e5 terms loses ~log2(4e5) = 19 ulps, and 20 rounds of
# node updates compound a few ulps each.
TOL_PRIMAL = 1e-5      # |F_A(x_T) - float64| / |float64|
TOL_GAP = 1e-5         # |gap - float64 gap| / (|H_A| + |H_B|); the same
#                        bound below zero counts as non-negative
TOL_INVARIANT = 1e-4   # ||(1/K) sum_k v_k - A x|| / (||A x|| + 1)
TOL_X_CPU = 1e-3       # ||x_tpu - x_cpu|| / ||x_cpu|| after the same rounds
TOL_X_DIST = 1e-5      # ||x_dist - x_sim|| / ||x_sim|| after the same rounds

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise SmokeFailure(what)


class Compiles:
    """Counts XLA compilations (persistent-cache loads included) and the
    seconds they take, process-wide."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, fun_name="?", **_):
        if event == BACKEND_COMPILE_EVENT:
            self.events.append((duration, fun_name))

    def snapshot(self) -> int:
        return len(self.events)

    def since(self, start: int) -> tuple[int, float, str]:
        """Compilations after ``snapshot()`` returned ``start``: count,
        seconds, and the slowest few by name."""
        new = self.events[start:]
        slow = sorted(new, reverse=True)[:3]
        return len(new), sum(d for d, _ in new), ", ".join(
            f"{name} {d:.1f}s" for d, name in slow)


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def timed_run(fn, compiles: Compiles, label: str):
    """Run ``fn`` to completion (``block_until_ready`` on the state) and
    report its wall time and the compilations it triggered."""
    import jax
    start = compiles.snapshot()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready(res.state)
        wall = time.perf_counter() - t0
    n, secs, slowest = compiles.since(start)
    donation = [w for w in caught if "donat" in str(w.message).lower()]
    log(f"  {label}: {wall:.3f}s wall, {n} compilations taking {secs:.3f}s"
        f"{' (slowest: ' + slowest + ')' if n else ''}, {len(donation)} "
        "donation warnings")
    for w in donation[:3]:
        log(f"    donation warning: {str(w.message)[:200]}")
    return res, n, donation


# -- float64 host references -------------------------------------------------

def _rows(a, fn, chunk: int = 20_000):
    for i in range(0, a.shape[0], chunk):
        fn(slice(i, i + chunk), a[i:i + chunk].astype(np.float64))


def matvec64(a, x):
    out = np.empty(a.shape[0])

    def f(sl, blk):
        out[sl] = blk @ x
    _rows(a, f)
    return out


def rmatvec64(a, w):
    out = np.zeros(a.shape[1])

    def f(sl, blk):
        out[:] += blk.T @ w[sl]
    _rows(a, f)
    return out


def logistic_report64(a, y, lam, x_parts, v_stack):
    """Float64 Lemma-1/2 quantities of L2 logistic regression at the
    returned state (the ``duality.gap_report`` formulas)."""
    n = a.shape[1]
    x = np.asarray(x_parts, np.float64).reshape(-1)[:n]
    v = np.asarray(v_stack, np.float64)
    y = np.asarray(y, np.float64)
    ax = matvec64(a, x)
    f = lambda u: np.sum(np.logaddexp(0.0, -y * u), axis=-1)
    g = 0.5 * lam * np.dot(x, x)
    w = -y / (1.0 + np.exp(y * v))                  # grad f at every v_k
    u = np.clip(-w * y, 1e-12, 1.0 - 1e-12)
    f_conj = np.sum(u * np.log(u) + (1.0 - u) * np.log1p(-u), axis=-1)
    atw = rmatvec64(a, w.mean(axis=0))
    h_a = np.mean(f(v)) + g
    h_b = np.mean(f_conj) + np.sum(atw ** 2) / (2.0 * lam)
    resid = (np.linalg.norm(v.mean(axis=0) - ax)
             / (np.linalg.norm(ax) + 1.0))
    return {"primal": f(ax) + g, "h_a": h_a, "h_b": h_b, "gap": h_a + h_b,
            "invariant": resid}


# -- phases --------------------------------------------------------------------

def epsilon_data(seed: int, shape=EPS_SHAPE):
    from repro.data import synthetic
    t0 = time.perf_counter()
    x, y, _ = synthetic.classification(*shape, seed=seed)
    log(f"  data: A {x.shape} {x.dtype}, y {y.shape}, drawn in "
        f"{time.perf_counter() - t0:.1f}s")
    return x, y


def epsilon_problem(x, y, lam=EPS_LAM):
    import jax.numpy as jnp
    from repro.core import problems
    return problems.logistic_l2(jnp.asarray(x), jnp.asarray(y), lam)


def phase_epsilon(seed: int, compiles: Compiles, *, shape=EPS_SHAPE,
                  rounds=EPS_ROUNDS, block=EPS_BLOCK) -> None:
    import jax
    from repro.core import topology as topo
    from repro.core.cola import ColaConfig, run_cola
    from repro.core.partition import make_partition
    from repro.core.subproblem import gram_pays

    log(f"phase (a): L2 logistic regression, epsilon-sized, K={K} ring")
    x, y = epsilon_data(seed, shape)
    prob = epsilon_problem(x, y)
    n_k = make_partition(prob.n, K).block
    log(f"  d={prob.d} n={prob.n} n_k={n_k}: Gram path "
        f"{gram_pays(prob.d, n_k, 4)}; a_parts {(K, prob.d, n_k)}")
    graph, cfg = topo.ring(K), ColaConfig()

    def run():
        return run_cola(prob, graph, cfg, rounds, record_every=1,
                        block_size=block)
    timed_run(run, compiles, "warm-up run (compiles)")
    res, steady_compiles, _ = timed_run(run, compiles, "steady run")
    check(steady_compiles == 0, "no compilation after warm-up")
    h = res.history
    log("  gap by round: " + " ".join(f"{g:.6g}" for g in h["gap"]))
    ref = logistic_report64(x, y, EPS_LAM, res.state.x_parts,
                            res.state.v_stack)
    scale = abs(ref["h_a"]) + abs(ref["h_b"])
    r_primal = abs(h["primal"][-1] - ref["primal"]) / abs(ref["primal"])
    r_gap = abs(h["gap"][-1] - ref["gap"]) / scale
    low = min(min(h["gap"]), ref["gap"]) / scale
    log(f"  F_A(x_T): device {h['primal'][-1]:.9g}, float64 "
        f"{ref['primal']:.9g}, relative residual {r_primal:.3e}")
    log(f"  gap: device {h['gap'][-1]:.9g}, float64 {ref['gap']:.9g}, "
        f"residual / (|H_A|+|H_B|) {r_gap:.3e}; lowest {low:.3e}")
    log(f"  Lemma-1 invariant residual {ref['invariant']:.3e}")
    check(r_primal <= TOL_PRIMAL, f"F_A(x_T) within {TOL_PRIMAL:g}")
    check(r_gap <= TOL_GAP, f"gap within {TOL_GAP:g} of float64")
    check(low >= -TOL_GAP, "gap non-negative within tolerance")
    check(h["gap"][-1] < h["gap"][0], "gap falls")
    check(ref["invariant"] <= TOL_INVARIANT,
          f"Lemma-1 invariant within {TOL_INVARIANT:g}")
    log(f"  peak_bytes_in_use: {peak_bytes(jax.devices()[:1])}")


def phase_lasso(seed: int, compiles: Compiles, *, shape=LASSO_SHAPE,
                eps=LASSO_EPS, budget=LASSO_BUDGET) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import problems, topology as topo
    from repro.core.cola import ColaConfig, run_cola
    from repro.data import synthetic

    log(f"phase (b): Fig.-1 lasso {shape}, K={K} ring, certificate eps={eps}")
    x, y, _ = synthetic.regression(*shape, seed=seed, sparsity_solution=0.2)
    graph, cfg = topo.ring(K), ColaConfig(kappa=LASSO_KAPPA)

    def lasso():
        return problems.lasso(jnp.asarray(x), jnp.asarray(y), LASSO_LAM,
                              box=LASSO_BOX)
    prob = lasso()
    res, _, _ = timed_run(
        lambda: run_cola(prob, graph, cfg, budget, record_every=LASSO_RECORD,
                         recorder="gap+certificate", eps=eps),
        compiles, "certified run")
    h = res.history
    stop = h["stop_round"]
    log(f"  stop_round {stop} of budget {budget}; gap at stop "
        f"{h['gap'][-1]:.6g}; condition 9 on {int(h['cond9_nodes'][-1])}"
        f"/{K}, condition 10 on {int(h['cond10_nodes'][-1])}/{K}")
    check(stop is not None and h["certified"][-1] == 1,
          "stopped at certification")
    check(h["gap"][-1] <= eps, "true gap at certification <= eps")
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = run_cola(lasso(), graph, cfg, stop + 1,
                       record_every=LASSO_RECORD)
    x_tpu = np.asarray(res.state.x_parts, np.float64)
    x_cpu = np.asarray(cpu.state.x_parts, np.float64)
    r_x = np.linalg.norm(x_tpu - x_cpu) / np.linalg.norm(x_cpu)
    log(f"  x_parts after {stop + 1} rounds, device vs CPU backend: "
        f"relative L2 {r_x:.3e}, max |diff| {np.abs(x_tpu - x_cpu).max():.3e}")
    check(r_x <= TOL_X_CPU, f"x_parts within {TOL_X_CPU:g} of the CPU run")
    log(f"  peak_bytes_in_use: {peak_bytes(jax.devices()[:1])}")


def phase_mesh(seed: int, compiles: Compiles, *, chips: int,
               shape=EPS_SHAPE, rounds=EPS_ROUNDS, block=EPS_BLOCK) -> None:
    import jax
    from repro.analysis import drivers
    from repro.core import executor, topology as topo
    from repro.core.cola import ColaConfig, run_cola
    from repro.dist.runtime import run_dist_cola
    from repro.launch import hlo_analysis

    devices = jax.devices()[:chips]
    log(f"phase (mesh): K={K} over {chips} chips, comm='plan' "
        f"({K // chips} nodes per chip), against run_cola on one chip")
    x, y = epsilon_data(seed, shape)
    prob = epsilon_problem(x, y)
    graph, cfg = topo.ring(K), ColaConfig()
    sim, _, _ = timed_run(
        lambda: run_cola(prob, graph, cfg, rounds, record_every=1,
                         block_size=block), compiles, "run_cola, one chip")
    mesh = jax.make_mesh((chips,), ("data",), devices=devices)
    a_parts_shape = (K, prob.d, -(-prob.n // K))
    seen = []

    def probe(_key, _kind):
        # at the first block dispatch the runtime's env is live: record
        # where its node blocks sit
        if not seen:
            seen.extend(sorted({d.id for d in a.devices()})
                        for a in jax.live_arrays()
                        if a.shape == a_parts_shape)
    with executor.cache_listener(probe):
        dist, _, _ = timed_run(
            lambda: run_dist_cola(prob, graph, cfg, mesh, rounds,
                                  comm="plan", record_every=1,
                                  block_size=block),
            compiles, f"run_dist_cola, {chips} chips")
    log(f"  live a_parts arrays at first dispatch, by device ids: {seen}")
    check(any(len(ids) == chips for ids in seen),
          f"a_parts shards sit on {chips} distinct devices")
    x_sim = np.asarray(sim.state.x_parts, np.float64)
    x_dist = np.asarray(dist.state.x_parts, np.float64)
    r_x = np.linalg.norm(x_dist - x_sim) / np.linalg.norm(x_sim)
    bitwise = bool(np.array_equal(np.asarray(sim.state.x_parts),
                                  np.asarray(dist.state.x_parts))
                   and np.array_equal(np.asarray(sim.state.v_stack),
                                      np.asarray(dist.state.v_stack)))
    log(f"  x_parts dist vs sim: relative L2 {r_x:.3e}; state bitwise "
        f"equal: {bitwise}")
    log("  gap by round, sim:  " + " ".join(
        f"{g:.6g}" for g in sim.history["gap"]))
    log("  gap by round, dist: " + " ".join(
        f"{g:.6g}" for g in dist.history["gap"]))
    check(r_x <= TOL_X_DIST, f"dist x_parts within {TOL_X_DIST:g} of sim")
    # the round alone, rebuilt by the analysis driver on this mesh: the
    # block program the run dispatched also holds the gap recorder, whose
    # all-gathers are by design (GapRecorder.collective_footprint)
    hlo, plan = drivers.block_round_hlo(prob, graph, K, chips, mesh=mesh)
    counts = hlo_analysis.analyze(hlo)["collective_counts"]
    log(f"  one plan round, rebuilt without the recorder and compiled: "
        f"{int(counts['collective-permute'])} collective-permutes, "
        f"{int(counts['all-gather'])} all-gathers ({plan.num_colors} block "
        "colors)")
    log(f"  peak_bytes_in_use by device: {peak_bytes(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing runs without one",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind}, {len(devices)} visible, "
        f"using {args.chips}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    compiles = Compiles()
    try:
        if args.chips == 1:
            phase_epsilon(args.seed, compiles)
            phase_lasso(args.seed, compiles)
        else:
            phase_mesh(args.seed, compiles, chips=args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
