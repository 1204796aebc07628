"""The system under test, and the control that can take its place.

A system is built once in set-up from the configuration, the traffic's job
and the data on the device. Its ``solve()`` is one whole solve through the
program's entry point, ending with ``x_parts`` on the host and ``v_stack``
ready on the device; it returns an ``Outcome``.

* ``program``: ``run_cola`` on one chip (``layout: run_cola``) or
  ``run_dist_cola(comm="plan")`` over a mesh of the cell's chips
  (``layout: run_dist_cola_plan``).
* ``control``: the plain reference (``bench/reference``) at precision
  ``high``, the nearest below the configurations' float32 at ``highest``.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench.reference import cola as ref


@dataclasses.dataclass
class Outcome:
    x: np.ndarray          # (K, n_k) x_parts, on the host
    v: jax.Array           # (K, d) v_stack, on the device
    rounds: int            # rounds run
    records: int           # record rounds among them
    stop_round: int | None
    certified: bool


def job_of(traffic: dict) -> dict:
    """The arguments of one solve, from the traffic file."""
    kind = traffic["kind"]
    if kind == "certified_solves":
        return {"rounds": int(traffic["budget_rounds"]),
                "record_every": int(traffic["record_every"]),
                "recorder": traffic["recorder"], "eps": float(traffic["eps"]),
                "block_size": int(traffic.get("block_size", 64))}
    if kind == "fixed_rounds":
        return {"rounds": int(traffic["rounds"]),
                "record_every": int(traffic["record_every"]),
                "recorder": traffic["recorder"], "eps": None,
                "block_size": int(traffic["block_size"])}
    raise ValueError(f"unknown traffic kind {kind!r}")


def program(config: dict, traffic: dict, a, y, devices):
    """The program's entry point, set up for this cell. Returns (solve,
    release): ``release()`` drops what the program holds on the devices."""
    from repro.core import executor, problems, topology
    from repro.core.cola import ColaConfig, run_cola

    spec = dict(config["problem"])
    problem = problems.PROBLEMS[spec.pop("name")](a, y, **spec)
    solver = config["solver"]
    graph = getattr(topology, solver["topology"])(int(solver["nodes"]))
    cfg = ColaConfig(kappa=float(solver["kappa"]))
    job = job_of(traffic)
    kwargs = {key: job[key] for key in
              ("record_every", "recorder", "eps", "block_size")}
    layout = config["layout"]
    if layout == "run_cola":
        def call():
            return run_cola(problem, graph, cfg, job["rounds"], **kwargs)
    elif layout == "run_dist_cola_plan":
        from repro.dist.runtime import run_dist_cola
        mesh = jax.make_mesh((len(devices),), ("data",), devices=devices)

        def call():
            return run_dist_cola(problem, graph, cfg, mesh, job["rounds"],
                                 comm="plan", **kwargs)
    else:
        raise ValueError(f"unknown layout {layout!r}")

    def solve() -> Outcome:
        with TraceAnnotation("bench.run_call"):
            res = call()
        with TraceAnnotation("bench.fetch"):
            x = np.asarray(res.state.x_parts)
            v = jax.block_until_ready(res.state.v_stack)
        hist = res.history
        stop = hist["stop_round"]
        return Outcome(
            x=x, v=v, rounds=job["rounds"] if stop is None else stop + 1,
            records=len(hist["round"]), stop_round=stop,
            certified=stop is not None and hist.get("certified", [0])[-1] > 0)

    def release():
        nonlocal problem, call
        problem = call = None
        executor.clear_driver_cache()

    return solve, release


def control(config: dict, traffic: dict, a, y, devices):
    """The reference at precision ``high`` in the program's place."""
    inst = ref.Instance(a, y, config["solver"], config["problem"], "high")
    job = job_of(traffic)

    def solve() -> Outcome:
        out = ref.run(inst, job["rounds"], record_every=job["record_every"],
                      eps=job["eps"])
        records = len([t for t in range(out["rounds"])
                       if t % job["record_every"] == 0
                       or t == job["rounds"] - 1])
        return Outcome(
            x=np.asarray(out["x"]), v=jax.block_until_ready(out["v"]),
            rounds=out["rounds"], records=records,
            stop_round=out["stop_round"],
            certified=out["stop_round"] is not None)

    def release():
        nonlocal inst
        inst = None

    return solve, release


SYSTEMS = {"program": program, "control": control}
