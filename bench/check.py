"""The comparison that decides ``correct``: what the timed path returned,
against the plain reference (``bench/reference``) at the configuration's
precision, on the same data and at the same sizes.

Numbers compared, each against its limit (a number over its limit, or not
finite, is a failure):

* ``x_rel``: over every solve of the window, the largest
  ||x - x_ref|| / ||x_ref||, with x_ref the reference's x after as many
  rounds as that solve ran. It covers the local solver and the mixing, and
  on a mesh the exchange between chips.
* certified solves: ``stop_apart``, the largest distance in rounds between
  a solve's certified stop and the reference's; ``gap``, the duality gap of
  the last solve's (x, {v_k}), which its certificate claims is at most eps
  (the limit is the traffic's eps).
* fixed-round solves: ``invariant``, the Lemma-1 residual
  ||(1/K) sum_k v_k - A x|| / (||A x|| + 1) of the last solve.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import cola as ref
from bench.systems import job_of


def _rel(x, x_ref) -> float:
    x, x_ref = np.asarray(x, np.float64), np.asarray(x_ref, np.float64)
    return float(np.linalg.norm(x - x_ref) / max(np.linalg.norm(x_ref),
                                                 1e-30))


def compare(config: dict, traffic: dict, a, y, solves: list, last,
            device) -> dict:
    """{name: (value, limit)}. ``solves``: the window's solves that returned
    (each with its ``x``, ``rounds`` and ``stop_round``); ``last``: the last
    one's ``Outcome``. ``a``, ``y``: the data on ``device``, where the
    reference runs."""
    import jax

    job = job_of(traffic)
    inst = ref.Instance(a, y, config["solver"], config["problem"], "highest")
    counts = sorted({o.rounds for o in solves})
    out = ref.run(inst, job["rounds"], record_every=job["record_every"],
                  eps=job["eps"], keep=counts)
    x_rel = max(_rel(o.x, out["kept"][o.rounds]) for o in solves)
    x_last = jax.device_put(np.asarray(last.x), device)
    v_last = jax.device_put(np.asarray(last.v), device)
    said = inst.gap(x_last, v_last)
    limits = config["limits"]
    numbers = {"x_rel": (x_rel, limits["x_rel"])}
    if job["eps"] is not None:
        apart = [math.inf if o.stop_round is None or out["stop_round"] is None
                 else abs(o.stop_round - out["stop_round"]) for o in solves]
        numbers["stop_apart"] = (float(max(apart)), limits["stop_apart"])
        numbers["gap"] = (said["gap"], job["eps"])
    else:
        numbers["invariant"] = (said["invariant"], limits["invariant"])
    return numbers


def passes(numbers: dict) -> bool:
    return all(math.isfinite(v) and v <= lim for v, lim in numbers.values())
