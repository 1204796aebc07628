"""The least ICI time of the window's mixing steps over the device time of
the exchange ops inside the mixing (``cola.exchange`` under ``cola.mix``),
%, mean over devices.

The least time a step is ``bench.exchange``'s count for a ring of the
cell's nodes over its chips. A device's op line may cover only part of
the window (``_scopes``): its steps are taken in proportion. None on one
chip, for a graph other than the ring, and where no op carries both
scopes."""
import sys

from bench import counts, exchange
from bench.layer_metrics import _scopes

SCOPES = {"cola.mix", "cola.exchange"}


def read(run):
    solver = run.config.get("solver", {}) if run.config else {}
    if run.chips < 2 or solver.get("topology") != "ring":
        return None
    devs = _scopes.scopes_of(run)
    if not devs:
        return None
    shape = run.shape()
    steps = (sum(s.rounds for s in run.solves)
             * int(solver.get("gossip_steps", 1)))
    step_s = exchange.ring_mixing_seconds(
        shape.k, run.chips, shape.d,
        counts.peaks(run.device_kind)["ici_link_bytes_per_s"])
    shares = []
    for dev in devs:
        spent = sum(s for op, s in dev.by_tf_op.items()
                    if SCOPES <= set(_scopes.components(op)))
        if spent and dev.covered_s:
            least = steps * step_s * dev.covered_s / run.trace.window_s
            print(f"exchange roofline {dev.name}: {steps} steps, least "
                  f"{least:.6f} s of {spent:.6f} s", file=sys.stderr)
            shares.append(least / spent)
    return 100.0 * sum(shares) / len(shares) if shares else None
