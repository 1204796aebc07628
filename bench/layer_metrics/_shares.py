"""Shares of the traced window, in percent, shared by the readers beside
this file."""
import sys

from bench import counts


def idle(run):
    """1 - busy / window, the mean over the cell's devices, each over the
    part of the traced window that its trace covers."""
    if run.trace is None or not run.trace.devices:
        return None
    shares = [run.trace.idle_share(dev) for dev in run.trace.devices]
    return 100.0 * sum(shares) / len(shares)


def roofline(run):
    """Least time of the window's rounds on the cell's chips (the larger of
    operations over the bf16 peak and bytes over HBM bandwidth), over the
    whole traced window."""
    if run.trace is None or not run.solves:
        return None
    cost = run.window_cost()
    secs, bound = counts.roofline_seconds(cost, counts.peaks(run.device_kind),
                                          run.chips)
    print(f"round roofline: {cost.flops:.6g} flops, {cost.bytes:.6g} bytes, "
          f"bound by {bound}: {secs:.6f} s of {run.trace.window_s:.6f} s",
          file=sys.stderr)
    return 100.0 * secs / run.trace.window_s


def mfu(run):
    """Operations the window's rounds require over window time x chips x
    bf16 peak."""
    if run.trace is None or not run.solves:
        return None
    peak = counts.peaks(run.device_kind)["flops_bf16"]
    return (100.0 * run.window_cost().flops
            / (run.trace.window_s * run.chips * peak))
