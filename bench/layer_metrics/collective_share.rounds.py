"""Device time in collective-permute ops over the traced window, mean over
devices."""
import sys


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    shares = []
    for dev in run.trace.devices:
        share = run.trace.op_share(dev, lambda op: "collective-permute" in op)
        print(f"{dev.name}: collective-permute share {share:.6f}",
              file=sys.stderr)
        shares.append(share)
    return 100.0 * sum(shares) / len(shares)
