"""Seconds of XLA compilations (backend_compile_duration events, cache
loads included) during set-up."""
import sys


def read(run):
    secs = [d for phase, event, d in run.compiles if phase == "setup"
            and event == "/jax/core/compile/backend_compile_duration"]
    print(f"setup_compile_s: {len(secs)} compilations", file=sys.stderr)
    return sum(secs)
