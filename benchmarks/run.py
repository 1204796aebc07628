"""Benchmark runner: one section per paper figure + roofline summary.

  PYTHONPATH=src python -m benchmarks.run [--full]

Output is CSV-ish lines prefixed by the figure tag. ``--full`` uses the
paper-scale problem sizes (slow on CPU); the default is a reduced but
faithful sweep.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig2,fig3,fig4,kernels,roofline")
    args = ap.parse_args()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (fig1_theta, fig2_baselines, fig3_topology,
                            fig4_fault, kernel_bench, roofline)

    sections = [
        ("fig1", lambda: fig1_theta.run(fast)),
        ("fig2", lambda: fig2_baselines.run(fast)),
        ("fig3", lambda: fig3_topology.run(fast)),
        ("fig4", lambda: fig4_fault.run(fast)),
        ("kernels", lambda: kernel_bench.run(fast)),
    ]
    for name, fn in sections:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # keep the suite going; report the failure
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
            raise
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    if not only or "roofline" in only:
        print("\n===== roofline (single-pod baselines) =====", flush=True)
        rows = roofline.run()
        if rows:
            print("fig,arch,shape,compute_s,memory_s,collective_s,dominant,"
                  "useful_ratio,temp_gb_dev")
            for r in rows:
                print(f"roofline,{r['arch']},{r['shape']},"
                      f"{r['compute_s']:.4e},{r['memory_s']:.4e},"
                      f"{r['collective_s']:.4e},{r['dominant']},"
                      f"{r['useful_ratio']:.4f},{r['hbm_gb']:.2f}")
        else:
            print("# no dry-run artifacts found — run "
                  "`python -m repro.launch.dryrun --all` first")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
