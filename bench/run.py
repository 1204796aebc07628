"""Run one cell of the benchmark once, on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are named in
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` (with ``--trace 1`` also ``busy_s`` and
``window_s``), with ``--trace 1`` a ``breakdown``, and last ``check``: each
number compared with the reference, beside its limit. Progress and the
check go to standard error. Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 3; without the program's
``src/`` beside it, 2.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--system", choices=("program", "control"),
                    default="program",
                    help="control: the reference at precision 'high' in the "
                         "program's place (never in the benchmark's runs)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    # the TPU library would otherwise write its logs to a fixed path
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    harness.enable_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), system=args.system,
                                  start=START)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, entry in result["check"].items():
        print(f"check {name}: {entry['value']!r} (limit {entry['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
