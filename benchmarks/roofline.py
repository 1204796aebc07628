"""Roofline analysis from the dry-run artifacts (deliverable g).

Reads experiments/dryrun/*.json (produced by repro.launch.dryrun), computes
the three per-chip roofline terms on the chip the production mesh targets
(``repro.launch.mesh.PRODUCTION_DEVICE_KIND``, a TPU v5e; peaks from
``mesh.chip_peaks``)

    compute    = HLO_FLOPs_per_device / peak bf16 FLOP/s      (197e12)
    memory     = HLO_bytes_per_device / peak HBM bytes/s      (819e9)
    collective = collective_bytes_per_device / ICI link bytes/s (50e9)

identifies the dominant term, and reports MODEL_FLOPS / HLO_FLOPs (useful-
compute ratio; catches remat/redundancy waste). MODEL_FLOPS uses 6*N*D for
training (2*N*D forward-only for prefill/decode), with N the ACTIVE
parameter count for MoE.

Usage: PYTHONPATH=src python -m benchmarks.roofline [--dir experiments/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

import jax

from repro.configs.base import SHAPES, get_config
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, chip_peaks


def active_params(arch: str) -> tuple[int, int]:
    """(total, active) parameter counts; active discounts unrouted experts."""
    from repro.launch.specs import params_specs
    cfg = get_config(arch)
    shapes = params_specs(cfg)
    total = active = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    for path, leaf in flat:
        keys = [str(e.key) for e in path
                if isinstance(e, jax.tree_util.DictKey)]
        n = leaf.size
        total += n
        if cfg.num_experts and "moe" in keys and keys[-1] in (
                "w_gate", "w_up", "w_down") and "shared" not in keys:
            n = n * cfg.experts_per_token // cfg.num_experts
        active += n
    return total, active


def model_flops(arch: str, shape_name: str) -> float:
    """Global MODEL_FLOPS for one step of this (arch, shape)."""
    shape = SHAPES[shape_name]
    _, n_active = active_params(arch)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per request
    return 2.0 * n_active * shape.global_batch


def analyze(report: dict) -> dict:
    peaks = chip_peaks(PRODUCTION_DEVICE_KIND)
    arch, shape = report["arch"], report["shape"]
    chips = report["chips"]
    hlo = report.get("hlo")
    if hlo:  # trip-count-aware analyzer (repro.launch.hlo_analysis)
        flops_dev = hlo["flops"]
        bytes_dev = hlo["bytes"]
        coll_dev = hlo["collectives"]["total"]
    else:    # legacy: XLA cost_analysis (counts while bodies once)
        flops_dev = report["flops_per_device"]
        bytes_dev = report["bytes_per_device"]
        coll_dev = report["collectives"]["total"]
    t_compute = flops_dev / peaks["flops_bf16"]
    t_memory = bytes_dev / peaks["hbm_bytes_per_s"]
    t_coll = coll_dev / peaks["ici_link_bytes_per_s"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(arch, shape) / chips
    return {
        "arch": arch, "shape": shape, "mesh": report["mesh"],
        "device_kind": PRODUCTION_DEVICE_KIND,
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_dev": mf,
        "useful_ratio": mf / flops_dev if flops_dev else 0.0,
        "roofline_bound_s": max(terms.values()),
        "hbm_gb": report.get("memory", {}).get("temp_bytes", 0) / 1e9,
    }


def run(dir_: str = "experiments/dryrun", mesh: str = "single"):
    rows = []
    for path in sorted(glob.glob(os.path.join(dir_, f"*__{mesh}.json"))):
        rep = json.load(open(path))
        if rep.get("status") != "compiled":
            continue
        rows.append(analyze(rep))
    return rows


def markdown_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant "
           "| useful FLOP ratio | temp GB/dev |\n"
           "|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} "
            f"| {r['memory_s']:.3e} | {r['collective_s']:.3e} "
            f"| **{r['dominant']}** | {r['useful_ratio']:.3f} "
            f"| {r['hbm_gb']:.1f} |")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    rows = run(args.dir, args.mesh)
    if args.markdown:
        print(markdown_table(rows))
        return
    print("fig,arch,shape,compute_s,memory_s,collective_s,dominant,"
          "useful_ratio,temp_gb_dev")
    for r in rows:
        print(f"roofline,{r['arch']},{r['shape']},{r['compute_s']:.4e},"
              f"{r['memory_s']:.4e},{r['collective_s']:.4e},{r['dominant']},"
              f"{r['useful_ratio']:.4f},{r['hbm_gb']:.2f}")


if __name__ == "__main__":
    main()
