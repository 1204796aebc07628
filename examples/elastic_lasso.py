"""Elastic decentralized LASSO: nodes drop out and re-join mid-training,
and the run stops itself by Prop.-1 certification.

Reproduces the Fig.-4 fault-tolerance setting in miniature: every round each
node stays in the network with probability p; leavers freeze their block
(Theta_k = 1) and the surviving nodes re-normalize the Metropolis weights.
CoLA keeps converging monotonically — no tuning, no restart — and instead
of a fixed round count, ``eps=`` arms the local certificates: the run
terminates at the first record round where every node certifies the global
duality gap from its own neighborhood, churn and all.

The gossip graph is any name from the ``repro.topo`` registry (default: a
2-D torus — non-circulant, so on a device mesh this exact schedule executes
through the compiled topology program at neighbor-only cost; the compiled
plan is printed). Recording runs on the adaptive cadence: geometric
back-off while far from eps, tightening to every round near certification.

With ``--byzantine`` some nodes LIE instead of leaving: they emit
sign-flipped x10 payloads from round 5 on (the ``repro.attack`` harness,
composed on top of the same churn schedule). Undefended, the honest-cohort
certificate detects the tampering (``certificate_violated``); with
``--robust trim`` the mixing layer drops flagged payloads and the run
converges and certifies as if clean. Composing the attack with heavy churn
(low ``--p-stay``) thins neighborhoods until a liar can dominate one and
slip the outlier gate — in that regime the certificate fires instead of
the defense holding: either way a lying participant is never silent. Use
``--p-stay 1.0`` to see the defense hold cleanly.

With ``--wire fp8`` (or ``int8``) the gossip payloads cross the wire as
1-byte codewords plus a per-node fp32 absmax scale (``repro.core.quant``)
— the printed plan shows the byte budget shrinking to ~0.25x — while
error feedback carries the rounding residual across rounds, so the run
still certifies the SAME eps; ``--no-error-feedback`` shows the contrast
(the quantization noise floor can hold the gap above a tight eps
forever). The codec composes with churn AND with ``--byzantine`` /
``--robust``: attacked payloads are re-encoded onto the same wire, the
outlier gate judges the decoded rows, and error feedback rides the honest
stream only.

With ``--telemetry`` the run carries the ``repro.obs`` counters through the
round scan — wire bytes vs the printed plan's contract, robust-gate
rejections per sender, quantizer saturation, EF residual norm — and prints
the totals; ``--report`` additionally appends a ``RunReport`` to the run
registry (``.repro_runs/`` or ``$REPRO_RUNS_DIR``) for
``python -m repro.obs show/diff/timeline``.

  PYTHONPATH=src python examples/elastic_lasso.py [--topo torus2d]
      [--p-stay 0.8] [--eps 3.0] [--byzantine 0,10] [--robust trim]
      [--wire fp8] [--no-error-feedback] [--telemetry] [--report]
"""
import argparse
import os

import jax.numpy as jnp
import numpy as np

from repro import attack, topo as topo_programs
from repro.core import metrics as metrics_lib, problems
from repro.core.cola import ColaConfig, run_cola, solve_reference
from repro.data import synthetic


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--p-stay", type=float, default=0.8)
    ap.add_argument("--eps", type=float, default=3.0,
                    help="certified duality-gap target (stops the run)")
    ap.add_argument("--rounds", type=int, default=1500,
                    help="round budget: max rounds if certification "
                         "never fires")
    ap.add_argument("--topo", default="torus2d",
                    help="gossip graph (repro.topo.GRAPHS name)")
    ap.add_argument("--byzantine", default=None, metavar="NODES",
                    help="comma-separated node ids that emit sign-flipped "
                         "x10 payloads from round 5 on (e.g. '0,10')")
    ap.add_argument("--robust", default=None,
                    choices=["trim", "median", "clip"],
                    help="robust mixing defense (default: trust everyone)")
    ap.add_argument("--wire", default="fp32",
                    choices=["fp32", "fp8", "fp8_e5m2", "int8"],
                    help="gossip wire codec: quantize payloads to 1-byte "
                         "codewords + fp32 absmax scales (default fp32)")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the EF residual carry — shows the raw "
                         "quantization noise floor")
    ap.add_argument("--telemetry", action="store_true",
                    help="carry the repro.obs counters through the round "
                         "scan and print the totals")
    ap.add_argument("--report", action="store_true",
                    help="also append a RunReport to the run registry "
                         "(implies --telemetry)")
    args = ap.parse_args()
    quantized = args.wire != "fp32"
    telemetry = args.telemetry or args.report
    if telemetry and not args.report:
        # counters only: keep the registry untouched unless the user
        # already pointed REPRO_RUNS_DIR somewhere
        os.environ.setdefault("REPRO_RUNS_DIR", "off")

    x, y, _ = synthetic.regression(1500, 300, seed=1, sparsity_solution=0.1)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), lam=1e-3)
    opt = solve_reference(prob, rounds=500, kappa=8)
    k = 16
    graph = topo_programs.build(args.topo, k)

    # the comm program a device mesh would execute for this graph — churn
    # reweighting rides the same compiled permutations with zeroed weights
    plan = topo_programs.compile_plan(graph)
    print(plan.render(d=prob.d, wire=args.wire if quantized else None))
    if quantized:
        ef = not args.no_error_feedback
        print(f"wire={args.wire} error_feedback={'on' if ef else 'OFF'}: "
              "payloads quantized per (round, node) with stochastic "
              "rounding; the certificate stop below runs on the quantized "
              "exchange")

    def churn(t, rng):
        return rng.random(k) < args.p_stay

    attacks = None
    if args.byzantine:
        nodes = tuple(int(n) for n in args.byzantine.split(","))
        attacks = [attack.Byzantine(nodes=nodes, mode="sign_flip",
                                    scale=10.0, start=5)]
        print(f"byzantine nodes {nodes}: sign-flip x10 from round 5 "
              f"(defense: {args.robust or 'NONE — trusting the wire'})")

    cadence = metrics_lib.AdaptiveCadence(base=1, max_every=64, grow=2,
                                          near=2.0)
    res = run_cola(prob, graph,
                   ColaConfig(kappa=2.0, robust=args.robust, wire=args.wire,
                              error_feedback=not args.no_error_feedback,
                              telemetry=telemetry),
                   rounds=args.rounds,
                   record_every=cadence, recorder="gap+certificate",
                   eps=args.eps, active_schedule=churn, leave_mode="freeze",
                   attacks=attacks)
    h = res.history
    print(f"p_stay={args.p_stay} topo={graph.name}: suboptimality "
          "trajectory (adaptive record cadence)")
    for t, p in zip(h["round"][::5], h["primal"][::5]):
        print(f"  round {t:4d}  F_A - F* = {p - opt:10.6f}")
    print(f"recorded {len(h['round'])} rows over {h['round'][-1] + 1} rounds"
          f" (fixed record_every=20 would have recorded "
          f"{(h['round'][-1] // 20) + 1})")
    if attacks is not None:
        if h["violated_round"] is not None:
            print(f"CERTIFICATE VIOLATED at round {h['violated_round']}: "
                  "the honest cohort's invariant was tampered with — "
                  "results untrusted (try --robust trim)")
        else:
            print("honest-cohort certificate sound: the defense held")
    if h["stop_round"] is not None:
        print(f"certified eps={args.eps} at round {h['stop_round']} "
              f"(true gap {h['gap'][-1]:.4f}) — stopped "
              f"{args.rounds - h['stop_round'] - 1} rounds early")
    else:
        print(f"budget exhausted before certifying eps={args.eps} "
              f"(gap {h['gap'][-1]:.4f})")

    x_final = res.state.x_parts.reshape(-1)[: prob.n]
    nnz = int(np.sum(np.abs(np.asarray(x_final)) > 1e-6))
    print(f"solution sparsity: {nnz}/{prob.n} nonzeros")

    if telemetry:
        tel = h["telemetry"]
        print(f"telemetry: {tel['rounds']} rounds moved "
              f"{tel['wire_bytes']:.0f} wire bytes "
              f"({tel['permutes']} ppermutes) — contract: {tel['contract']}")
        if args.robust:
            msg = f"  robust gate: {tel['gate_total']} payload rejections"
            if "gate_dishonest" in tel:
                msg += (f" (honest senders {tel['gate_honest']}, "
                        f"dishonest {tel['gate_dishonest']})")
            print(msg)
        if quantized:
            print(f"  codec: mean saturation {tel['saturation_mean']:.4f}, "
                  f"final EF residual norm {tel['ef_norm']:.4f}")
        if args.report:
            from repro.obs import report as obs_report
            print(f"report appended to {obs_report.runs_file()} — inspect "
                  "with: python -m repro.obs show -1 (or diff/timeline)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
