"""On-device telemetry counters carried through the round-block scan.

``Counters`` rides the executor state (``ColaState.counters``, an optional
field defaulting to ``None`` so telemetry-off pytrees — and programs — are
unchanged). The per-round update is a pure function of the global
(state-before, state-after, schedule-slice) triple, so one implementation
serves the single-host simulator and the shard_map distributed runtime:
every signal is either a static host-derived increment (wire bytes,
ppermute counts — exact, from the compiled plan's contract budget) or a
recomputation of an expression the round body already evaluates (the
step-0 payload encode, the robust-gate flags), which XLA CSEs against the
round's own computation inside the same jitted program.

Semantics to know when reading the numbers:

* ``wire_bytes`` / ``permutes`` model the wire the compiled topology plan
  executes for the run's graph — the simulator's dense matmuls stand in
  for that plan, so its counter equals the contract budget the dist
  lowering is held to (``plan.contract(d, wire=...)``).
* ``sat_sum`` accumulates the saturation fraction of each round's STEP-0
  encode (the honest payload); ``gate`` counts FIRST-step rejections (wire
  attacks only exist on step 0; with the default ``gossip_steps=1`` that
  is every rejection).
* the f32 byte/permute device counters stay exact up to 2^24 increments;
  ``summarize`` therefore reports the exact integer product
  ``rounds x per-round budget`` when the static increments are known.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mixing, quant
from repro.core.cola import _apply_payload_attack


class Counters(NamedTuple):
    """Per-run telemetry accumulators (leaves of the scan carry)."""

    rounds: jax.Array      # i32 () — rounds actually executed (pre-stop)
    wire_bytes: jax.Array  # f32 () — cumulative per-device gossip bytes
    permutes: jax.Array    # f32 () — cumulative collective-permute count
    sat_sum: jax.Array     # f32 () — sum of per-round step-0 saturation
    ef_sq: jax.Array       # f32 () — ||EF residual||^2 after last round
    gate: jax.Array        # (K,) i32 — robust-gate rejections per SENDER


def init_counters(k: int) -> Counters:
    return Counters(rounds=jnp.zeros((), jnp.int32),
                    wire_bytes=jnp.zeros((), jnp.float32),
                    permutes=jnp.zeros((), jnp.float32),
                    sat_sum=jnp.zeros((), jnp.float32),
                    ef_sq=jnp.zeros((), jnp.float32),
                    gate=jnp.zeros((k,), jnp.int32))


def round_increments(graph, d: int, cfg, itemsize: int = 4) -> dict:
    """Static per-round wire budget of the plan compiled for ``graph``.

    Returns ``{"bytes_per_round", "permutes_per_round", "contract",
    "contract_name"}`` — the same ``comm_budget`` numbers the plan's
    ``CommContract`` caps the lowered HLO to, so the telemetry byte counter
    and the checked contract agree by construction.
    """
    from repro.topo import compile_plan
    from repro.topo.lowering import comm_budget

    plan = compile_plan(graph)
    wire = cfg.wire if quant.is_quantized(cfg.wire) else None
    budget = comm_budget(plan, d, itemsize, gossip_steps=cfg.gossip_steps,
                         wire=wire)
    contract = plan.contract(d, itemsize, gossip_steps=cfg.gossip_steps,
                             wire=wire)
    return {"bytes_per_round": int(budget["bytes_per_device"]),
            "permutes_per_round": int(budget["collective_permutes"]),
            "contract": contract.describe(),
            "contract_name": contract.name}


def dist_round_increments(cfg, d: int, *, comm: str, plan=None,
                          conn: int = 1, k: int | None = None,
                          itemsize: int = 4) -> dict:
    """Per-round wire budget of the dist runtime's ACTUAL comm mode.

    ``comm="plan"`` uses the compiled (Block)Plan's contract budget
    (exact); ``"ring"`` counts the banded ppermutes; ``"dense"`` counts the
    all-gather payload per device (no ppermutes).
    """
    wire = cfg.wire if quant.is_quantized(cfg.wire) else None
    if comm == "plan" and plan is not None:
        from repro.topo.lowering import comm_budget
        budget = comm_budget(plan, d, itemsize,
                             gossip_steps=cfg.gossip_steps, wire=wire)
        contract = plan.contract(d, itemsize, gossip_steps=cfg.gossip_steps,
                                 wire=wire)
        return {"bytes_per_round": int(budget["bytes_per_device"]),
                "permutes_per_round": int(budget["collective_permutes"]),
                "contract": contract.describe(),
                "contract_name": contract.name}
    if comm == "ring":
        per = 2 * conn
        pb = quant.payload_bytes(d, cfg.wire)
        return {"bytes_per_round": cfg.gossip_steps * per * pb,
                "permutes_per_round": cfg.gossip_steps * per,
                "contract": f"ring conn={conn}: {per} ppermute(s)/step, "
                            f"{per * pb:,}B/device/step",
                "contract_name": f"ring-c{conn}-d{d}"}
    # dense all-gather fallback: each device receives the full K-row stack
    kk = int(k or 0)
    pb = quant.payload_bytes(d, cfg.wire, rows=max(kk, 1))
    return {"bytes_per_round": cfg.gossip_steps * pb,
            "permutes_per_round": 0,
            "contract": f"dense all-gather: {pb:,}B/device/step",
            "contract_name": f"dense-K{kk}-d{d}"}


def make_update(cfg, k: int, inc: dict):
    """Build the per-round counter update for one run.

    Returns ``update(before, after, s_t, atk, w) -> (Counters, obs_row)``
    where ``before``/``after`` are the (global-array) ColaStates around one
    executed round, ``s_t`` the round's schedule slice, ``atk`` the round's
    attack operand dict (or None) and ``w`` the round's (K, K) mixing
    matrix — None is only legal when ``cfg.robust`` is off (a comm path
    that lowered W away must reconstruct it, e.g. via
    ``topo.plan.w_from_coefficients_device``, before the gate recompute;
    silently skipping would report zero rejections for a defended run).
    ``obs_row`` is the f32 (3,) per-round series row
    ``[saturation, ef_norm, gate_total]``. The update runs under the device
    scope ``obs.counters``.
    """
    quantized = quant.is_quantized(cfg.wire)
    b_inc = jnp.float32(inc["bytes_per_round"])
    p_inc = jnp.float32(inc["permutes_per_round"])
    row_ids = jnp.arange(k)
    if cfg.robust is not None and not hasattr(cfg, "robust_trim"):
        raise ValueError("robust config without trim/clip knobs")

    def step0_key(s_t):
        return (quant.step_key(s_t["qkey"], 0) if "qkey" in s_t else None)

    def update(before, after, s_t, atk, w):
        c = before.counters
        # -- quant signals: saturation of the step-0 payload ---------------
        if quantized:
            if cfg.pipeline and before.buf is not None:
                q = before.buf[0]  # payload pre-encoded last round
            else:
                p = (before.v_stack if before.ef is None
                     else before.v_stack + before.ef)
                q, _ = quant.quantize_rows(p, cfg.wire, step0_key(s_t))
            sat_t = quant.saturation_frac(q, cfg.wire)
        else:
            sat_t = jnp.float32(0.0)
        ef_sq = (jnp.float32(0.0) if after.ef is None
                 else jnp.sum(jnp.square(after.ef)).astype(jnp.float32))
        # -- robust-gate rejections: recompute the exact gate the defended
        # mix applied this round (step 0) — same helpers, so XLA CSEs it
        gate_t = jnp.zeros((k,), jnp.int32)
        if cfg.robust is not None and w is None:
            raise ValueError(
                "telemetry gate recompute needs the round's (K, K) mixing "
                f"matrix but the comm path supplied none with robust="
                f"{cfg.robust!r} — reconstruct it from the lowered schedule "
                "(topo.plan.w_from_coefficients_device on plan_diag/"
                "plan_coefs) instead of dropping gate counts to zero")
        if cfg.robust is not None:
            v_send = _apply_payload_attack(before.v_stack, atk)
            if quantized:
                key0 = step0_key(s_t)
                _, _, deq_self, _ = quant.encode(before.v_stack, cfg.wire,
                                                 key0, None, before.ef)
                if v_send is before.v_stack:
                    stack, ov = deq_self, None
                else:
                    p_atk = (v_send if before.ef is None
                             else v_send + before.ef)
                    qa, sa = quant.quantize_rows(p_atk, cfg.wire, key0)
                    stack, ov = quant.dequantize(qa, sa), deq_self
            else:
                stack = v_send
                ov = None if v_send is before.v_stack else before.v_stack
            flat = stack.reshape(k, -1)
            flags = mixing.gate_flags(
                jnp.asarray(w, flat.dtype), flat, row_ids, cfg.robust,
                trim=cfg.robust_trim, clip=cfg.robust_clip,
                self_override=None if ov is None else ov.reshape(k, -1))
            gate_t = jnp.sum(flags, axis=0).astype(jnp.int32)  # per sender
        obs_row = jnp.stack([sat_t, jnp.sqrt(ef_sq),
                             jnp.sum(gate_t).astype(jnp.float32)])
        new = Counters(rounds=c.rounds + 1,
                       wire_bytes=c.wire_bytes + b_inc,
                       permutes=c.permutes + p_inc,
                       sat_sum=c.sat_sum + sat_t,
                       ef_sq=ef_sq,
                       gate=c.gate + gate_t)
        return new, obs_row

    def scoped(before, after, s_t, atk, w):
        with jax.named_scope("obs.counters"):
            return update(before, after, s_t, atk, w)

    return scoped


def summarize(counters: Counters, inc: dict | None = None, *,
              series=None, stop_round=None, dishonest=None) -> dict:
    """Host-side counter totals for ``history["telemetry"]`` / RunReport.

    ``inc`` (the static per-round increments) upgrades the f32 device byte
    and permute counters to exact integer products; ``dishonest`` (the
    materialized (T, K) ``atk_dishonest`` schedule entry) splits the gate
    counts into honest vs dishonest sender columns; ``series`` is the
    stacked (T, 3) per-round obs rows from the executor aux.
    """
    c = jax.device_get(counters)
    n = int(c.rounds)
    gate = np.asarray(c.gate).astype(int)
    out = {
        "rounds": n,
        "wire_bytes": int(round(float(c.wire_bytes))),
        "permutes": int(round(float(c.permutes))),
        "saturation_mean": float(c.sat_sum) / max(n, 1),
        "ef_norm": float(np.sqrt(float(c.ef_sq))),
        "gate_rejections": gate.tolist(),
        "gate_total": int(gate.sum()),
        "stop_round": stop_round,
    }
    if inc is not None:
        # exact integer totals — the f32 device counters lose exactness
        # past 2^24 increments, the host product never does
        out["wire_bytes"] = n * int(inc["bytes_per_round"])
        out["permutes"] = n * int(inc["permutes_per_round"])
        out["contract"] = inc["contract"]
    if dishonest is not None:
        bad = np.any(np.asarray(dishonest).astype(bool), axis=0)
        out["dishonest_nodes"] = np.nonzero(bad)[0].tolist()
        out["gate_dishonest"] = int(gate[bad].sum())
        out["gate_honest"] = int(gate[~bad].sum())
    if series is not None:
        s = np.asarray(jax.device_get(series))
        m = min(n, s.shape[0])
        out["series"] = {"saturation": s[:m, 0].astype(float).tolist(),
                         "ef_norm": s[:m, 1].astype(float).tolist(),
                         "gate": s[:m, 2].astype(int).tolist()}
    return out


def render_footprint(k: int, axis: str = "nodes") -> str:
    """Counter pspec footprint for ``dryrun --plan``: each leaf's shape,
    dtype, bytes and the ``dist.sharding.cola_counters_pspecs`` placement
    it gets on a device mesh."""
    from repro.dist import sharding as shard_specs

    cts = init_counters(k)
    specs = shard_specs.cola_counters_pspecs(axis)
    lines = [f"[obs counters] K={k} (ColaConfig.telemetry=True carry)"]
    total = 0
    for name, leaf, spec in zip(Counters._fields, cts, specs):
        nbytes = leaf.size * leaf.dtype.itemsize
        total += nbytes
        shape = "x".join(map(str, leaf.shape)) or "scalar"
        lines.append(f"  {name:<11} {shape:<8} {leaf.dtype.name:<8} "
                     f"{nbytes:>6,}B  pspec={spec}")
    lines.append(f"  total {total:,}B per run (donated with the state)")
    return "\n".join(lines)
