"""``run_dist_cola``: the multi-host shard_map CoLA runtime.

The single-host simulator (``repro.core.cola.run_cola``) keeps all K nodes
stacked in one device's arrays; this driver lays the node axis over a mesh
axis instead, so K paper-nodes execute as K/M node blocks on M devices with
no coordinator. Three design rules make it bit-compatible with the simulator
and as cheap to dispatch:

* **same round body** — the per-round function is ``cola._round_body`` with
  only the two mixing hooks swapped for collective implementations, so every
  node-local op (CD solve, local updates, churn masking) is literally the
  simulator's code;
* **same executor** — rounds run through the round-block scan engine
  (``repro.core.executor.run_round_blocks``): ``block_size`` rounds per
  dispatch, schedules pre-materialized by the simulator's own
  ``_materialize_schedule`` (identical rng consumption), metrics recorded on
  device, state donated across blocks;
* **neighbor exchange, not all-reduce** — ``comm="ring"`` mixes v via the
  banded ``lax.ppermute`` ring from ``repro.core.mixing`` (deg(k)·|v| bytes
  per link per gossip step, the paper's communication model);
  ``comm="plan"`` generalizes it to ARBITRARY sparse graphs AND to meshes
  smaller than the graph through the topology-program compiler
  (``repro.topo``): with one node per device the support is edge-colored
  into matchings, each color one ``lax.ppermute``, per-round weights —
  including churn-reweighted ones — riding the schedule as ``PlanSchedule``
  coefficient arrays; with K/M > 1 nodes per device the node graph
  quotients onto the mesh (``BlockPlan``): intra-block edges become local
  mixing terms (zero communication), inter-block edges collapse onto a
  device-level graph whose Delta+1 colors each move one (K/M, d) block
  payload per ppermute, and each device contracts its assembled
  neighborhood buffer against its (K/M, K) W rows in one dot — bitwise the
  simulator's dense mix, at O(colors·(K/M)·|v|) bytes per device. So one
  compiled program executes any paper topology (K=8/16/32) on any mesh
  whose size divides K; ``comm="dense"`` is the all-gather + W matmul
  oracle. A ``ring`` request whose W turns out non-circulant, that runs
  under churn, or that lands on a mesh smaller than K, dispatches to the
  plan path instead of failing (the historical "churn forces comm='dense'"
  and "plan places one node per device" restrictions are both retired).

Metric recording follows the same split (``repro.core.metrics`` recorders):
the gap recorder evaluates ``gap_report`` on the globally-sharded state and
lets GSPMD insert the (K, d)/(K, n_k) stack gathers — fine at paper scale,
O(K) bytes per device per record round. The Prop.-1 certificate recorder
instead records UNDER shard_map from local quantities: gradients of the
local node block, the Eq.-10 neighborhood mean via ``lax.ppermute`` of the
(d,)-sized local gradient (ring / per-node plan) or of the (K/M, d) local
gradient block over the block-level colors (block plan), plus scalar
``psum``/``pmax`` reductions for the row — O(colors·(K/M)·d) per device per
record round, no stack gathers (asserted against the lowered HLO in tests
via ``launch.hlo_analysis``). Certificate stop conditions short-circuit
remaining rounds exactly as in the simulator.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import executor as exec_engine, metrics as metrics_lib, \
    mixing, quant, topology as topo
from repro.topo import lowering as topo_lowering, plan as topo_plan
from repro.core.cola import (ColaConfig, RunResult,
                             _arm_wire_state, _as_schedule_fn,
                             _check_wire_config,
                             _materialize_schedule, _reset_leavers,
                             _round_body, build_env, init_state)
from repro.core.duality import consensus_residual, neighborhood_mean
from repro.core.partition import make_partition
from repro.core.problems import Problem
from repro.dist.sharding import (auto_mesh, block_payload_pspec,
                                 cola_counters_pspecs, cola_env_pspecs,
                                 cola_recorder_pspecs, cola_state_pspecs,
                                 plan_payload_pspecs)


def _dist_mixers(axis: str, local_nodes: int, conn: int, comm: str,
                 gossip_steps: int,
                 plan: topo_plan.CommPlan | topo_plan.BlockPlan | None = None,
                 robust: str | None = None, robust_trim: int = 1,
                 robust_clip: float | None = None
                 ) -> tuple[Callable, Callable]:
    """(mix_fn, grad_mix_fn) for the shard_map round body.

    The first mixer argument is the round's *comm payload* — the schedule
    slice the driver routes in: the replicated (K, K) W for ``dense`` /
    ``ring``, or the node-sharded ``(plan_diag, plan_coefs)`` pair for
    ``plan``. ``mix_fn(payload, v_send, v_self)`` follows the simulator's
    wire-only attack contract: ``v_send`` is what goes over the wire,
    ``v_self`` the honest local stack (None on unattacked rounds — the fast
    path, bitwise identical to the pre-attack program).

    ``dense``: all-gather the (K, d) stack, fold W^B once (redundantly per
    device, O(B K^3) — cheap next to the solve), mix, slice back this
    device's node block. On a 1-device mesh every collective degenerates to
    the identity, which is what makes the dense path bitwise equal to the
    simulator there.

    ``ring``: banded circulant mixing via ``ppermute`` neighbor pushes —
    one node per device, round-constant circulant W (the historical
    TPU-native special case, kept for bitwise compatibility).

    ``plan``: the compiled topology program. One node per device
    (``CommPlan``): one ``ppermute`` per node-level edge color, per-node
    coefficients from the ``PlanSchedule`` slice. K/M nodes per device
    (``BlockPlan``): one ``ppermute`` of the (K/M, d) block payload per
    BLOCK-level color, this device's (K/M, K) W rows (the
    ``BlockPlanSchedule`` slice) contracted against the assembled
    neighborhood buffer in one dot — bitwise the simulator's dense mix.
    Either way any sparse graph (and any churn reweighting of it) runs at
    neighbor-only cost with a single compiled program.

    ``robust`` swaps the v-aggregation for the Byzantine-resilient
    neighborhood statistic (``mixing.robust_neighborhood_mix``): on
    ``dense`` every device robust-mixes the all-gathered full stack and
    slices its block back (bitwise the simulator's ``robust_mix_steps``);
    on ``plan`` the plan MUST be a BlockPlan — the assembled neighborhood
    buffer feeds ``block_robust_mix_steps`` (``run_dist_cola`` compiles a
    BlockPlan whenever robust is set, even at one node per device). The
    gradient mixer stays LINEAR regardless — the simulator's
    ``grad_mode='mixed'`` default is the plain ``dense_mix``, and robust
    statistics defend the consensus state, not the gradient average.
    """
    if comm == "dense":
        def steps_mix(w, stack, steps):
            if steps <= 0:
                return stack
            full = lax.all_gather(stack, axis, tiled=True)      # (K, d)
            mixed = mixing.mix_power(w, full, steps)
            i = lax.axis_index(axis)
            return lax.dynamic_slice_in_dim(mixed, i * local_nodes,
                                            local_nodes)
    elif comm == "ring":
        if local_nodes != 1:
            raise ValueError(
                f"comm='ring' places one node per device; got {local_nodes} "
                "nodes per device — use comm='dense' or a bigger mesh axis")

        def steps_mix(w, stack, steps):
            band = mixing.banded_weights(w, conn)
            out = stack[0]
            for _ in range(steps):
                out = mixing.ring_mix_ppermute(out, axis, band, conn)
            return out[None]
    elif comm == "plan":
        if isinstance(plan, topo_plan.BlockPlan):
            if local_nodes != plan.local_nodes:
                raise ValueError(
                    f"block plan carries {plan.local_nodes} nodes/device but "
                    f"the mesh layout implies {local_nodes}")

            def steps_mix(payload, stack, steps):
                # payload: this device's (K/M, K) rows of the round's W
                return topo_lowering.block_mix_steps(stack, axis, plan,
                                                     payload, steps)
        else:
            if local_nodes != 1:
                raise ValueError(
                    f"a per-node CommPlan places one node per device; got "
                    f"{local_nodes} nodes per device — compile a BlockPlan "
                    "(run_dist_cola does this automatically)")

            def steps_mix(payload, stack, steps):
                diag, coefs = payload  # node-sharded slices: (1,), (C, 1)
                out = topo_lowering.plan_mix_steps(
                    stack[0], axis, plan, diag[0], coefs[:, 0], steps)
                return out[None]
    else:
        raise ValueError(
            f"unknown comm {comm!r} (want 'dense', 'ring' or 'plan')")

    if robust is None:
        if comm == "dense":
            # bitwise the simulator's mix_power_wire: gather both the wire
            # payload and (when attacked) the honest stack, run the full-K
            # computation redundantly per device, slice this block back
            def mix_fn(w, v_send, v_self):
                if v_self is None:
                    return steps_mix(w, v_send, gossip_steps)
                full = lax.all_gather(v_send, axis, tiled=True)
                full_self = lax.all_gather(v_self, axis, tiled=True)
                mixed = mixing.mix_power_wire(w, full, full_self,
                                              gossip_steps)
                i = lax.axis_index(axis)
                return lax.dynamic_slice_in_dim(mixed, i * local_nodes,
                                                local_nodes)
        elif comm == "ring":
            def mix_fn(w, v_send, v_self):
                if v_self is None or gossip_steps <= 0:
                    return steps_mix(w, v_send, gossip_steps)
                band = mixing.banded_weights(w, conn)
                out = mixing.ring_mix_ppermute(v_send[0], axis, band, conn)
                out = out + band[conn] * (v_self[0] - v_send[0])
                for _ in range(gossip_steps - 1):
                    out = mixing.ring_mix_ppermute(out, axis, band, conn)
                return out[None]
        elif isinstance(plan, topo_plan.BlockPlan):
            def mix_fn(payload, v_send, v_self):
                return topo_lowering.block_mix_steps_wire(
                    v_send, v_self, axis, plan, payload, gossip_steps)
        else:
            def mix_fn(payload, v_send, v_self):
                diag, coefs = payload
                out = topo_lowering.plan_mix_steps_wire(
                    v_send[0], None if v_self is None else v_self[0],
                    axis, plan, diag[0], coefs[:, 0], gossip_steps)
                return out[None]
    elif comm == "dense":
        def mix_fn(w, v_send, v_self):
            if gossip_steps <= 0:
                return v_send
            full = lax.all_gather(v_send, axis, tiled=True)   # (K, d)
            full_self = (None if v_self is None
                         else lax.all_gather(v_self, axis, tiled=True))
            mixed = mixing.robust_mix_steps(w, full, robust,
                                            trim=robust_trim,
                                            clip=robust_clip,
                                            steps=gossip_steps,
                                            self_stack=full_self)
            i = lax.axis_index(axis)
            return lax.dynamic_slice_in_dim(mixed, i * local_nodes,
                                            local_nodes)
    elif comm == "plan" and isinstance(plan, topo_plan.BlockPlan):
        def mix_fn(payload, v_send, v_self):
            return topo_lowering.block_robust_mix_steps(
                v_send, axis, plan, payload, robust, trim=robust_trim,
                clip=robust_clip, steps=gossip_steps, v_self=v_self)
    else:
        raise ValueError(
            f"robust={robust!r} needs comm='dense' or a block-level plan; "
            f"got comm={comm!r} (run_dist_cola compiles the BlockPlan and "
            "re-dispatches 'ring' automatically)")
    # one LINEAR step for grad_mode='mixed', matching the simulator's
    # dense_mix default even when the v aggregation is robust
    grad_mix_fn = lambda w, g: steps_mix(w, g, 1)
    return mix_fn, grad_mix_fn


def _dist_qmixers(axis: str, local_nodes: int, comm: str, cfg: ColaConfig,
                  plan) -> tuple[Callable, Callable]:
    """(qmix_fn, qencode_fn) — the quantized-wire counterparts of
    ``_dist_mixers`` for the shard_map round body.

    ``qmix_fn(payload, v, ef, qkey, buf)`` runs the B EF-compensated gossip
    steps on the codec wire view; ``buf`` is the pre-encoded (payload,
    scale) double buffer when ``cfg.pipeline`` (consumed by step 0's
    ppermutes at the TOP of the round body). ``qencode_fn(v, ef, nkey)``
    encodes the NEXT round's step-0 payload at the end of the body.
    Stochastic-rounding keys always derive from GLOBAL node ids
    (``axis_index * K/M + row``), so the draws — and hence the wire bits —
    are bitwise the simulator's regardless of the mesh layout.

    ``plan`` (CommPlan): per-node lowering — the int8/fp8 payload AND its
    fp32 scale sidecar each ppermute per edge color, receivers dequantize
    before the coefficient contraction. ``plan`` (BlockPlan): the (K/M, d)
    quantized block + (K/M, 1) scales ppermute per block color into the
    dequantized neighborhood buffer, one dot against the W rows. ``dense``:
    quantize locally, all-gather the NARROW payload + scales (the oracle
    keeps the byte reduction), dequantize, dense mix, slice back.

    ``cfg.robust`` composes on both paths: the outlier gate judges the
    DEQUANTIZED neighborhood rows — the same values an honest receiver
    would consume — via ``lowering.block_robust_qmix_step`` (block plan;
    ``run_dist_cola`` always compiles a BlockPlan when robust is set) or
    ``mixing.robust_mix_steps`` on the gathered dequantized stack
    (``dense``), bitwise the simulator's composed branch for trim/median
    (clip: allclose, see ``lowering.block_robust_mix_step``).
    """
    wire, steps = cfg.wire, cfg.gossip_steps

    def _row_ids():
        return lax.axis_index(axis) * local_nodes + jnp.arange(local_nodes)

    if comm == "plan" and not isinstance(plan, topo_plan.BlockPlan):
        def qmix_fn(payload, v, ef, qkey, buf):
            diag, coefs = payload
            pb = None if buf is None else (buf[0][0], buf[1][0])
            out, ef_new = topo_lowering.plan_qmix_steps(
                v[0], None if ef is None else ef[0], axis, plan,
                diag[0], coefs[:, 0], steps, wire, qkey, payload=pb)
            return out[None], (None if ef_new is None else ef_new[None])

        def qencode_fn(v, ef, nkey):
            key = jax.random.fold_in(quant.step_key(nkey, 0),
                                     lax.axis_index(axis))
            p = v[0] if ef is None else v[0] + ef[0]
            q, s = quant.quantize(p, wire, key)
            deq = quant.dequantize(q, s)
            ef_new = None if ef is None else (p - deq)[None]
            return q[None], s[None], deq[None], ef_new
    elif comm == "plan":
        if cfg.robust is not None:
            # composed robust x quantized wire: single-step by the
            # _check_wire_config scoping (and buf is always None — pipeline
            # is rejected when composed)
            def qmix_fn(payload, v, ef, qkey, buf):
                return topo_lowering.block_robust_qmix_step(
                    v, ef, axis, plan, payload, wire, qkey, cfg.robust,
                    trim=cfg.robust_trim, clip=cfg.robust_clip)
        else:
            def qmix_fn(payload, v, ef, qkey, buf):
                return topo_lowering.block_qmix_steps(
                    v, ef, axis, plan, payload, steps, wire, qkey,
                    payload=buf)

        def qencode_fn(v, ef, nkey):
            p = v if ef is None else v + ef
            q, s = quant.quantize_rows(p.reshape(local_nodes, -1), wire,
                                       quant.step_key(nkey, 0),
                                       node_ids=_row_ids())
            deq = quant.dequantize(q, s)
            ef_new = (None if ef is None
                      else (p.reshape(local_nodes, -1) - deq).reshape(p.shape))
            return q, s, deq.reshape(v.shape), ef_new
    elif comm == "dense":
        def qmix_fn(w, v, ef, qkey, buf):
            out, ef_l = v.reshape(local_nodes, -1), ef
            for s in range(steps):
                if s == 0 and buf is not None:
                    q, sc = buf
                else:
                    k = None if qkey is None else quant.step_key(qkey, s)
                    p = out if ef_l is None else out + ef_l
                    q, sc = quant.quantize_rows(p, wire, k,
                                                node_ids=_row_ids())
                    if ef_l is not None:
                        ef_l = p - quant.dequantize(q, sc)
                # the oracle's all-gather moves the NARROW payload + the
                # fp32 sidecar — quantize-then-gather, never the reverse
                # (gathered as raw bytes so no backend upcasts float8,
                # see topo_lowering.ppermute_wire)
                if q.dtype.itemsize == 1 and \
                        jnp.issubdtype(q.dtype, jnp.floating):
                    qf = lax.bitcast_convert_type(
                        lax.all_gather(
                            lax.bitcast_convert_type(q, jnp.uint8),
                            axis, tiled=True), q.dtype)
                else:
                    qf = lax.all_gather(q, axis, tiled=True)
                sf = lax.all_gather(sc, axis, tiled=True)
                deq_full = quant.dequantize(qf, sf)
                if cfg.robust is not None:
                    # composed oracle: the gate judges the dequantized
                    # stack, exactly the simulator's composed branch
                    mixed = mixing.robust_mix_steps(
                        w, deq_full, cfg.robust, trim=cfg.robust_trim,
                        clip=cfg.robust_clip, steps=1)
                else:
                    mixed = mixing.dense_mix(w, deq_full)
                out = lax.dynamic_slice_in_dim(
                    mixed, lax.axis_index(axis) * local_nodes, local_nodes)
            return out.reshape(v.shape), ef_l

        def qencode_fn(v, ef, nkey):
            p = (v if ef is None else v + ef).reshape(local_nodes, -1)
            q, s = quant.quantize_rows(p, wire, quant.step_key(nkey, 0),
                                       node_ids=_row_ids())
            deq = quant.dequantize(q, s)
            ef_new = None if ef is None else (p - deq).reshape(v.shape)
            return q, s, deq.reshape(v.shape), ef_new
    else:
        raise ValueError(
            f"quantized wire has no comm={comm!r} lowering (a 'ring' "
            "request re-dispatches to 'plan' in run_dist_cola)")
    return qmix_fn, qencode_fn


# ---------------------------------------------------------------------------
# distributed recorders
# ---------------------------------------------------------------------------

def _place_recorder(recorder, mesh, axis):
    """Lay the recorder's per-run arrays (its ``init_spec`` state plus the
    per-node problem blocks it closes over) out over the node mesh axis, so
    the record program's captured constants start sharded like the state."""
    if isinstance(recorder, metrics_lib.ComposedRecorder):
        return dataclasses.replace(recorder, parts=tuple(
            _place_recorder(p, mesh, axis) for p in recorder.parts))
    if not isinstance(recorder, metrics_lib.CertificateRecorder):
        return recorder
    arrays = {"a_parts": recorder.a_parts, "gp_parts": recorder.gp_parts,
              "masks": recorder.masks, **recorder.init_spec()}
    specs = cola_recorder_pspecs(axis, arrays)
    placed = {name: jax.device_put(arr, NamedSharding(mesh, specs[name]))
              for name, arr in arrays.items()}
    return dataclasses.replace(recorder, **placed)


def _certificate_dist_record(rec, mesh, axis: str, local_nodes: int,
                             comm: str, conn: int,
                             plan=None) -> Callable:
    """Shard_map record_fn for ``CertificateRecorder``: O(d) collectives.

    Condition (9) is node-local. Condition (10)'s neighborhood mean comes
    from the gossip exchange pattern itself: on the ring, ``2*conn``
    ``ppermute`` pushes of this device's (d,) gradient (the certificate's
    only vector communication); on the per-node plan path, one ``ppermute``
    per edge color with the round's neighbor-mask row selecting what
    arrives (so the neighborhood follows the ACTIVE plan — under churn, the
    reweighted support from the certificate schedule — instead of a static
    band); on the block plan path, one ``ppermute`` of the (K/M, d) local
    gradient block per BLOCK-level color, mask-rows selecting per node; on
    the dense fallback, the same all-gather the round body already
    performs. Row entries reduce with scalar ``psum``/``pmax`` — on a
    1-device mesh every collective degenerates to the identity and the
    program is bitwise the simulator's record_fn.
    """
    k = rec.part.num_nodes

    def compile_support(support):
        return (topo_plan.compile_plan(support) if local_nodes == 1
                else topo_plan.compile_block_plan(support,
                                                  k // local_nodes))

    if comm == "ring":
        # the ppermute neighborhood must match the recorder's mask; a mask
        # that is NOT the circulant band (historically a ValueError)
        # dispatches into the plan path — compile the mask's own support.
        # Attack-aware mode also needs per-round mask rows (dishonest
        # columns drop out of the Eq.-10 mean), which the band path has no
        # slot for.
        band = np.zeros((k, k))
        idx = np.arange(k)
        for off in range(-conn, conn + 1):
            band[idx, (idx + off) % k] = 1.0
        if (rec.attack_aware or not np.array_equal(
                np.asarray(rec.neigh_mask) != 0, band != 0)):
            comm, plan = "plan", compile_support(np.asarray(rec.neigh_mask))
    if comm == "plan" and plan is None:
        plan = compile_support(np.asarray(rec.neigh_mask))

    def body(x_l, v_l, a_l, gp_l, m_l, nm_l, thr, hon):
        hon_l = None
        if rec.attack_aware:
            # hon is the replicated (K,) honesty mask from the attack
            # schedule: columns mask the neighborhood mean (a liar's
            # gradient never enters it), the own-node slice masks the
            # cohort sums and conditions
            nm_l = nm_l * hon[None, :].astype(nm_l.dtype)
            hon_l = lax.dynamic_slice_in_dim(
                hon, lax.axis_index(axis) * local_nodes, local_nodes)
        grads = jax.vmap(rec.problem.grad_f)(v_l)            # (ln, d)
        if comm == "plan" and isinstance(plan, topo_plan.BlockPlan):
            # block exchange of the whole (ln, d) gradient block; the
            # mask rows zero exactly what the stacked oracle excludes, so
            # the mean matches duality.neighborhood_mean bitwise
            nsum, count = topo_lowering.block_neighborhood_stats(
                grads, axis, plan, nm_l)
            neigh_mean = nsum / count[:, None]               # (ln, d)
        elif comm == "plan":
            # mask-selected plan exchange: nm_l is this node's row of the
            # self-inclusive neighborhood mask (static graph or the churn
            # round's reweighted support via the certificate schedule)
            nsum, count = topo_lowering.plan_neighborhood_stats(
                grads[0], axis, plan, nm_l[0])
            neigh_mean = (nsum / count)[None]                # (1, d)
        elif comm == "ring":
            g = grads[0]
            nsum = g
            for off in range(1, conn + 1):
                fwd = lax.ppermute(g, axis,
                                   [(i, (i + off) % k) for i in range(k)])
                bwd = lax.ppermute(g, axis,
                                   [((i + off) % k, i) for i in range(k)])
                nsum = nsum + fwd + bwd
            neigh_mean = (nsum / (2 * conn + 1))[None]       # (1, d)
        else:
            full = lax.all_gather(grads, axis, tiled=True)   # (K, d)
            neigh_mean = neighborhood_mean(full, nm_l)       # (ln, d)
        # condition (9) uses only this device's blocks — swap the local
        # slices in so the vmapped node math runs on (ln, ...) operands
        local = dataclasses.replace(rec, a_parts=a_l, gp_parts=gp_l,
                                    masks=m_l)
        local_gap, disagree = local.local_row_inputs(x_l, v_l, grads,
                                                     neigh_mean)
        # Lemma-1 tamper detection: local [sum_l v_l, sum_l A_l x_l]
        # partials completed with ONE stacked (2, d) psum — O(d), no stack
        # gathers; identity on a 1-device mesh (bitwise the simulator)
        sums = lax.psum(rec.invariant_sums(x_l, v_l, a_l, honest=hon_l),
                        axis)
        resid = consensus_residual(sums[0], sums[1], k)
        return rec.summarize(local_gap, disagree, resid=resid,
                             grad_thresh=thr, honest=hon_l,
                             psum=lambda s: lax.psum(s, axis),
                             pmax=lambda s: lax.pmax(s, axis))

    node, repl = P(axis), P()
    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(node, node, node, node, node, node, repl, repl),
        out_specs=P())

    def record(state, sched=None):
        if rec.dynamic:
            # churn: the reweighted round's neighbor mask + threshold come
            # in through the schedule (see metrics.certificate_schedule)
            nm, thr = sched["cert_mask"], sched["cert_grad_thresh"]
        else:
            nm, thr = rec.neigh_mask, jnp.asarray(rec.grad_thresh)
        if rec.attack_aware:
            hon = (jnp.asarray(sched["atk_dishonest"])
                   <= 0).astype(state.v_stack.dtype)
        else:
            hon = jnp.ones((k,), state.v_stack.dtype)  # unused, DCE'd
        return shard(state.x_parts, state.v_stack, rec.a_parts,
                     rec.gp_parts, rec.masks, nm, thr, hon)

    return record


def _dist_record_fn(recorder, mesh, axis, local_nodes, comm, conn,
                    plan=None) -> Callable:
    """The distributed record program for any recorder: certificates record
    under shard_map (O(d) collectives), everything else records on the
    globally-sharded state as-is (GSPMD inserts the gathers)."""
    if isinstance(recorder, metrics_lib.ComposedRecorder):
        pairs = [(p, _dist_record_fn(p, mesh, axis, local_nodes, comm, conn,
                                     plan))
                 for p in recorder.parts]
        return lambda st, sched=None: jnp.concatenate([
            f(st, sched) if getattr(p, "uses_schedule", False) else f(st)
            for p, f in pairs])
    if isinstance(recorder, metrics_lib.CertificateRecorder):
        return _certificate_dist_record(recorder, mesh, axis, local_nodes,
                                        comm, conn, plan)
    return recorder.record_fn


class _DistRecorder:
    """Duck-typed Recorder view with the record program specialized for the
    mesh; labels / stop condition / cache identity delegate to the inner
    recorder (plus the comm layout, which changes the compiled program)."""

    def __init__(self, inner, record_fn, comm: str, conn: int, plan=None):
        self._inner = inner
        self._record_fn = record_fn
        self._comm, self._conn = comm, conn
        self._plan = plan

    @property
    def labels(self):
        return self._inner.labels

    @property
    def uses_schedule(self):
        return bool(getattr(self._inner, "uses_schedule", False))

    def record_fn(self, state, sched=None):
        if self.uses_schedule:
            return self._record_fn(state, sched)
        return self._record_fn(state)

    @property
    def stop_fn(self):
        return self._inner.stop_fn

    def init_spec(self):
        return self._inner.init_spec()

    def cadence_ratio(self, row):
        return self._inner.cadence_ratio(row)

    def cache_token(self):
        plan_tok = self._plan.cache_token() if self._plan else None
        return ("dist", self._comm, self._conn, plan_tok,
                self._inner.cache_token())


def run_dist_cola(problem: Problem, graph: topo.Topology, cfg: ColaConfig,
                  mesh, rounds: int, *, comm: str = "ring",
                  axis: str | None = None, conn: int = 1,
                  record_every: int = 1,
                  recorder="gap", eps: float | None = None,
                  active_schedule=None, budget_schedule=None,
                  leave_mode: str = "freeze", seed: int = 0,
                  w_override: np.ndarray | None = None,
                  attacks=None, wire: str | None = None,
                  block_size: int = 64) -> RunResult:
    """Run Algorithm 1 with the node axis sharded over ``mesh``.

    Args mirror ``run_cola`` (same schedules, same rng consumption, same
    history layout, same ``recorder``/``eps`` certificate-driven stopping)
    plus:

      mesh: a jax Mesh; the node axis K shards over ``axis`` (default: the
        mesh's first axis), K % axis_size == 0, K/axis_size nodes per device.
      comm: "ring" (banded ppermute; round-constant circulant W, one node
        per device), "plan" (compiled topology program from ``repro.topo``:
        ANY sparse graph, including time-varying churn-reweighted ones; one
        ``ppermute`` per edge color with per-round schedule coefficients
        when K equals the mesh axis, or — on a smaller mesh — one
        ``ppermute`` of the (K/M, d) node-block payload per BLOCK-level
        color, bitwise-equal to the simulator), or "dense" (all-gather + W
        matmul; any W, any node count — and bitwise identical to
        ``run_cola`` on a 1-device mesh). A "ring" request dispatches to
        "plan" automatically when churn is scheduled, W is not
        circulant-banded, or the mesh is smaller than K.
      conn: connectivity of the circulant band for ``comm="ring"``.
      attacks: the same ``repro.attack`` scenarios ``run_cola`` accepts —
        they transform the identical pre-materialized schedule, so a seeded
        attack corrupts the distributed run bitwise like the simulator.
        ``Eavesdropper`` taps are simulator-only (rejected here).
      wire: shorthand overriding ``cfg.wire`` — the gossip payload codec
        ("fp32" | "int8" | "fp8" | "fp8_e5m2", see ``repro.core.quant``).
        On a quantized wire every gossip collective moves the 1-byte
        payload plus the fp32 scale sidecar instead of the fp32 stack; a
        "ring" request re-dispatches to "plan" (the band path has no codec
        lowering), and the "dense" oracle quantizes BEFORE its all-gather
        so even the oracle honors the byte budget.

    ``cfg.robust`` swaps the v aggregation for the Byzantine-resilient
    neighborhood statistic on every comm path: ``dense`` robust-mixes the
    all-gathered stack; ``ring``/``plan`` compile a block-level plan (even
    at one node per device — the robust statistic needs the assembled
    neighborhood buffer) and run ``block_robust_mix_steps``, bitwise the
    simulator's ``robust_mix_steps``.

    The certificate recorder records under shard_map from local gradients
    (``ppermute``/``psum``, O(colors·(K/M)·d) per device per record round)
    — its neighborhood exchange follows the active comm plan (the churn
    round's reweighted support) rather than a static band; the gap recorder
    keeps the gather-everything ``gap_report`` semantics. ``record_every``
    accepts the same ``"adaptive"`` / ``AdaptiveCadence`` controller as
    ``run_cola``.

    Returns ``RunResult(state, history)`` with the fully-stacked (K, ...)
    state, like the simulator.
    """
    if wire is not None:
        cfg = dataclasses.replace(cfg, wire=wire)
    _check_wire_config(cfg, attacks=attacks, leave_mode=leave_mode,
                       dist=True)
    quantized = quant.is_quantized(cfg.wire)
    mesh = auto_mesh(mesh)
    axis = axis or mesh.axis_names[0]
    m = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    k = graph.num_nodes
    if k % m != 0:
        raise ValueError(f"K={k} nodes must divide over {m} devices on "
                         f"mesh axis {axis!r}")
    local_nodes = k // m

    active_schedule = _as_schedule_fn(active_schedule, rounds, k,
                                      "active_schedule")
    budget_schedule = _as_schedule_fn(budget_schedule, rounds, k,
                                      "budget_schedule")
    base_w = (w_override if w_override is not None
              else topo.metropolis_weights(graph))
    plan = None
    if comm == "ring":
        # the circulant ppermute band only executes a round-constant
        # circulant W with one node per device; churn reweighting, a
        # non-circulant graph, or a mesh smaller than K now dispatches into
        # the compiled topology-program path instead of the historical
        # ValueErrors ("churn forces comm='dense'" / "one node per device");
        # robust aggregation is nonlinear — it also needs the plan path's
        # assembled neighborhood buffer
        if (active_schedule is not None or local_nodes != 1
                or cfg.robust is not None or quantized):
            comm = "plan"
        else:
            try:
                mixing.check_circulant_band(base_w, conn)
            except ValueError:
                comm = "plan"
    if comm == "plan":
        # under churn the per-round W is a reweighting of the graph (its
        # support only shrinks), so the graph's adjacency is the complete
        # compile-time support. A static w_override contributes its own
        # support too; the union also covers the certificate recorder's
        # adjacency-derived neighborhoods when they are denser than W's.
        support = graph.adjacency.copy()
        if active_schedule is None:
            off = np.asarray(base_w) != 0
            np.fill_diagonal(off, False)
            support = support | off
        # one node per device lowers per-node colors; K/M > 1 nodes per
        # device quotients the graph onto the mesh (block-level colors).
        # Robust aggregation always takes the block form — the trimmed-mean
        # / median / clip statistic runs over the ppermute-assembled
        # neighborhood buffer, which only the BlockPlan materializes (a
        # 1-node block is a valid BlockPlan). Quantized wires take it too:
        # the block contraction (W rows against the dequantized buffer) is
        # bitwise the simulator's dense mix, and bitwise matters here — a
        # 1-ulp reassociation difference in v would flip stochastic-
        # rounding draws next round and snowball through the codec, so the
        # per-node coefficient-sum form cannot hold multi-round parity
        plan = (topo_plan.compile_plan(support)
                if local_nodes == 1 and cfg.robust is None and not quantized
                else topo_plan.compile_block_plan(support, m))

    part = make_partition(problem.n, k)
    env = build_env(problem, part,
                    with_gram=cfg.use_gram(problem.d, part.block,
                                           problem.a.dtype.itemsize))
    state = init_state(problem, part)
    dtype = problem.a.dtype
    sched = _materialize_schedule(graph, rounds, active_schedule,
                                  budget_schedule, leave_mode, seed, base_w,
                                  dtype)
    if quantized:
        # the SAME per-round codec key stack both simulator drivers slice —
        # the stochastic-rounding draws are a function of (seed, round,
        # step, color, node), never of the mesh layout
        qkeys = np.asarray(quant.round_keys(seed, rounds + 1))
        sched["qkey"] = qkeys[:rounds]
        if cfg.pipeline:
            sched["qkey_next"] = qkeys[1:]
        state = _arm_wire_state(state, cfg, qkeys[0])
    atk_info = None
    if attacks is not None:
        from repro import attack as attack_lib
        # same transform order as the simulator: churn/budgets materialize,
        # attacks corrupt, then the certificate/plan schedules derive from
        # the corrupted exchange
        sched, atk_info = attack_lib.apply_attacks(
            sched, attacks,
            attack_lib.AttackContext(graph=graph, rounds=rounds, k=k,
                                     d=problem.d, dtype=dtype, seed=seed))
        if atk_info.tap_nodes:
            raise ValueError(
                "Eavesdropper taps are simulator-only (per-round payload "
                "trajectories are an analysis artifact) — record them with "
                "run_cola(attacks=...)")
    atk_names = atk_info.entry_names if atk_info else ()
    has_budget = "budgets" in sched
    has_reset = "leavers" in sched

    rec = metrics_lib.make_recorder(recorder, problem, part, env, graph,
                                    base_w, eps)
    if active_schedule is not None:
        rec = metrics_lib.dynamize(rec)  # churn-aware certificate inputs
    if "dishonest" in atk_names:
        # payload-corrupting attacks: certificates audit the honest cohort
        # against the schedule's ground-truth mask (metrics.attackify)
        rec = metrics_lib.attackify(rec)

    # lay the node axis of state + env over the mesh axis up front so the
    # donated buffers never migrate between blocks
    state_spec, env_spec = cola_state_pspecs(axis), cola_env_pspecs(axis)
    state = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, state_spec)), state)
    env = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, env_spec)), env)
    obs_upd = obs_inc = None
    if cfg.telemetry:
        # counters attach AFTER the state placement with their OWN specs
        # (scalars replicate, the per-sender gate row shards): the P(axis)
        # prefix spec above must never see them, and the shard_map round
        # program never does either — step_fn strips the counters off the
        # carry, runs the sharded round on the core state, then updates
        # them from the global (before, after, schedule) triple outside
        # shard_map, where GSPMD lays the recompute out over the mesh
        from repro.obs import counters as obs_counters
        obs_inc = obs_counters.dist_round_increments(
            cfg, problem.d, comm=comm, plan=plan, conn=conn, k=k,
            itemsize=dtype.itemsize)
        obs_upd = obs_counters.make_update(cfg, k, obs_inc)
        cts = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            obs_counters.init_counters(k), cola_counters_pspecs(axis))
        state = state._replace(counters=cts)
    rec = _place_recorder(rec, mesh, axis)
    dist_rec = _DistRecorder(
        rec, _dist_record_fn(rec, mesh, axis, local_nodes, comm, conn, plan),
        comm, conn, plan)

    mix_fn, grad_mix_fn = _dist_mixers(axis, local_nodes, conn, comm,
                                       cfg.gossip_steps, plan,
                                       robust=cfg.robust,
                                       robust_trim=cfg.robust_trim,
                                       robust_clip=cfg.robust_clip)
    qmix_fn = qencode_fn = None
    if quantized:
        qmix_fn, qencode_fn = _dist_qmixers(axis, local_nodes, comm, cfg,
                                            plan)
    body = _round_body(problem, part, cfg, mix_fn=mix_fn,
                       grad_mix_fn=grad_mix_fn, qmix_fn=qmix_fn,
                       qencode_fn=qencode_fn)

    def shard_round(st, env_l, w_t, active_l, budgets_l, leavers_l,
                    reset_any, atk_l, qkey_t, qkey_next_t):
        if has_reset:
            # the simulator's reset, with the node-sum completed across
            # devices — shares the Lemma-1 invariant implementation
            st = lax.cond(
                reset_any,
                lambda ss: _reset_leavers(
                    ss, env_l, part, leavers_l,
                    total_fn=lambda c: lax.psum(jnp.sum(c, axis=0), axis)),
                lambda ss: ss, st)
        return body(st, env_l, w_t, active_l,
                    budgets_l if has_budget else None,
                    atk_l if atk_names else None,
                    qkey_t if quantized else None,
                    qkey_next_t if quantized and cfg.pipeline else None)

    # node-axis operands shard over `axis`; the per-round scalars are
    # replicated. The comm payload is the replicated (K, K) W for
    # dense/ring, the node-sharded PlanSchedule slices (diag (K,),
    # coefs (C, K)) for the per-node plan path, or the row-sharded (K, K)
    # round W for the block plan path. ColaEnv.gram_parts may be None — a
    # P(axis) prefix covers whichever leaves exist.
    node, repl = P(axis), P()
    block_mode = isinstance(plan, topo_plan.BlockPlan)
    if plan is None:
        payload_spec = repl
    elif block_mode:
        payload_spec = block_payload_pspec(axis)
    else:
        payload_spec = plan_payload_pspecs(axis)
    # attack entries are per-node (K,)-rows (the (T, K, d) bias slices to
    # (K, d)) — they shard over the node axis like the state they corrupt
    shard_step = jax.shard_map(
        shard_round, mesh=mesh,
        in_specs=(state_spec, env_spec, payload_spec, node,
                  node if has_budget else repl,
                  node if has_reset else repl, repl,
                  {n: node for n in atk_names}, repl, repl),
        out_specs=state_spec)

    zeros_k = np.zeros((rounds,), dtype)

    def step_fn(st, env_ctx, s_t):
        if plan is None:
            payload = s_t["w"]
        elif block_mode:
            payload = s_t["plan_w"]
        else:
            payload = (s_t["plan_diag"], s_t["plan_coefs"])
        atk = {n: s_t["atk_" + n] for n in atk_names}
        core = st if obs_upd is None else st._replace(counters=None)
        core = shard_step(core, env_ctx, payload, s_t["active"],
                          s_t["budgets"] if has_budget else s_t["_pad"],
                          s_t["leavers"] if has_reset else s_t["_pad"],
                          s_t["reset_any"] if has_reset else s_t["_pad"],
                          atk,
                          s_t["qkey"] if quantized else s_t["_pad"],
                          (s_t["qkey_next"] if quantized and cfg.pipeline
                           else s_t["_pad"]))
        if obs_upd is None:
            return core, None
        w = s_t.get("plan_w", s_t.get("w"))
        if w is None and plan is not None and not block_mode:
            # the per-node CommPlan path dropped the (T, K, K) W stack at
            # lowering time; rebuild this round's matrix from the executed
            # coefficients so the gate recompute judges the true W (and
            # make_update's robust-without-W guard never silently zeroes)
            w = topo_plan.w_from_coefficients_device(
                plan, s_t["plan_diag"], s_t["plan_coefs"])
        cts, obs_row = obs_upd(st, core, s_t, atk if atk_names else None, w)
        return core._replace(counters=cts), {"obs": obs_row}

    sched = dict(sched)
    sched["_pad"] = zeros_k  # scalar per-round filler for unused operands

    cad = metrics_lib.as_cadence(record_every)
    rec_mask = (None if cad
                else exec_engine.record_flags(rounds, record_every))
    cert = metrics_lib.first_certificate(rec)
    if cert is not None and cert.dynamic:
        # (attack-aware recorders also read the schedule, but their entry —
        # atk_dishonest — was materialized by apply_attacks already)
        sched.update(metrics_lib.certificate_schedule(
            rec, sched["w"], sched["active"],
            np.ones((rounds,), dtype=bool) if cad else rec_mask))
    if plan is not None:
        # materialize the per-round plan coefficients (validating that
        # every round's W stays inside the compiled support); the per-node
        # path drops the now-unconsumed (T, K, K) W stack from the device
        # schedule, the block path re-enters it row-sharded as ``plan_w``
        sched_cls = (topo_plan.BlockPlanSchedule if block_mode
                     else topo_plan.PlanSchedule)
        # a LinkCorruption-rewritten W stack varies per round even without
        # churn — the static broadcast fast path would bake round 0's links
        w_static = (active_schedule is None
                    and not (atk_info is not None and atk_info.w_modified))
        sched.update(sched_cls.from_w_stack(
            plan, sched["w"], static=w_static).entries())
        del sched["w"]
    with contextlib.ExitStack() as stack:
        run_tr = None
        if cfg.telemetry:
            from repro.obs import trace as obs_trace
            run_tr = stack.enter_context(obs_trace.use(obs_trace.Tracer()))
            stack.enter_context(run_tr.attach())
        res = exec_engine.run_round_blocks(
            step_fn, state, sched, context=env, recorder=dist_rec,
            record_mask=rec_mask, block_size=block_size, cadence=cad,
            num_rounds=rounds,
            cache_key=("cola-dist", exec_engine.fingerprint(problem), part,
                       cfg, mesh, axis, comm, conn, has_budget, has_reset,
                       dist_rec.cache_token(),
                       atk_info.token if atk_info else None))
    history = metrics_lib.history_from(dist_rec, res)
    if cfg.telemetry:
        from repro.obs import counters as obs_counters, report as obs_report
        obs_series = res.aux.get("obs") if isinstance(res.aux, dict) else None
        history["telemetry"] = obs_counters.summarize(
            res.state.counters, obs_inc, series=obs_series,
            stop_round=res.stop_round, dishonest=sched.get("atk_dishonest"))
        obs_report.auto_emit(obs_report.make_report(
            driver="run_dist_cola",
            problem_fp=exec_engine.fingerprint(problem),
            config=dataclasses.asdict(cfg),
            graph={"kind": getattr(graph, "name", type(graph).__name__),
                   "num_nodes": k},
            rounds=(rounds if res.stop_round is None
                    else res.stop_round + 1),
            history=history,
            contract=obs_inc["contract"],
            spans=run_tr.summary() if run_tr is not None else None))
    return RunResult(state=res.state, history=history)
