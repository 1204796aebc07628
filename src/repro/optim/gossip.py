"""Gossip data-parallelism: CoLA's decentralized communication pattern as a
first-class optimizer feature for the deep architectures in the zoo.

Instead of the canonical all-reduce of gradients, K nodes (mesh shards / pods)
each hold their OWN model replica, take local optimizer steps on local data,
and mix parameters with a doubly-stochastic Metropolis matrix over the node
graph — exactly Algorithm 1's communication step applied to the parameter
vector (the decentralized-SGD analogue the paper's Related Work situates CoLA
against, with CoLA's elasticity semantics carried over):

* per-round communication is O(deg(k) * |params|) neighbor exchanges
  (``lax.ppermute`` ring) instead of a global all-reduce — on a multi-pod
  deployment this removes the slow cross-pod collective from the critical
  path;
* nodes can drop (their replica freezes, W re-normalizes over the survivors)
  and re-join (re-initialized from a neighbor average) without any global
  coordination — the Fig. 4 fault-tolerance experiment for deep nets.

Two execution paths with identical semantics (validated in tests):
``vmap`` (single host, node axis stacked) and GSPMD/ppermute (node axis on a
mesh axis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import executor as exec_engine, metrics as metrics_lib, \
    mixing, quant, topology as topo
from repro.optim import privacy


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """Node graph + mixing schedule for gossip-DP."""

    num_nodes: int
    topology: str = "ring"        # any key of topology.TOPOLOGIES
    gossip_steps: int = 1         # B mixing applications per round (App. E.2)
    mix_every: int = 1            # local steps between gossip rounds
    # Byzantine-resilient aggregation of the neighbor replicas — the same
    # robust mixing layer CoLA's v-aggregation uses (repro.core.mixing);
    # dense (vmap/GSPMD) path only: the ppermute ring folds W^B and has no
    # per-neighborhood buffer to aggregate over
    robust: str | None = None     # None | "trim" | "median" | "clip"
    robust_trim: int = 1
    robust_clip: float | None = None
    # parameter-payload codec on the gossip wire ("fp32" | "int8" | "fp8" |
    # "fp8_e5m2", see repro.core.quant): every emitted replica — the own
    # diagonal term included — goes through quantize-dequantize before the
    # mix, cutting the per-link parameter traffic ~4x. STATELESS: the
    # gossip-SGD mixer re-quantizes fresh values every mix round (no error-
    # feedback carry; the local optimizer steps between rounds already
    # decorrelate the rounding error). Dense path only, like robust/dp.
    wire: str = "fp32"

    def graph(self) -> topo.Topology:
        return topo.TOPOLOGIES[self.topology](self.num_nodes)

    def weights(self, active: np.ndarray | None = None) -> np.ndarray:
        g = self.graph()
        if active is None:
            return topo.metropolis_weights(g)
        return topo.reweight_for_active(g, active)


def mix_pytree(w: jax.Array, stacked: Any, steps: int = 1) -> Any:
    """Apply the gossip matrix to every leaf of a (K, ...)-stacked pytree."""
    def mix_leaf(p):
        out = p
        for _ in range(steps):
            out = mixing.dense_mix(w, out)
        return out.astype(p.dtype)
    return jax.tree.map(mix_leaf, stacked)


def ring_mix_pytree(stacked_local: Any, axis: str, band: jax.Array,
                    conn: int, steps: int = 1) -> Any:
    """ppermute ring mixing of per-node param shards (inside shard_map)."""
    def mix_leaf(p):
        out = p[0]
        for _ in range(steps):
            out = mixing.ring_mix_ppermute(out, axis, band, conn)
        return out[None].astype(p.dtype)
    return jax.tree.map(mix_leaf, stacked_local)


def robust_mix_pytree(w: jax.Array, stacked: Any, mode: str, *,
                      trim: int = 1, clip: float | None = None,
                      steps: int = 1) -> Any:
    """Byzantine-resilient gossip over a (K, ...)-stacked pytree: each leaf
    flattens to a (K, d) stack and goes through the same
    ``mixing.robust_mix_steps`` aggregation CoLA's v-mixing uses."""
    def mix_leaf(p):
        flat = p.reshape(p.shape[0], -1)
        out = mixing.robust_mix_steps(w, flat, mode, trim=trim, clip=clip,
                                      steps=steps)
        return out.reshape(p.shape).astype(p.dtype)
    return jax.tree.map(mix_leaf, stacked)


def _param_mixer(gcfg: GossipConfig, mesh, axis: str | None,
                 conn: int | None,
                 dp: privacy.DPConfig | None = None) -> Callable:
    """``mix(w, params, key=None) -> params`` applying the B gossip steps —
    the ONE mixing dispatch both gossip drivers (per-round
    ``make_gossip_step`` and the block runner) share: dense (K, K) pytree
    mix without a mesh (optionally robust and/or DP-noised), banded
    ``ppermute`` ring under shard_map with one (circulant W of connectivity
    ``conn``). ``key`` is consumed only by the DP wire mechanism."""
    if gcfg.robust is not None and mesh is not None:
        raise ValueError(
            "robust= gossip needs the dense path: the ppermute ring folds "
            "W^B and exposes no per-neighborhood buffer (drop mesh/axis)")
    wired = quant.is_quantized(gcfg.wire)
    if wired:
        if mesh is not None:
            raise ValueError(
                "wire= gossip quantization is implemented on the dense path "
                "— the ppermute ring folds W^B and has no codec lowering "
                "(drop mesh/axis)")
        if gcfg.robust is not None:
            raise ValueError(
                "wire= with robust= is unsupported: the robust aggregators "
                "consume raw neighbor stacks, not codec payloads")
    if dp is not None:
        if mesh is not None:
            raise ValueError("dp= gossip is implemented on the dense path "
                             "(drop mesh/axis)")
        if gcfg.robust is not None:
            raise ValueError(
                "dp= with robust= is unsupported: per-link noise gives "
                "every receiver a distinct wire view, which the shared "
                "neighborhood buffer of the robust aggregation cannot "
                "represent")

    def mix(w, params, key=None):
        if dp is not None:
            return privacy.noisy_dense_mix(w, params, dp, key,
                                           gcfg.gossip_steps,
                                           wire_codec=gcfg.wire)
        if mesh is None:
            if gcfg.robust is not None:
                return robust_mix_pytree(w, params, gcfg.robust,
                                         trim=gcfg.robust_trim,
                                         clip=gcfg.robust_clip,
                                         steps=gcfg.gossip_steps)
            if wired:
                # stateless wire view per gossip step: every emission —
                # including the node's own diagonal term — is quantize-
                # dequantized before the linear mix (round-to-nearest:
                # the non-DP drivers pass no key)
                out = params
                for s in range(gcfg.gossip_steps):
                    k_s = (None if key is None
                           else quant.wire_stream(jax.random.fold_in(key, s)))
                    out = mix_pytree(
                        w, quant.wire_view_pytree(out, gcfg.wire, k_s), 1)
                return out
            return mix_pytree(w, params, gcfg.gossip_steps)
        band = mixing.banded_weights(w, conn or 1)
        shard = jax.shard_map(
            lambda p: ring_mix_pytree(p, axis, band, conn or 1,
                                      gcfg.gossip_steps),
            mesh=mesh, in_specs=P(axis), out_specs=P(axis))
        return shard(params)

    return mix


def _node_mesh(mesh, axis):
    """``(mesh, place)`` for the ring path: the Auto-typed view of the
    caller's mesh (see ``dist.sharding.auto_mesh`` — the local step is
    vmapped over replicas and batches that the model code does not
    constrain) and a function laying node-stacked pytrees out over
    ``axis`` on it. Without a mesh, ``place`` is the identity."""
    if mesh is None:
        return None, lambda tree: tree
    from repro.dist.sharding import auto_mesh
    mesh = auto_mesh(mesh)
    sh = NamedSharding(mesh, P(axis))
    return mesh, lambda tree: jax.tree.map(
        lambda x: jax.device_put(x, sh), tree)


def make_gossip_step(local_step: Callable, gcfg: GossipConfig, *,
                     mesh=None, axis: str | None = None,
                     conn: int | None = None,
                     dp: privacy.DPConfig | None = None) -> Callable:
    """Wrap a local (state, batch) -> (state, metrics) step with gossip mixing.

    Returns step(states, batches, w, active) operating on (K, ...)-stacked
    state/batch pytrees:

      1. every ACTIVE node runs ``local_step`` on its local shard of data
         (frozen nodes keep their state — the paper's Theta_k = 1 model);
      2. parameters are gossip-mixed ``gossip_steps`` times with ``w``.

    With ``mesh``/``axis`` the mixing runs as a ppermute ring under a
    shard_map over that axis (requires circulant W of connectivity ``conn``);
    otherwise a dense (K,K) mix (vmap/GSPMD path, any W) — optionally
    Byzantine-robust (``gcfg.robust``) or DP-noised (``dp=``, see
    ``repro.optim.privacy``; pass the round index as ``dp_round`` so the
    key schedule stays reproducible, and account one
    ``dp.releases_per_mix_round(...)`` batch per mixed round).
    """
    mesh, place = _node_mesh(mesh, axis)
    mix_params = _param_mixer(gcfg, mesh, axis, conn, dp)
    base_key = None if dp is None else jax.random.PRNGKey(dp.seed)

    def step(states, batches, w, active, do_mix=True, dp_round=0):
        new_states, metrics = jax.vmap(local_step)(states, batches)
        keep = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(
                active.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b),
            new, old)
        new_states = keep(new_states, states)
        if not do_mix:
            # mix_every > 1: local steps between gossip rounds — divides the
            # communication volume by mix_every at a Theta-quantified
            # convergence cost (App. E.2 in reverse)
            return new_states, metrics
        key = (None if base_key is None
               else jax.random.fold_in(base_key, dp_round))
        return new_states._replace(
            params=mix_params(w, new_states.params, key)), metrics

    jitted = jax.jit(step, static_argnames=("do_mix",))
    if mesh is None:
        return jitted

    def placed_step(states, batches, w, active, do_mix=True, dp_round=0):
        states, batches, active = place((states, batches, active))
        return jitted(states, batches, w, active, do_mix=do_mix,
                      dp_round=dp_round)

    return placed_step


def mix_schedule(rounds: int, mix_every: int) -> np.ndarray:
    """(T,) bool: gossip-mix on every ``mix_every``-th round (the last round
    of each local-step window), i.e. ``(t + 1) % mix_every == 0``."""
    return (np.arange(rounds) + 1) % mix_every == 0


def make_gossip_block_runner(local_step: Callable, gcfg: GossipConfig, *,
                             mesh=None, axis: str | None = None,
                             conn: int | None = None,
                             recorder=None,
                             dp: privacy.DPConfig | None = None,
                             telemetry: bool = False) -> Callable:
    """Round-block gossip-DP: many local-step+mix rounds per device dispatch.

    The per-round ``make_gossip_step`` path dispatches one jitted program per
    round from Python; this runner drives the identical round body through
    the shared scan executor (``repro.core.executor``) instead — batches,
    mixing matrices, active masks and mix flags are pre-staged as stacked
    (T, ...) schedule arrays, and per-round train metrics come back stacked
    in one end-of-run fetch.

    Both communication paths share the engine: the default dense (K, K) mix
    on vmap-stacked replicas, and — with ``mesh``/``axis`` — the
    shard_map/``lax.ppermute`` ring over that mesh axis (circulant W of
    connectivity ``conn``, exactly as in ``make_gossip_step``).

    A ``repro.core.metrics`` Recorder (e.g. ``ConsensusRecorder``) adds
    on-device eval rows over the replica stack — with a stop condition the
    engine short-circuits remaining rounds exactly as in the CoLA drivers
    (consensus-driven early exit).

    Returns ``run(states, batches, w, active, mix, *, block_size=32,
    record_mask=None)`` with
      batches: (T, K, ...) stacked batch pytree,
      w:       (T, K, K) per-round mixing matrices,
      active:  (T, K) participation masks,
      mix:     (T,) bool gossip-mix flags (see ``mix_schedule``),
    returning (states, metrics) — metrics leaves are (T, ...) stacks — or,
    when a recorder is set, (states, metrics, history).

    With ``dp=`` every mixed round applies the clipped Gaussian wire
    mechanism (``repro.optim.privacy``) with a per-round folded key, and
    the returned history (recorder path) gains ``dp_epsilon`` — the
    cumulative zCDP-accounted epsilon at each recorded round, counting
    ``gossip_steps * deg_max`` releases per mixed round under per-link
    noise — plus a ``dp`` summary dict (final epsilon/rho/releases).
    NOTE: ``states`` buffers are donated — do not reuse the argument.
    """
    mesh, place = _node_mesh(mesh, axis)
    mix_params = _param_mixer(gcfg, mesh, axis, conn, dp)
    base_key = None if dp is None else jax.random.PRNGKey(dp.seed)
    if telemetry and recorder is None:
        raise ValueError("telemetry=True needs a recorder (the history "
                         "carries the counters and the dp_epsilon series)")

    def step_fn(states, _ctx, sched_t):
        new_states, metrics = jax.vmap(local_step)(states, sched_t["batch"])
        active = sched_t["active"]
        keep = jax.tree.map(
            lambda a, b: jnp.where(
                active.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b),
            new_states, states)
        key = (None if base_key is None
               else jax.random.fold_in(base_key, sched_t["dp_round"]))
        mixed = lax.cond(
            sched_t["mix"],
            lambda p: mix_params(sched_t["w"], p, key),
            lambda p: p, keep.params)
        return keep._replace(params=mixed), metrics

    def run(states, batches, w, active, mix, *, block_size: int = 32,
            record_mask=None):
        states = place(states)
        sched = {"batch": batches, "w": w, "active": active, "mix": mix}
        if dp is not None:
            # per-round key index: noise draws are a function of the round,
            # not of block boundaries or early stopping
            sched["dp_round"] = np.arange(len(np.asarray(mix)))
        run_tr = None
        if telemetry:
            # per-replica parameter payload: the gossip wire moves whole
            # replicas, so the modeled budget is params x codec bytes per
            # emission — K emissions per mixed round on the dense path
            # (the all-gather oracle view), 2*conn ppermutes on the ring
            pcount = int(sum(np.prod(leaf.shape[1:])
                             for leaf in jax.tree.leaves(states.params)))
            pb = quant.payload_bytes(pcount, gcfg.wire)
            if mesh is None:
                per_mix = gcfg.gossip_steps * gcfg.num_nodes * pb
                permutes_mix = 0
                contract = (f"gossip dense x{gcfg.gossip_steps}: "
                            f"{per_mix:,}B/device/mixed-round "
                            f"({pcount:,} params, wire={gcfg.wire})")
            else:
                c = conn or 1
                per_mix = gcfg.gossip_steps * 2 * c * pb
                permutes_mix = gcfg.gossip_steps * 2 * c
                contract = (f"gossip ring conn={c}: {per_mix:,}B/device/"
                            f"mixed-round ({pcount:,} params)")
            from repro.obs import trace as obs_trace
            with obs_trace.use(obs_trace.Tracer()) as run_tr, \
                    run_tr.attach():
                res = exec_engine.run_round_blocks(step_fn, states, sched,
                                                   recorder=recorder,
                                                   record_mask=record_mask,
                                                   block_size=block_size)
        else:
            res = exec_engine.run_round_blocks(step_fn, states, sched,
                                               recorder=recorder,
                                               record_mask=record_mask,
                                               block_size=block_size)
        if recorder is None:
            return res.state, res.aux
        history = metrics_lib.history_from(recorder, res)
        if dp is not None:
            mix_host = np.asarray(mix, dtype=bool)
            rounds_rec = np.asarray(history["round"], dtype=np.int64)
            cum = np.cumsum(mix_host)
            history["dp_epsilon"] = privacy.epsilon_schedule(
                dp, gcfg.graph(), gcfg.gossip_steps,
                cum[np.clip(rounds_rec, 0, len(cum) - 1)]).tolist()
            final = privacy.GaussianAccountant(dp.sigma, dp.delta).add(
                int(cum[-1]) * dp.releases_per_mix_round(gcfg.graph(),
                                                         gcfg.gossip_steps))
            history["dp"] = {
                "clip": dp.clip, "sigma": dp.sigma, "delta": dp.delta,
                "per_link": dp.per_link, "releases": final.releases,
                "rho": final.rho, "epsilon": final.epsilon()}
        if telemetry:
            from repro.obs import report as obs_report
            mixed = int(np.asarray(mix, dtype=bool).sum())
            t_total = int(np.asarray(mix).shape[0])
            history["telemetry"] = {
                "rounds": t_total, "mixed_rounds": mixed,
                "wire_bytes": mixed * per_mix,
                "permutes": mixed * permutes_mix,
                "contract": contract, "stop_round": res.stop_round}
            if dp is not None and history.get("dp_epsilon"):
                history["telemetry"]["dp_epsilon"] = \
                    float(history["dp_epsilon"][-1])
            obs_report.auto_emit(obs_report.make_report(
                driver="gossip",
                problem_fp=exec_engine.fingerprint(gcfg),
                config=dataclasses.asdict(gcfg),
                graph={"kind": gcfg.topology,
                       "num_nodes": gcfg.num_nodes},
                rounds=t_total, history=history, contract=contract,
                spans=run_tr.summary()))
        return res.state, res.aux, history

    return run


@dataclasses.dataclass(frozen=True)
class ConsensusRecorder:
    """Recorder over the (K, ...)-stacked replica state: the deep-net
    consensus distance (Fig. 5 analogue), with optional early stop once the
    replicas agree to ``eps`` (e.g. after a final full-averaging round)."""

    eps: float | None = None

    labels = ("consensus_distance",)

    def record_fn(self, states) -> jax.Array:
        return jnp.stack([consensus_distance(states.params)])

    @property
    def stop_fn(self):
        if self.eps is None:
            return None
        eps = self.eps
        return lambda row: row[0] <= eps

    def init_spec(self) -> dict:
        return {}

    def cache_token(self):
        return ("ConsensusRecorder", self.eps)

    def collective_footprint(self, k, d, n_k, itemsize=4, comm="dense",
                             conn=1) -> dict:
        return {"all-gather": 0, "all-reduce": 2 * itemsize,
                "collective-permute": 0}


def replicate_state(state: Any, k: int) -> Any:
    """Stack K identical replicas on a new leading node axis."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (k,) + p.shape),
                        state)


def consensus_distance(params_stack: Any) -> jax.Array:
    """sum_k ||p_k - p_bar||^2 over all leaves — the deep-net analogue of the
    paper's consensus violation (Fig. 5)."""
    def leaf(p):
        mean = jnp.mean(p, axis=0, keepdims=True)
        return jnp.sum((p.astype(jnp.float32) - mean.astype(jnp.float32))**2)
    return sum(jax.tree.leaves(jax.tree.map(leaf, params_stack)))


def average_params(params_stack: Any) -> Any:
    return jax.tree.map(lambda p: jnp.mean(p, axis=0), params_stack)
