"""CoLA — Algorithm 1, plus the CoCoA special case and the elastic runtime.

The single-host simulator keeps all K nodes' state stacked:
  x_parts (K, n_k), v_stack (K, d); one round is a single jitted program
(gossip mix -> vmapped local CD solve -> local updates). The shard_map
distributed runtime in ``repro.dist.runtime`` executes the same math with the
node axis laid out over mesh devices; tests assert bitwise-equivalent rounds.

Two interchangeable drivers execute the rounds (tests assert they are
bitwise identical):

* ``executor="loop"`` — the retained reference path: one ``make_round``
  dispatch per round, metrics fetched synchronously every ``record_every``.
* ``executor="block"`` (default) — the round-block engine
  (``repro.core.executor``): schedules (per-round mixing matrices, active
  masks, CD budgets, reset flags) are pre-materialized as stacked (T, ...)
  arrays, ``block_size`` rounds run per device dispatch inside a
  ``lax.scan``, metric history is recorded on device and fetched once at
  the end, and the (K, n_k)/(K, d) state buffers are donated across blocks.

Recording and stopping go through the pluggable Recorder layer
(``repro.core.metrics``): ``recorder="gap"`` keeps the historical Lemma-2
history, ``recorder="certificate"`` records the Prop.-1 local certificates,
and ``eps=`` arms certificate-driven early termination (the round budget
becomes an upper bound).

The local CD solve picks between the residual and Gram-cached formulations
(``repro.core.subproblem.gram_pays``) via ``ColaConfig.cd_mode``.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import executor as exec_engine, metrics as metrics_lib, \
    mixing, quant, schedule as schedule_lib, topology as topo
from repro.core.duality import GapReport, gap_report
from repro.core.partition import Partition, make_partition
from repro.core.precision import MATMUL
from repro.core.problems import Problem
from repro.core.subproblem import (SubproblemSpec, block_gram, cd_solve_all,
                                   gram_pays)


@dataclasses.dataclass(frozen=True)
class ColaConfig:
    """Hyper-parameters of Algorithm 1. The paper's safe defaults need no tuning."""

    gamma: float = 1.0              # aggregation parameter (paper uses 1)
    sigma_prime: float | None = None  # subproblem relaxation; default gamma*K
    kappa: float = 1.0              # CD passes over the local block per round;
    #   kappa * n_k = the paper's "number of coordinates updated" (Fig. 1),
    #   the knob controlling the local accuracy Theta. May be fractional.
    gossip_steps: int = 1           # B gossip steps per round (App. E.2)
    grad_mode: str = "local"        # "local" (Eq. 2) | "mixed" (App. E.1)
    cd_mode: str = "auto"           # local solver formulation:
    #   "auto" — Gram-cached when subproblem.gram_pays says it's cheaper,
    #   "gram" / "residual" — force one path (see subproblem docstring).
    robust: str | None = None       # Byzantine-resilient v aggregation:
    #   None — the paper's linear W mix; "trim" / "median" / "clip" swap in
    #   repro.core.mixing.robust_neighborhood_mix (per-neighborhood trimmed
    #   mean / median / per-neighbor norm clipping). Nonlinear: B gossip
    #   steps apply sequentially (no W^B fold).
    robust_trim: int = 1            # extremes dropped per side ("trim" mode)
    robust_clip: float | None = None  # clip radius; None = median-adaptive
    wire: str = "fp32"              # gossip payload codec (repro.core.quant):
    #   "fp32" — the paper's full-precision wire; "int8" / "fp8" /
    #   "fp8_e5m2" — per-node-row absmax quantization with stochastic
    #   rounding keyed by fold_in(round, step, color): payloads cross every
    #   link at 1 byte/elem plus a 4-byte fp32 scale sidecar per row.
    error_feedback: bool = True     # EF-compressed gossip on quantized
    #   wires: send Q(v + e), keep e' = (v + e) - deq. The residual rides
    #   the scan carry (ColaState.ef) and telescopes across rounds, which
    #   is what lets the narrow wire reach the fp32 fixed point; without it
    #   the quantization error accumulates as a noise floor.
    pipeline: bool = False          # software-pipeline comm against compute
    #   (quantized wires only): round t+1's step-0 payload is encoded at
    #   the END of round t and double-buffered in the scan carry
    #   (ColaState.buf), so its ppermutes issue at the TOP of the next
    #   round body BEFORE the local CD solve — bitwise identical to the
    #   unpipelined schedule, structured so a Pallas async-remote-DMA
    #   backend can overlap the transfer with the solve.
    telemetry: bool = False         # carry repro.obs.Counters through the
    #   round scan (block executor only): per-round wire bytes/ppermutes,
    #   quant saturation + EF norm, robust-gate rejection counts. Totals
    #   land in history["telemetry"] and a RunReport is appended to the
    #   .repro_runs registry. Off: the program is bitwise the untelemetered
    #   one (the counters field stays None and traces away).
    participation: Any = None       # partial participation (client
    #   sampling): a repro.core.schedule.SampleConfig — each round K' of K
    #   nodes are sampled active via a fold_in(round) draw STREAMED inside
    #   the round scan (no (T, K)-shaped schedule is materialized). Dense
    #   mode (K <= schedule.DENSE_MAX_NODES) streams the reweighted mixing
    #   matrix through the standard round body; cohort mode (million-node
    #   populations) gathers/updates only the (K', ...) cohort slices and
    #   never builds a (K, K) array. Requires executor="block" and a
    #   complete base graph (see repro.core.schedule).

    def resolved_sigma(self, k: int) -> float:
        return self.gamma * k if self.sigma_prime is None else self.sigma_prime

    def coord_steps(self, block: int) -> int:
        return max(1, int(round(self.kappa * block)))

    def use_gram(self, d: int, n_k: int, itemsize: int = 4) -> bool:
        if self.cd_mode == "gram":
            return True
        if self.cd_mode == "residual":
            return False
        return gram_pays(d, n_k, itemsize)


class ColaState(NamedTuple):
    x_parts: jax.Array  # (K, n_k)
    v_stack: jax.Array  # (K, d)
    # (K, d) error-feedback residual on quantized wires (None on fp32: the
    # pytree — and every fp32 program — is unchanged by the new fields)
    ef: jax.Array | None = None
    # pre-encoded (payload, scale) for the NEXT round's step-0 gossip when
    # cfg.pipeline — the double buffer the round body's ppermutes consume
    buf: Any = None
    # repro.obs.Counters telemetry accumulators when cfg.telemetry (None
    # otherwise — the pytree, and every untelemetered program, unchanged)
    counters: Any = None


class ColaEnv(NamedTuple):
    """Per-run arrays derived from the problem + partition."""

    a_parts: jax.Array   # (K, d, n_k)
    gp_parts: jax.Array  # (K, n_k)
    masks: jax.Array     # (K, n_k)
    # (K, n_k, n_k) node-local Gram blocks A_[k]^T A_[k] for the Gram-cached
    # CD path, or None when the heuristic says the residual path is cheaper.
    gram_parts: jax.Array | None = None


def build_env(problem: Problem, part: Partition, *,
              with_gram: bool | None = None) -> ColaEnv:
    """Materialize the per-run arrays. ``with_gram=None`` precomputes the
    Gram blocks exactly when ``subproblem.gram_pays`` says the Gram-cached
    CD formulation is the cheaper one for this (d, n_k, dtype)."""
    a_parts = part.split_matrix(problem.a)
    if with_gram is None:
        with_gram = gram_pays(problem.d, part.block, a_parts.dtype.itemsize)
    return ColaEnv(
        a_parts=a_parts,
        gp_parts=part.split_vector(problem.g_params()),
        masks=part.mask(problem.a.dtype),
        gram_parts=block_gram(a_parts) if with_gram else None,
    )


def init_state(problem: Problem, part: Partition) -> ColaState:
    return ColaState(
        x_parts=jnp.zeros((part.num_nodes, part.block), dtype=problem.a.dtype),
        v_stack=jnp.zeros((part.num_nodes, problem.d), dtype=problem.a.dtype),
    )


def _apply_payload_attack(v: jax.Array, atk: dict | None) -> jax.Array:
    """The wire transform a Byzantine/free-rider schedule applies to the
    OUTGOING per-node payloads: ``coef * v + bias_coef * bias``. One shared
    implementation feeds both the round body's mix input and the
    eavesdropper taps, so what the tap records is exactly what crossed the
    wire. Elementwise per node: identical on stacked (K, d) and node-sharded
    (ln, d) operands."""
    if not atk:
        return v
    if "coef" in atk:
        v = atk["coef"][:, None].astype(v.dtype) * v
    if "bias_coef" in atk:
        v = v + (atk["bias_coef"][:, None].astype(v.dtype)
                 * atk["bias"].astype(v.dtype))
    return v


def _round_body(problem: Problem, part: Partition, cfg: ColaConfig, *,
                mix_fn: Callable | None = None,
                grad_mix_fn: Callable | None = None,
                qmix_fn: Callable | None = None,
                qencode_fn: Callable | None = None) -> Callable:
    """The pure one-round function of Algorithm 1, shared verbatim by the
    per-round loop (``make_round``), the round-block scan executor, and the
    shard_map distributed runtime (``repro.dist.runtime``) — which is what
    makes the drivers bitwise identical.

    ``mix_fn(w, v_send, v_self)`` applies the B gossip steps (default: the
    dense ``mixing.mix_power_wire`` on the full stacked state, or the
    robust dense aggregation when ``cfg.robust`` is set); ``v_self`` is
    None unless a wire attack corrupted the payloads. ``grad_mix_fn(w,
    grads)`` applies one mixing step for ``grad_mode='mixed'``. The
    distributed runtime swaps in collective (ppermute/all-gather)
    implementations while every node-local op stays this exact code.

    ``atk`` (an optional dict of per-node attack operands sliced from the
    schedule by the drivers — see ``repro.attack``) corrupts the round: the
    emitted payload becomes ``coef * v + bias_coef * bias`` on the wire
    BEFORE the gossip mix — receivers consume the lie while every node's
    own state (and own mixing term) evolves honestly — and ``work`` masks
    dx after the solve (free riders). All elementwise per node, so the
    simulator's (K,) entries and the distributed runtime's node-sharded
    slices produce bitwise-identical rounds.
    """
    k = part.num_nodes
    sigma = cfg.resolved_sigma(k)
    spec = SubproblemSpec(sigma_over_tau=sigma / problem.tau, inv_k=1.0 / k)
    quantized = quant.is_quantized(cfg.wire)
    # a caller-supplied qmix_fn is a LOWERED wire (the dist runtime's
    # collective codec path — robust-aware when cfg.robust is set): the
    # composed simulator-oracle branch below must not shadow it, or the
    # encode would draw LOCAL row keys under shard_map
    lowered_qmix = qmix_fn is not None
    if quantized and qmix_fn is None:
        # simulator oracle: quantize-dequantize every node's payload (own
        # diagonal term included — the device-count-invariant wire view),
        # then the dense W contraction on the dequantized stack
        qmix_fn = lambda w, v, ef, qkey, payload: mixing.qmix_steps(
            w, v, ef, cfg.gossip_steps, cfg.wire, qkey, payload=payload)
    if quantized and qencode_fn is None:
        qencode_fn = lambda v, ef, nkey: quant.encode(
            v, cfg.wire, quant.step_key(nkey, 0), None, ef)
    if mix_fn is None:
        if cfg.robust is not None:
            mix_fn = lambda w, v_send, v_self: mixing.robust_mix_steps(
                w, v_send, cfg.robust, trim=cfg.robust_trim,
                clip=cfg.robust_clip, steps=cfg.gossip_steps,
                self_stack=v_self)
        else:
            mix_fn = lambda w, v_send, v_self: mixing.mix_power_wire(
                w, v_send, v_self, cfg.gossip_steps)
    if grad_mix_fn is None:
        grad_mix_fn = mixing.dense_mix

    def one_round(state: ColaState, env: ColaEnv, w: jax.Array,
                  active: jax.Array,
                  budgets: jax.Array | None = None,
                  atk: dict | None = None,
                  qkey: jax.Array | None = None,
                  qkey_next: jax.Array | None = None) -> ColaState:
        # Step 4: gossip mixing of the local estimates (B steps, App. E.2).
        # A payload attack exists ONLY on the wire: receivers consume the
        # lie, but each node's own mixing term and its internal state stay
        # honest (a two-faced attacker — the stealthiest case for the
        # certificate layer to catch). v_self=None flags the honest fast
        # path, which is then bitwise the unattacked program.
        if quantized and not lowered_qmix and (cfg.robust is not None or atk):
            # quantized wire composed with attacks and/or a robust defense
            # (simulator only — _check_wire_config scopes it to the dense
            # path, gossip_steps=1, no pipeline): the lie transforms the
            # fp32 value and is then ENCODED, so only codec payloads ever
            # cross the narrow wire; each node's own slot (and its EF
            # residual) tracks the codec view of its HONEST value, making
            # honest nodes' draws — and a clean defended run — bitwise the
            # undefended quantized program's.
            key0 = None if qkey is None else quant.step_key(qkey, 0)
            _, _, deq_self, ef_new = quant.encode(state.v_stack, cfg.wire,
                                                  key0, None, state.ef)
            v_send = _apply_payload_attack(state.v_stack, atk)
            if v_send is state.v_stack:
                deq_send, self_stack = deq_self, None
            else:
                p_atk = v_send if state.ef is None else v_send + state.ef
                qa, sa = quant.quantize_rows(p_atk, cfg.wire, key0)
                deq_send, self_stack = quant.dequantize(qa, sa), deq_self
            if cfg.robust is not None:
                v_half = mixing.robust_mix_steps(
                    w, deq_send, cfg.robust, trim=cfg.robust_trim,
                    clip=cfg.robust_clip, steps=cfg.gossip_steps,
                    self_stack=self_stack)
            else:
                v_half = mixing.mix_power_wire(w, deq_send, self_stack,
                                               cfg.gossip_steps)
        elif quantized:
            # quantized wire: EF-compensated codec view of every payload;
            # when pipelining, state.buf holds the step-0 payload encoded
            # at the end of the previous round — the first ppermutes issue
            # here, BEFORE this round's CD solve below
            v_half, ef_new = qmix_fn(w, state.v_stack, state.ef, qkey,
                                     state.buf)
        else:
            v_send = _apply_payload_attack(state.v_stack, atk)
            v_self = None if v_send is state.v_stack else state.v_stack
            v_half = mix_fn(w, v_send, v_self)

        # Gradient each node uses for its subproblem.
        grads = jax.vmap(problem.grad_f)(v_half)
        if cfg.grad_mode == "mixed":
            # App. E.1: use the neighborhood-mixed gradient sum_l W_kl grad f(v_l).
            grads = grad_mix_fn(w, grads)

        # Step 5: Theta-approximate local subproblem solve (kappa * n_k CD
        # steps; per-node budgets model heterogeneous Theta_k, Definition 5).
        use_gram = (env.gram_parts is not None
                    and cfg.use_gram(problem.d, part.block,
                                     env.a_parts.dtype.itemsize))
        if cfg.cd_mode == "gram" and env.gram_parts is None:
            raise ValueError(
                "cd_mode='gram' but the env has no Gram blocks — build it "
                "with build_env(problem, part, with_gram=True)")
        dx = cd_solve_all(problem, spec, env.a_parts, state.x_parts, grads,
                          env.gp_parts, env.masks, cfg.coord_steps(part.block),
                          step_budgets=budgets,
                          gram_parts=env.gram_parts if use_gram else None)
        dx = dx * active[:, None].astype(dx.dtype)
        if atk is not None and "work" in atk:
            # free riders: no local progress this round
            dx = dx * atk["work"][:, None].astype(dx.dtype)

        # Steps 6-8: local variable + local estimate updates.
        x_new = state.x_parts + cfg.gamma * dx
        dv = jnp.einsum("kdn,kn->kd", env.a_parts, dx, precision=MATMUL)
        v_new = v_half + cfg.gamma * k * dv
        if not quantized:
            return ColaState(x_parts=x_new, v_stack=v_new)
        buf_new = None
        if cfg.pipeline:
            # modulo schedule: encode the NEXT round's step-0 payload now,
            # with the next round's codec key — bitwise what the next round
            # would have encoded at its top, just issued one round early
            q, s, _, ef_new = qencode_fn(v_new, ef_new, qkey_next)
            buf_new = (q, s)
        return ColaState(x_parts=x_new, v_stack=v_new, ef=ef_new,
                         buf=buf_new)

    return one_round


def make_round(problem: Problem, part: Partition, cfg: ColaConfig
               ) -> Callable[[ColaState, ColaEnv, jax.Array, jax.Array], ColaState]:
    """Build the jitted one-round function of Algorithm 1.

    Returned signature: round(state, env, w, active) -> state. ``w`` and
    ``active`` are dynamic so fault-tolerance schedules don't retrigger
    compilation.
    """
    return jax.jit(_round_body(problem, part, cfg))


def cocoa_mixing(k: int) -> np.ndarray:
    """W = (1/K) 11^T: one gossip step yields the exact consensus v_c = Ax,
    recovering centralized CoCoA as a special case of CoLA."""
    return np.full((k, k), 1.0 / k)


class RunResult(NamedTuple):
    state: ColaState
    history: dict  # lists keyed by metric name
    # Eavesdropper tap trajectory (T, n_tap, d) when the attack list carries
    # a repro.attack.Eavesdropper (simulator only); None otherwise.
    taps: Any = None


_METRICS = metrics_lib.GAP_METRICS


def run_cola(problem: Problem, graph: topo.Topology, cfg: ColaConfig,
             rounds: int, *, record_every: int = 1,
             recorder: str | Any = "gap", eps: float | None = None,
             active_schedule: Callable[[int, np.random.Generator], np.ndarray] | None = None,
             budget_schedule: Callable[[int, np.random.Generator], np.ndarray] | None = None,
             leave_mode: str = "freeze", seed: int = 0,
             w_override: np.ndarray | None = None,
             attacks=None,
             executor: str = "block", block_size: int = 64) -> RunResult:
    """Driver: runs Algorithm 1 under a pluggable metric Recorder.

    Args:
      recorder: "gap" (Lemma-1/2 diagnostics, the historical history keys),
        "certificate" (Prop.-1 local certificates), "gap+certificate", or a
        ``repro.core.metrics`` Recorder instance. History keys follow the
        recorder's labels.
      eps: target duality gap; arms certificate-driven early stopping.
        ``rounds`` becomes a budget: the run terminates at the first record
        round whose row certifies (certificate recorder) or reaches
        ``gap <= eps`` (gap recorder), with final state bitwise identical
        to a non-stopping run truncated at that round. Stopping is only
        checked on record rounds — ``record_every`` is the certification
        cadence.
      record_every: fixed integer cadence, or ``"adaptive"`` / a
        ``metrics.AdaptiveCadence`` to let the recorder drive it on device:
        geometric back-off while the recorded row is far from the stop
        threshold, tightening to ``base`` near certification. Both drivers
        implement the identical controller (the loop driver on host, the
        block driver inside the scan carry), so histories still match.
      active_schedule: optional (round, rng) -> (K,) bool mask simulating node
        churn (Fig. 4/6), or a pre-materialized (T, K) bool array (the
        array form consumes no draws from the shared schedule rng). W is
        re-normalized over the active subgraph each round via Metropolis
        weights.
      budget_schedule: optional (round, rng) -> (K,) int CD-step budgets —
        heterogeneous per-node solver quality Theta_k (Definition 5):
        stragglers do fewer coordinate updates this round. Also accepts a
        pre-materialized (T, K) int array.
      leave_mode: "freeze" (paper's main model: x_[k] frozen) or "reset"
        (App. D Fig. 6: x_[k] zeroed and all v_j adjusted to preserve the
        Lemma-1 mean invariant).
      w_override: use this mixing matrix instead of Metropolis weights
        (e.g. ``cocoa_mixing(K)`` for the centralized special case).
      attacks: optional ``repro.attack`` scenario (or list of scenarios) —
        Byzantine payloads, free riders, link corruption, eavesdropper
        taps — applied as transforms over the pre-materialized schedule
        (block executor only). Composes with churn/budget schedules, which
        materialize first. Defenses are orthogonal: set ``cfg.robust``.
        An ``Eavesdropper`` fills ``RunResult.taps``.
      executor: "block" (default) runs ``block_size`` rounds per device
        dispatch via the round-block engine; "loop" is the retained
        one-dispatch-per-round reference path. Both consume the schedule
        rngs identically and produce bitwise-identical states.
      block_size: rounds per dispatch for the block executor.
    """
    k = graph.num_nodes
    _check_wire_config(cfg, attacks=attacks, leave_mode=leave_mode)
    part = make_partition(problem.n, k)
    sample = cfg.participation
    if sample is not None:
        if not isinstance(sample, schedule_lib.SampleConfig):
            raise TypeError(
                f"cfg.participation must be a repro.core.schedule."
                f"SampleConfig, got {type(sample).__name__}")
        if active_schedule is not None:
            raise ValueError(
                "participation= and active_schedule= both set: client "
                "sampling IS an active schedule — pass one or the other")
        if executor != "block":
            raise ValueError(
                "cfg.participation requires executor='block' — the sampled "
                "schedule streams through the round-block scan")
        schedule_lib.require_complete(graph)
        if sample.resolve_mode(k) == "cohort":
            return _run_cola_cohort(
                problem, graph, cfg, rounds, part=part,
                record_every=record_every, recorder=recorder, eps=eps,
                budget_schedule=budget_schedule, leave_mode=leave_mode,
                seed=seed, w_override=w_override, attacks=attacks,
                block_size=block_size)
    # honor cfg.cd_mode: forced "gram" must materialize the blocks even when
    # the heuristic declines, forced "residual" must not pay for them
    env = build_env(problem, part,
                    with_gram=cfg.use_gram(problem.d, part.block,
                                           problem.a.dtype.itemsize))
    state = init_state(problem, part)
    base_w = w_override if w_override is not None else topo.metropolis_weights(graph)
    rec = metrics_lib.make_recorder(recorder, problem, part, env, graph,
                                    base_w, eps)
    active_schedule = _as_schedule_fn(active_schedule, rounds, k,
                                      "active_schedule")
    budget_schedule = _as_schedule_fn(budget_schedule, rounds, k,
                                      "budget_schedule")
    if active_schedule is not None or sample is not None:
        # churn (and client sampling, which is streamed churn): certificates
        # must judge each record round against the REWEIGHTED exchange
        # (mask + beta of the active subnetwork), not the static graph
        # baked at init
        rec = metrics_lib.dynamize(rec)
    args = (problem, part, env, state, graph, cfg, rounds, record_every,
            rec, active_schedule, budget_schedule, leave_mode, seed, base_w)
    if executor == "block":
        return _run_cola_block(*args, attacks=attacks, block_size=block_size)
    if executor == "loop":
        if attacks is not None:
            raise ValueError(
                "attacks= requires executor='block' — attack scenarios are "
                "schedule transforms over the pre-materialized (T, ...) "
                "schedules the loop driver does not build")
        if cfg.telemetry:
            raise ValueError(
                "cfg.telemetry requires executor='block' — the obs "
                "counters ride the round-block scan carry")
        return _run_cola_loop(*args)
    raise ValueError(f"unknown executor {executor!r} (want 'block' or 'loop')")


def _check_wire_config(cfg: ColaConfig, *, attacks=None,
                       leave_mode: str = "freeze", dist: bool = False) -> None:
    """Reject config corners the quantized wire deliberately does not
    support yet (scope control: each would silently change what crosses
    the wire, so failing loudly beats a wrong byte budget)."""
    if not quant.is_quantized(cfg.wire):
        if cfg.pipeline:
            raise ValueError(
                "cfg.pipeline requires a quantized wire — the fp32 payload "
                "has no encode step to hoist (set wire='int8'/'fp8')")
        return
    composed = attacks is not None or cfg.robust is not None
    if dist and attacks is not None:
        raise NotImplementedError(
            "attacks= with a quantized wire on the distributed runtime: "
            "the shard_map qmix lowerings have no attacked-encode path yet "
            "(the simulator supports this composition)")
    if composed and cfg.pipeline:
        raise NotImplementedError(
            "cfg.pipeline with attacks=/cfg.robust on a quantized wire: "
            "the double-buffered payload is encoded a round early, before "
            "the attack transform / gate decision for its round exists")
    if composed and cfg.gossip_steps != 1:
        raise NotImplementedError(
            "attacks=/cfg.robust on a quantized wire require "
            "gossip_steps=1: steps 2..B would have to re-encode mixed "
            "values, which the composed path does not model yet")
    if cfg.grad_mode == "mixed":
        raise NotImplementedError(
            "grad_mode='mixed' with a quantized wire: the gradient exchange "
            "would cross in fp32 and break the declared byte budget")
    if cfg.pipeline and leave_mode == "reset":
        raise NotImplementedError(
            "cfg.pipeline with leave_mode='reset': the pre-encoded payload "
            "in flight would be stale after the leaver reset")


def _arm_wire_state(state: ColaState, cfg: ColaConfig, key0) -> ColaState:
    """Attach the quantized-wire carry to a fresh state: the EF residual
    (zeros) and, when pipelining, round 0's pre-encoded payload."""
    if not quant.is_quantized(cfg.wire):
        return state
    ef = quant.ef_init(state.v_stack, cfg.wire) if cfg.error_feedback else None
    buf = None
    if cfg.pipeline:
        q, s, _, ef = quant.encode(state.v_stack, cfg.wire,
                                   quant.step_key(jnp.asarray(key0), 0),
                                   None, ef)
        buf = (q, s)
    return state._replace(ef=ef, buf=buf)


def _run_cola_loop(problem, part, env, state, graph, cfg, rounds, record_every,
                   recorder, active_schedule, budget_schedule, leave_mode,
                   seed, base_w) -> RunResult:
    """Reference driver: one jitted dispatch per round, blocking metric sync
    every ``record_every`` rounds (the seed behaviour, kept for equivalence
    tests and as the benchmark baseline). Consumes the same Recorder as the
    block engine: one jitted row per record round, host-side stop check."""
    k = part.num_nodes
    # content-addressed: a rebuilt identical Problem reuses the driver, a
    # same-address different-content Problem misses (see executor.fingerprint)
    prob_fp = exec_engine.fingerprint(problem)
    one_round = exec_engine.cached_driver(
        ("cola-round", prob_fp, part, cfg),
        lambda: make_round(problem, part, cfg))
    rng = np.random.default_rng(seed)
    qkeys = None
    if quant.is_quantized(cfg.wire):
        # one extra row: the pipelined body encodes round t+1's payload
        qkeys = jnp.asarray(quant.round_keys(seed, rounds + 1))
        state = _arm_wire_state(state, cfg, qkeys[0])

    dtype = problem.a.dtype
    w = jnp.asarray(base_w, dtype=dtype)
    all_active = np.ones((k,), dtype=bool)
    history: dict = {"round": []}
    history.update({name: [] for name in recorder.labels})
    history["stop_round"] = None

    uses_sched = bool(getattr(recorder, "uses_schedule", False))
    cert = metrics_lib.first_certificate(recorder) if uses_sched else None
    report = exec_engine.cached_driver(
        ("cola-report", prob_fp, part, recorder.cache_token()),
        lambda: jax.jit(recorder.record_fn))
    stop_fn = recorder.stop_fn

    # host twin of the executor's on-device AdaptiveCadence controller:
    # identical integer cadence arithmetic and f32 ratio compare, so loop
    # and block drivers record the same rounds
    cad = metrics_lib.as_cadence(record_every)
    next_rec, every = 0, (cad.base if cad else None)

    prev_active = all_active
    for t in range(rounds):
        if active_schedule is not None:
            active = np.asarray(active_schedule(t, rng), dtype=bool)
            if not active.any():
                active = all_active.copy()  # never let the whole network die
            w_t = jnp.asarray(topo.reweight_for_active(graph, active), dtype=dtype)
            if leave_mode == "reset":
                leavers = prev_active & ~active
                if leavers.any():
                    state = _reset_leavers(state, env, part, leavers)
            prev_active = active
        else:
            active, w_t = all_active, w
        budgets = None
        if budget_schedule is not None:
            budgets = jnp.asarray(budget_schedule(t, rng), dtype=jnp.int32)
        if qkeys is None:
            state = one_round(state, env, w_t,
                              jnp.asarray(active, dtype=dtype), budgets)
        else:
            state = one_round(state, env, w_t,
                              jnp.asarray(active, dtype=dtype), budgets,
                              None, qkeys[t], qkeys[t + 1])
        due = (t >= next_rec) if cad else (t % record_every == 0)
        if due or t == rounds - 1:
            if uses_sched:
                mask_t, thr_t = metrics_lib.certificate_round_inputs(
                    cert, w_t, active)
                row = report(state, {
                    "cert_mask": jnp.asarray(mask_t, dtype),
                    "cert_grad_thresh": jnp.asarray(thr_t, dtype)})
            else:
                row = report(state)
            history["round"].append(t)
            for j, name in enumerate(recorder.labels):
                history[name].append(float(row[j]))
            if cad:
                far = (np.float32(recorder.cadence_ratio(row))
                       > np.float32(cad.near))
                every = (min(every * cad.grow, cad.max_every) if far
                         else cad.base)
                next_rec = t + every
            if stop_fn is not None and bool(stop_fn(row)):
                history["stop_round"] = t
                break
    return RunResult(state=state,
                     history=metrics_lib.annotate_violation(history))


def _as_schedule_fn(s, rounds: int, k: int, name: str):
    """Normalize a schedule argument: pass callables (and None) through,
    wrap a pre-materialized (T, K) array as a per-round lookup. The wrapper
    ignores the shared schedule rng — callers mixing array and callable
    schedules must account for the draws the array form no longer takes."""
    if s is None or callable(s):
        return s
    arr = np.asarray(s)
    if arr.shape != (rounds, k):
        raise ValueError(f"pre-materialized {name} must be ({rounds}, {k}),"
                         f" got {arr.shape}")
    return lambda t, rng: arr[t]


def _materialize_schedule(graph, rounds, active_schedule, budget_schedule,
                          leave_mode, seed, base_w, dtype) -> dict:
    """Evaluate the host-side schedule callables for all T rounds up front,
    into stacked (T, ...) arrays the scan executor can slice per block.

    The rng is consumed in the same per-round order as the loop driver
    (active draw, then budget draw), so both drivers see identical schedules
    for the same seed.
    """
    k = graph.num_nodes
    has_churn = active_schedule is not None
    has_budget = budget_schedule is not None
    has_reset = has_churn and leave_mode == "reset"
    rng = np.random.default_rng(seed)

    if has_churn:
        w_stack = np.empty((rounds, k, k), dtype=dtype)
        actives = np.empty((rounds, k), dtype=dtype)
    else:
        # no churn: every round shares base_w; broadcast views keep the
        # schedule O(K^2) in host memory, copied blockwise at dispatch
        w_stack = np.broadcast_to(np.asarray(base_w, dtype=dtype),
                                  (rounds, k, k))
        actives = np.broadcast_to(np.ones((k,), dtype=dtype), (rounds, k))
    budgets = np.empty((rounds, k), np.int32) if has_budget else None
    leavers = np.zeros((rounds, k), bool) if has_reset else None
    reset_any = np.zeros((rounds,), bool) if has_reset else None

    prev_active = np.ones((k,), dtype=bool)
    if has_churn or has_budget:
        for t in range(rounds):
            if has_churn:
                active = np.asarray(active_schedule(t, rng), dtype=bool)
                if not active.any():
                    active = np.ones((k,), dtype=bool)
                w_stack[t] = topo.reweight_for_active(graph, active)
                actives[t] = active.astype(dtype)
                if has_reset:
                    left = prev_active & ~active
                    leavers[t] = left
                    reset_any[t] = left.any()
                prev_active = active
            if has_budget:
                budgets[t] = np.asarray(budget_schedule(t, rng),
                                        dtype=np.int32)

    sched = {"w": w_stack, "active": actives}
    if has_budget:
        sched["budgets"] = budgets
    if has_reset:
        sched["leavers"] = leavers
        sched["reset_any"] = reset_any
    return sched


def _run_cola_block(problem, part, env, state, graph, cfg, rounds,
                    record_every, recorder, active_schedule, budget_schedule,
                    leave_mode, seed, base_w, *, attacks=None,
                    block_size) -> RunResult:
    """Round-block driver: ``block_size`` rounds per dispatch (see
    ``repro.core.executor``), the Recorder's row computed on device inside
    the scan, certificate-driven early exit handled by the engine."""
    dtype = problem.a.dtype
    sample = cfg.participation
    sched = _materialize_schedule(graph, rounds, active_schedule,
                                  budget_schedule, leave_mode, seed, base_w,
                                  dtype)
    atk_info = None
    atk_part = None
    if attacks is not None:
        from repro import attack as attack_lib
        ctx = attack_lib.AttackContext(graph=graph, rounds=rounds,
                                       k=part.num_nodes, d=problem.d,
                                       dtype=dtype, seed=seed)
        if sample is not None:
            # a participation run streams its schedule, so the attacks must
            # be generative too: one composed jax part rides the same
            # stream (W-rewriting / recording scenarios raise here)
            atk_part, atk_info = attack_lib.streamed_attacks(attacks, ctx)
        else:
            # attacks transform the schedule AFTER churn/budgets materialize
            # and BEFORE the certificate schedule derives from it —
            # certificates judge the corrupted exchange, exactly what ran
            sched, atk_info = attack_lib.apply_attacks(sched, attacks, ctx)
        if "dishonest" in atk_info.entry_names:
            # payload-corrupting attacks: the certificate audits the honest
            # cohort against the ground-truth dishonesty mask the schedule
            # transform recorded (see metrics.attackify)
            recorder = metrics_lib.attackify(recorder)
    atk_names = atk_info.entry_names if atk_info else ()
    tap_nodes = atk_info.tap_nodes if atk_info else ()
    tap_idx = jnp.asarray(tap_nodes, jnp.int32) if tap_nodes else None
    stream = None
    if sample is not None:
        s_cert = metrics_lib.first_certificate(recorder)
        parts = schedule_lib.participation_parts(
            part.num_nodes, sample, dtype=dtype, run_seed=seed,
            cert=s_cert if (s_cert is not None and s_cert.dynamic) else None,
            leave_reset=(leave_mode == "reset"))
        if atk_part is not None:
            parts = parts + (atk_part,)
        prog = schedule_lib.ScheduleProgram(parts=parts)
        if sample.stream:
            # the no-churn broadcast w/active legs give way to the streamed
            # generator entries, merged inside the scan body each round
            del sched["w"], sched["active"]
            stream = prog.stream_fn()
        else:
            # escape hatch for the bitwise pins: the SAME jax generator,
            # evaluated host-side into classical stacked schedules
            sched.update(prog.materialize(rounds))
    has_budget = "budgets" in sched
    has_reset = ("leavers" in sched
                 or (stream is not None and leave_mode == "reset"))
    quantized = quant.is_quantized(cfg.wire)
    if quantized:
        # per-round codec keys ride the schedule like every other input;
        # the extra row feeds the pipelined body's encode of round t+1
        keys = np.asarray(quant.round_keys(seed, rounds + 1))
        sched["qkey"] = keys[:rounds]
        if cfg.pipeline:
            sched["qkey_next"] = keys[1:]
        state = _arm_wire_state(state, cfg, keys[0])
    obs_upd = obs_inc = None
    if cfg.telemetry:
        from repro.obs import counters as obs_counters
        obs_inc = obs_counters.round_increments(graph, problem.d, cfg,
                                                dtype.itemsize)
        obs_upd = obs_counters.make_update(cfg, part.num_nodes, obs_inc)
        state = state._replace(
            counters=obs_counters.init_counters(part.num_nodes))
    body = _round_body(problem, part, cfg)

    def step_fn(st, env_ctx, s_t):
        if has_reset:
            # cond matches the loop driver's host-side `leavers.any()` gate,
            # so rounds without leavers execute the identical program
            st = lax.cond(
                s_t["reset_any"],
                lambda ss: _reset_leavers(ss, env_ctx, part, s_t["leavers"]),
                lambda ss: ss, st)
        atk = {n: s_t["atk_" + n] for n in atk_names} or None
        tap = None
        if tap_idx is not None:
            # what the tapped nodes emit THIS round (post-reset state, same
            # wire transform the mix consumes — XLA shares the computation)
            tap = _apply_payload_attack(st.v_stack, atk)[tap_idx]
        st_pre = st
        st = body(st, env_ctx, s_t["w"], s_t["active"],
                  s_t["budgets"] if has_budget else None, atk,
                  s_t["qkey"] if quantized else None,
                  s_t["qkey_next"] if quantized and cfg.pipeline else None)
        if obs_upd is None:
            return st, tap
        # the round body rebuilds the state pytree, so reattach the
        # updated counters — they stay leaves of the scan carry
        cts, obs_row = obs_upd(st_pre, st, s_t, atk, s_t["w"])
        st = st._replace(counters=cts)
        aux = {"obs": obs_row}
        if tap is not None:
            aux["taps"] = tap
        return st, aux

    cad = metrics_lib.as_cadence(record_every)
    rec = (None if cad
           else exec_engine.record_flags(rounds, record_every))
    cert = metrics_lib.first_certificate(recorder)
    if cert is not None and cert.dynamic and sample is None:
        # dynamic certificate: the per-round neighbor mask + threshold ride
        # the schedule like every other per-round input. Under an adaptive
        # cadence any round may record, so materialize every round's entry.
        # (attack-aware recorders also use the schedule, but their entry —
        # atk_dishonest — was materialized by apply_attacks already; a
        # participation run's entries come from its own streamed generator.)
        sched.update(metrics_lib.certificate_schedule(
            recorder, sched["w"], sched["active"],
            np.ones((rounds,), dtype=bool) if cad else rec))
    with contextlib.ExitStack() as stack:
        run_tr = None
        if cfg.telemetry:
            # scope a fresh tracer (+ its cache listener) to this run so the
            # report's span timings cover exactly these block dispatches
            from repro.obs import trace as obs_trace
            run_tr = stack.enter_context(obs_trace.use(obs_trace.Tracer()))
            stack.enter_context(run_tr.attach())
        res = exec_engine.run_round_blocks(
            step_fn, state, sched, context=env, recorder=recorder,
            record_mask=rec, block_size=block_size, cadence=cad,
            num_rounds=rounds, stream=stream,
            cache_key=("cola-block", exec_engine.fingerprint(problem), part,
                       cfg, has_budget, has_reset, recorder.cache_token(),
                       atk_info.token if atk_info else None))
    history = metrics_lib.history_from(recorder, res)
    taps = res.aux if tap_nodes else None
    if cfg.telemetry:
        from repro.obs import counters as obs_counters, report as obs_report
        obs_series = res.aux.get("obs") if isinstance(res.aux, dict) else None
        taps = res.aux.get("taps") if isinstance(res.aux, dict) else None
        history["telemetry"] = obs_counters.summarize(
            res.state.counters, obs_inc, series=obs_series,
            stop_round=res.stop_round,
            dishonest=sched.get("atk_dishonest"))
        obs_report.auto_emit(obs_report.make_report(
            driver="run_cola",
            problem_fp=exec_engine.fingerprint(problem),
            config=dataclasses.asdict(cfg),
            graph={"kind": getattr(graph, "name", type(graph).__name__),
                   "num_nodes": part.num_nodes},
            rounds=(rounds if res.stop_round is None
                    else res.stop_round + 1),
            history=history,
            contract=obs_inc["contract"],
            spans=run_tr.summary() if run_tr is not None else None))
    return RunResult(state=res.state, history=history, taps=taps)


def _run_cola_cohort(problem, graph, cfg, rounds, *, part, record_every,
                     recorder, eps, budget_schedule, leave_mode, seed,
                     w_override, attacks, block_size) -> RunResult:
    """Million-node client-sampling driver: each round only the sampled
    K'-node cohort computes.

    Nothing (K, K)- or (T, K)-shaped exists anywhere. The streamed schedule
    carries the sorted cohort indices (K',) and the active mask (K,); the
    round body gathers the cohort's (K', ...) state/env slices, applies the
    sampled-complete gossip mix in closed form (the induced Metropolis
    matrix over active nodes of a complete graph is the exact uniform
    average — see ``schedule.sampled_complete_weights``), runs the vmapped
    local CD solve on the slices, and scatters the updates back. Frozen
    nodes are untouched, exactly the dense participation semantics, so the
    two modes agree to reduction order at small K.

    The certificate stays sound on the sampled subnetwork via the cohort
    mode of ``metrics.CertificateRecorder`` (beta = 0 closed form over the
    complete induced subgraph; cond9 judged over ALL K nodes — frozen nodes
    must hold their thresholds too, matching the materialized-churn oracle).
    """
    sample = cfg.participation
    k = part.num_nodes
    for flag, what in (
            (attacks is not None, "attacks="),
            (budget_schedule is not None, "budget_schedule="),
            (leave_mode != "freeze", f"leave_mode={leave_mode!r}"),
            (w_override is not None, "w_override="),
            (cfg.telemetry, "cfg.telemetry"),
            (cfg.robust is not None, "cfg.robust"),
            (quant.is_quantized(cfg.wire), f"wire={cfg.wire!r}"),
            (cfg.grad_mode != "local", f"grad_mode={cfg.grad_mode!r}"),
            (cfg.gossip_steps != 1, "gossip_steps != 1"),
    ):
        if flag:
            raise NotImplementedError(
                f"{what} is not supported in cohort participation mode — "
                "the gather/scatter round body implements the bare "
                "Algorithm-1 round over the sampled cohort (dense "
                f"participation mode, K <= {schedule_lib.DENSE_MAX_NODES}, "
                "supports these compositions)")
    env = build_env(problem, part,
                    with_gram=cfg.use_gram(problem.d, part.block,
                                           problem.a.dtype.itemsize))
    state = init_state(problem, part)
    if isinstance(recorder, str):
        # make_recorder wants a dense graph/W for the certificate — the
        # cohort form derives its thresholds without either
        if recorder not in ("gap", "certificate", "gap+certificate"):
            raise ValueError(f"unknown recorder {recorder!r} (want 'gap', "
                             "'certificate', 'gap+certificate' or a "
                             "Recorder instance)")
        recs = []
        if recorder in ("gap", "gap+certificate"):
            recs.append(metrics_lib.GapRecorder(
                problem, part, eps=eps if recorder == "gap" else None))
        if recorder in ("certificate", "gap+certificate"):
            if eps is None:
                raise ValueError(
                    f"recorder={recorder!r} needs eps=: the Prop.-1 "
                    "conditions certify a specific accuracy")
            recs.append(metrics_lib.cohort_certificate_recorder(
                problem, part, env, eps))
        rec = (recs[0] if len(recs) == 1
               else metrics_lib.ComposedRecorder(tuple(recs)))
    else:
        rec = recorder

    dtype = problem.a.dtype
    sigma = cfg.resolved_sigma(k)
    spec = SubproblemSpec(sigma_over_tau=sigma / problem.tau, inv_k=1.0 / k)
    gamma = cfg.gamma
    steps = cfg.coord_steps(part.block)
    use_gram = (env.gram_parts is not None
                and cfg.use_gram(problem.d, part.block,
                                 env.a_parts.dtype.itemsize))
    if cfg.cd_mode == "gram" and env.gram_parts is None:
        raise ValueError(
            "cd_mode='gram' but the env has no Gram blocks — build it "
            "with build_env(problem, part, with_gram=True)")

    def step_fn(st, env_ctx, s_t):
        idx = s_t["cohort_idx"]                      # (K',) sorted
        v_sub = st.v_stack[idx]                      # (K', d)
        a_sub = env_ctx.a_parts[idx]                 # (K', d, n_k)
        # Step 4 over the sampled complete subnetwork: the mix is the exact
        # uniform cohort average (rank-one W), inactive nodes untouched
        v_half = jnp.broadcast_to(jnp.mean(v_sub, axis=0, keepdims=True),
                                  v_sub.shape)
        grads = jax.vmap(problem.grad_f)(v_half)
        dx = cd_solve_all(problem, spec, a_sub, st.x_parts[idx], grads,
                          env_ctx.gp_parts[idx], env_ctx.masks[idx], steps,
                          step_budgets=None,
                          gram_parts=env_ctx.gram_parts[idx] if use_gram
                          else None)
        # Steps 6-8 scattered back: frozen nodes keep x and v verbatim
        dv = jnp.einsum("kdn,kn->kd", a_sub, dx, precision=MATMUL)
        x_new = st.x_parts.at[idx].add(gamma * dx)
        v_new = st.v_stack.at[idx].set(v_half + gamma * k * dv)
        return ColaState(x_parts=x_new, v_stack=v_new), None

    prog = schedule_lib.ScheduleProgram(
        parts=schedule_lib.cohort_parts(k, sample, dtype=dtype,
                                        run_seed=seed))
    if sample.stream:
        sched, stream = {}, prog.stream_fn()
    else:
        sched, stream = prog.materialize(rounds), None
    cad = metrics_lib.as_cadence(record_every)
    rec_mask = (None if cad
                else exec_engine.record_flags(rounds, record_every))
    res = exec_engine.run_round_blocks(
        step_fn, state, sched, context=env, recorder=rec,
        record_mask=rec_mask, block_size=block_size, cadence=cad,
        num_rounds=rounds, stream=stream,
        cache_key=("cola-cohort", exec_engine.fingerprint(problem), part,
                   cfg, rec.cache_token()))
    return RunResult(state=res.state,
                     history=metrics_lib.history_from(rec, res))


def _reset_leavers(state: ColaState, env: ColaEnv, part: Partition,
                   leavers: np.ndarray,
                   total_fn: Callable | None = None) -> ColaState:
    """Fig.-6 model: zero x_[k] of leaving nodes; every node subtracts
    A_[k] x_[k] from its local estimate so (1/K) sum v_k = A x still holds.

    ``total_fn(contrib) -> (d,)`` reduces the per-node contributions over
    ALL K nodes; the default sums the stacked axis, the shard_map runtime
    passes a psum-augmented reduction so the one invariant implementation
    serves both drivers.
    """
    leave = jnp.asarray(leavers)
    contrib = jnp.einsum("kdn,kn->kd", env.a_parts,
                         state.x_parts * leave[:, None],
                         precision=MATMUL)                # (K, d)
    if total_fn is None:
        total_fn = lambda c: jnp.sum(c, axis=0)           # A_[k] x_[k] summed
    total = total_fn(contrib)
    x_new = jnp.where(leave[:, None], 0.0, state.x_parts)
    v_new = state.v_stack - total[None, :]
    # a leaver's codec residual describes payload history that no longer
    # exists — zero it with the rest of its local state (pipeline + reset
    # is rejected up front, so state.buf is always None here)
    ef_new = (None if state.ef is None
              else jnp.where(leave[:, None], 0.0, state.ef))
    return ColaState(x_parts=x_new, v_stack=v_new, ef=ef_new, buf=state.buf,
                     counters=state.counters)


def solve_reference(problem: Problem, rounds: int = 3000,
                    kappa: int = 10) -> float:
    """High-accuracy reference optimum via single-node CoCoA (used as F* when
    reporting suboptimality, mirroring the paper's methodology in App. D)."""
    graph = topo.complete(2)
    cfg = ColaConfig(kappa=kappa)
    res = run_cola(problem, graph, cfg, rounds, record_every=max(rounds // 4, 1),
                   w_override=cocoa_mixing(2))
    return min(res.history["primal"])
