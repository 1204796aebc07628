"""Pluggable recording/control layer for the round-block executor.

A ``Recorder`` bundles the three things a driver previously wired by hand
(three divergent copies across ``cola.py``, ``baselines.py`` and
``dist/runtime.py``): what to measure each record round, what the columns are
called, and when the run may stop early. The round-block executor
(``repro.core.executor``) consumes a Recorder directly — the row is computed
on device inside the scan, and when the recorder's stop condition fires the
remaining rounds of the block become no-ops and subsequent block dispatches
are skipped host-side.

The protocol (duck-typed, no base class required):

  labels      tuple[str, ...] — column names; drives history dict keys.
  record_fn   state -> (len(labels),) row, pure jax (runs inside the scan).
  stop_fn     None (never stop) or row -> scalar bool; evaluated only on
              record rounds, so ``record_every`` is also the certification
              cadence.
  init_spec() pytree of per-run constant arrays the recorder derives at build
              time (e.g. the sigma_k spectral-norm cache); the distributed
              runtime shards these over the node mesh axis via
              ``repro.dist.sharding.cola_recorder_pspecs``.
  cache_token()  small hashable-by-``executor.fingerprint`` summary of the
              recorder's semantics for compiled-driver cache keys (the big
              arrays are determined by (problem, partition), which drivers
              fingerprint separately).
  collective_footprint(...)  bytes-per-record-round by collective kind on a
              K-device mesh — what ``launch.dryrun --plan`` renders.

Three implementations ship:

* ``GapRecorder`` — the Lemma-2 ``gap_report`` (primal/dual/gap/consensus),
  numerics unchanged from the historical drivers. On a mesh this gathers the
  full (K, d)/(K, n_k) stacks per record round (GSPMD inserts the
  collectives); with ``eps`` it stops when ``gap <= eps``.
* ``CertificateRecorder`` — the Prop.-1 local certificates: condition (9)
  from node-local quantities, condition (10) from the masked-neighbor
  gradient mean (one gossip exchange of (d,)-vectors), summarized to scalar
  reductions. The distributed runtime evaluates it with ``ppermute``/``psum``
  of the LOCAL gradient — O(d) per device per record round, no stack
  gathers. Stops at certification.
* ``ComposedRecorder`` — concatenates several recorders' rows; stops when
  any constituent's stop fires.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topo
from repro.core.duality import (block_spectral_norms, certificate_thresholds,
                                consensus_residual, gap_report, neighbor_mask,
                                neighborhood_mean, node_subproblem_gaps)
from repro.core.partition import Partition
from repro.core.precision import MATMUL

GAP_METRICS = ("primal", "hamiltonian", "dual", "gap", "consensus_violation")
# append-only: downstream code indexes the first five by name, and new
# columns extend the row (consensus_residual = the Lemma-1 invariant
# residual, certificate_violated = the tamper-detection flag; see
# ``duality.consensus_residual``)
CERT_METRICS = ("local_gap_max", "grad_disagreement_max", "cond9_nodes",
                "cond10_nodes", "certified", "consensus_residual",
                "certificate_violated")


@dataclasses.dataclass(frozen=True)
class AdaptiveCadence:
    """On-device ``record_every`` controller: geometric back-off.

    Stopping is only checked on record rounds, so the recording cadence is
    also the certification latency — but far from ``eps`` every record
    round is wasted work. This controller doubles the cadence after each
    record round whose distance ratio (``recorder.cadence_ratio(row)``,
    ~"how many multiples of the stop threshold away we are") is still above
    ``near``, and snaps back to ``base`` the moment a row lands inside the
    ``near`` band — so certification is detected within ``base`` rounds of
    becoming true while the far-from-converged phase records only
    O(log T) rows.

    The decision runs on device inside the round-block scan (the next
    record round and current cadence ride the scan carry), so the
    executor's block short-circuiting and the single end-of-run metric
    fetch are unchanged. ``grow`` is an integer so the host loop driver
    reproduces the device arithmetic exactly.
    """

    base: int = 1        # cadence inside the near band (certification latency)
    max_every: int = 64  # back-off cap
    grow: int = 2        # geometric factor per far record round
    near: float = 2.0    # "near" band: ratio <= near tightens to base

    def __post_init__(self):
        if self.base < 1 or self.grow < 2 or self.max_every < self.base:
            raise ValueError(
                f"need base >= 1, grow >= 2, max_every >= base; got {self}")

    def cache_token(self):
        return ("AdaptiveCadence", self.base, self.max_every, self.grow,
                self.near)


def as_cadence(record_every) -> AdaptiveCadence | None:
    """Resolve a driver's ``record_every`` argument: an int keeps the fixed
    host-side mask, ``"adaptive"`` / an ``AdaptiveCadence`` instance arms
    the on-device controller."""
    if isinstance(record_every, AdaptiveCadence):
        return record_every
    if record_every == "adaptive":
        return AdaptiveCadence()
    return None


@dataclasses.dataclass(frozen=True)
class GapRecorder:
    """Lemma-2 global diagnostics (the historical ``gap_report`` row).

    ``record_fn`` is byte-for-byte the computation the drivers inlined before
    the recorder layer existed, so metric histories reproduce exactly.
    """

    problem: Any
    part: Partition
    eps: float | None = None

    labels = GAP_METRICS

    def record_fn(self, state) -> jax.Array:
        rep = gap_report(self.problem, self.part, state.x_parts,
                         state.v_stack)
        return jnp.stack([getattr(rep, name) for name in self.labels])

    @property
    def stop_fn(self) -> Callable | None:
        if self.eps is None:
            return None
        eps, idx = self.eps, self.labels.index("gap")
        return lambda row: row[idx] <= eps

    def cadence_ratio(self, row) -> jax.Array:
        """Distance-to-stop ratio for ``AdaptiveCadence``: gap / eps."""
        if self.eps is None:
            raise ValueError("adaptive record cadence needs eps= on the gap "
                             "recorder (the ratio is gap / eps)")
        return row[self.labels.index("gap")] / self.eps

    def init_spec(self) -> dict:
        return {}

    def cache_token(self):
        return ("GapRecorder", self.eps)

    def collective_footprint(self, k: int, d: int, n_k: int,
                             itemsize: int = 4, comm: str = "dense",
                             conn: int = 1) -> dict:
        # merge_vector + grad stack: every device materializes the full
        # (K, n_k) and (K, d) stacks, plus scalar reductions for the row
        return {"all-gather": k * (d + n_k) * itemsize,
                "all-reduce": 2 * len(self.labels) * itemsize,
                "collective-permute": 0}


@dataclasses.dataclass(frozen=True)
class CertificateRecorder:
    """Prop.-1 local certificates as an on-device metric row.

    All round-invariant inputs (sigma_k via ``block_spectral_norms``, the
    Eq.-9/10 thresholds, the self-inclusive neighbor mask) are resolved at
    construction — see ``certificate_recorder`` — so a record round costs one
    gradient evaluation, one neighborhood exchange of (d,)-vectors and scalar
    reductions. ``stop_fn`` fires at certification (``certified == 1``).
    """

    problem: Any
    part: Partition
    a_parts: jax.Array      # (K, d, n_k) — condition (9) needs A_[k]
    gp_parts: jax.Array     # (K, n_k)
    masks: jax.Array        # (K, n_k)
    # (K, K) 0/1 self-inclusive neighbor mask; None in cohort mode, where a
    # dense mask would be O(K^2) at million-node populations and the
    # neighborhood structure is the closed-form sampled-complete one
    neigh_mask: jax.Array | None
    sigma_k: jax.Array      # (K,) spectral-norm cache
    eps: float
    beta_ub: float
    l_bound: float
    gap_thresh: float
    grad_thresh: float
    stop_on_certified: bool = True
    # churn mode: read the Eq.-10 neighborhood mask and threshold from the
    # per-round schedule (support of the REWEIGHTED W_t, beta of the active
    # subnetwork) instead of the static init-time constants — the static
    # graph's denser mixing would otherwise yield a threshold looser than
    # the churn round's actual exchange justifies. See ``dynamize`` /
    # ``certificate_schedule``.
    dynamic: bool = False
    # Lemma-1 tamper detection (``duality.consensus_residual``): certifying
    # additionally requires the relative invariant residual <= cons_tol
    # (Prop. 1's proof rests on (1/K) sum v_k = A x — an attacked run that
    # satisfies Eqs. 9-10 at a SHIFTED fixed point must not certify), and
    # residual > viol_tol (or non-finite state) raises the
    # ``certificate_violated`` flag. Honest linear runs sit at float noise
    # (~1e-6); robust nonlinear aggregation drifts the invariant by the
    # neighborhood spread, which decays toward consensus — hence a band,
    # not an exact-zero check. An undefended Byzantine payload moves the
    # mean by O(||v||) per round, far above viol_tol.
    cons_tol: float = 1e-2
    viol_tol: float = 0.1
    stop_on_violation: bool = False
    # attack-harness mode (``attackify``): audit the HONEST COHORT. A node
    # that lies on the wire cannot have its data used by any sound
    # aggregator — the achievable target is the honest sub-network's
    # problem, so certifying the full-network invariant under a working
    # defense is impossible by construction. The recorder instead reads the
    # ground-truth per-round dishonesty mask the attack schedule recorded
    # (``sched["atk_dishonest"]`` — experimenter knowledge, never visible
    # to the defense) and restricts every certificate input to honest
    # nodes: the Lemma-1 sums, the Eq.-9/10 conditions and the Eq.-10
    # neighborhood mean. Under the trim defense (drop + weight-to-self)
    # with a symmetric W the restricted mixing is column-stochastic, so the
    # cohort invariant sum_H v_k = K * A_H x_H holds EXACTLY whenever the
    # gate rejects every lie — the defended run certifies at float noise,
    # while an undefended run absorbs the lies into honest states and
    # trips ``certificate_violated``.
    attack_aware: bool = False
    # client-sampling mode (million-node populations, see
    # ``core.schedule.SampleConfig``): the Eq.-10 neighborhood is the
    # sampled COMPLETE subnetwork — its mixing matrix is the exact uniform
    # average, so the neighborhood mean is one cohort-mean broadcast (no
    # (K, K) mask anywhere) and the dynamic threshold collapses to the
    # beta=0 run constant baked into ``grad_thresh`` at build time. The
    # schedule supplies ``cohort_idx`` (K',) and ``active`` (K,); frozen
    # nodes keep their own gradient as the neighborhood mean (disagreement
    # exactly 0), matching the churn oracle's isolated-node semantics.
    cohort: bool = False

    labels = CERT_METRICS

    @property
    def uses_schedule(self) -> bool:
        return self.dynamic or self.attack_aware or self.cohort

    def local_row_inputs(self, x_parts, v_stack, grads, neigh_mean):
        """(local_gap, disagreement) per node — shared by the stacked
        simulator path and the shard_map distributed path (which feeds the
        per-device slices plus a ppermute-built ``neigh_mean``)."""
        local_gap = node_subproblem_gaps(self.problem, x_parts, v_stack,
                                         self.a_parts, self.gp_parts,
                                         self.masks, grads)
        disagree = jnp.linalg.norm(grads - neigh_mean, axis=1)
        return local_gap, disagree

    def summarize(self, local_gap, disagree, *, resid, psum=None, pmax=None,
                  grad_thresh=None, honest=None, dtype=jnp.float32
                  ) -> jax.Array:
        """Assemble the scalar row from per-node quantities.

        ``psum``/``pmax`` default to identity (single-program stacked state);
        the distributed runtime passes ``lax.psum``/``lax.pmax`` partials so
        the cross-device reductions are scalar collectives. ``grad_thresh``
        overrides the static Eq.-10 threshold (the dynamic churn path feeds
        the per-round value). ``resid`` is the already-reduced Lemma-1
        consensus residual (``duality.consensus_residual``) — certification
        requires it <= cons_tol; > viol_tol (or non-finite, the divergence
        signature) raises ``certificate_violated``. ``honest`` (attack-aware
        mode) is this program's node slice of the 0/1 honesty mask: the
        Eq.-9/10 conditions and maxima restrict to honest nodes, and
        certification requires all HONEST nodes to satisfy both.
        """
        psum = psum if psum is not None else (lambda x: x)
        pmax = pmax if pmax is not None else (lambda x: x)
        if grad_thresh is None:
            grad_thresh = self.grad_thresh
        cond9 = local_gap <= self.gap_thresh
        cond10 = disagree <= grad_thresh
        if honest is None:
            n_target = jnp.asarray(self.part.num_nodes, dtype)
        else:
            ok = honest > 0
            cond9, cond10 = cond9 & ok, cond10 & ok
            local_gap = jnp.where(ok, local_gap, 0.0)
            disagree = jnp.where(ok, disagree, 0.0)
            n_target = psum(jnp.sum(honest.astype(dtype)))
        n9 = psum(jnp.sum(cond9.astype(dtype)))
        n10 = psum(jnp.sum(cond10.astype(dtype)))
        n_both = psum(jnp.sum((cond9 & cond10).astype(dtype)))
        resid = resid.astype(dtype)
        certified = ((n_both == n_target)
                     & (resid <= self.cons_tol)).astype(dtype)
        violated = ((resid > self.viol_tol)
                    | ~jnp.isfinite(resid)).astype(dtype)
        return jnp.stack([pmax(jnp.max(local_gap)).astype(dtype),
                          pmax(jnp.max(disagree)).astype(dtype),
                          n9, n10, certified, resid, violated])

    def invariant_sums(self, x_parts, v_stack, a_parts,
                       honest=None) -> jax.Array:
        """(2, d) stacked [sum_k v_k, sum_k A_[k] x_[k]] — the Lemma-1
        residual's inputs. Stacked so the distributed path completes BOTH
        partials with ONE vector psum (O(d), no stack gathers). ``honest``
        (attack-aware mode) restricts both sums to the honest cohort, whose
        invariant sum_H v = K * A_H x_H is what a working defense
        preserves (the full-network one is unpreservable: a rejected liar's
        data never reaches the cohort)."""
        if honest is None:
            v_sum = jnp.sum(v_stack, axis=0)
            ax_sum = jnp.einsum("kdn,kn->d", a_parts, x_parts,
                                precision=MATMUL)
        else:
            h = honest.astype(v_stack.dtype)
            v_sum = jnp.sum(h[:, None] * v_stack, axis=0)
            ax_sum = jnp.einsum("kdn,kn,k->d", a_parts, x_parts, h,
                                precision=MATMUL)
        return jnp.stack([v_sum, ax_sum])

    def record_fn(self, state, sched=None) -> jax.Array:
        if self.cohort:
            return self._cohort_record(state, sched)
        grads = jax.vmap(self.problem.grad_f)(state.v_stack)   # (K, d)
        if self.dynamic:
            mask = sched["cert_mask"]
            grad_thresh = sched["cert_grad_thresh"]
        else:
            mask, grad_thresh = self.neigh_mask, self.grad_thresh
        hon = None
        if self.attack_aware:
            hon = (jnp.asarray(sched["atk_dishonest"])
                   <= 0).astype(state.v_stack.dtype)
            # dishonest nodes leave every neighborhood mean: their emitted
            # gradient information is exactly what the defense discards
            mask = jnp.asarray(mask) * hon[None, :]
        neigh_mean = neighborhood_mean(grads, mask)
        local_gap, disagree = self.local_row_inputs(
            state.x_parts, state.v_stack, grads, neigh_mean)
        sums = self.invariant_sums(state.x_parts, state.v_stack,
                                   self.a_parts, honest=hon)
        resid = consensus_residual(sums[0], sums[1], self.part.num_nodes)
        return self.summarize(local_gap, disagree, resid=resid,
                              grad_thresh=grad_thresh, honest=hon)

    def _cohort_record(self, state, sched) -> jax.Array:
        """Cohort-mode row: everything O(K * d) or O(K' * d) — the Eq.-9
        gaps and Lemma-1 sums run over the full population (frozen nodes
        must still satisfy condition 9, exactly as under churn), while the
        Eq.-10 neighborhood mean is the one cohort-mean broadcast."""
        idx = sched["cohort_idx"]                               # (K',)
        act = jnp.asarray(sched["active"]) > 0                  # (K,)
        grads = jax.vmap(self.problem.grad_f)(state.v_stack)    # (K, d)
        cohort_mean = jnp.mean(grads[idx], axis=0)              # (d,)
        neigh_mean = jnp.where(act[:, None], cohort_mean[None, :], grads)
        local_gap, disagree = self.local_row_inputs(
            state.x_parts, state.v_stack, grads, neigh_mean)
        sums = self.invariant_sums(state.x_parts, state.v_stack,
                                   self.a_parts)
        resid = consensus_residual(sums[0], sums[1], self.part.num_nodes)
        return self.summarize(local_gap, disagree, resid=resid)

    @property
    def stop_fn(self) -> Callable | None:
        idx_c = self.labels.index("certified")
        idx_v = self.labels.index("certificate_violated")
        if self.stop_on_certified and self.stop_on_violation:
            return lambda row: (row[idx_c] > 0) | (row[idx_v] > 0)
        if self.stop_on_certified:
            return lambda row: row[idx_c] > 0
        if self.stop_on_violation:
            return lambda row: row[idx_v] > 0
        return None

    def cadence_ratio(self, row) -> jax.Array:
        """Distance-to-certification for ``AdaptiveCadence``: the worse of
        the two condition margins. Uses the static init-time thresholds even
        in dynamic (churn) mode — cadence is a scheduling heuristic, never a
        soundness input (certification itself always uses the round's true
        thresholds)."""
        gap_r = row[self.labels.index("local_gap_max")] / self.gap_thresh
        dis_r = (row[self.labels.index("grad_disagreement_max")]
                 / self.grad_thresh)
        return jnp.maximum(gap_r, dis_r)

    def init_spec(self) -> dict:
        if self.neigh_mask is None:
            return {"sigma_k": self.sigma_k}
        return {"sigma_k": self.sigma_k, "neigh_mask": self.neigh_mask}

    def cache_token(self):
        return ("CertificateRecorder", self.eps, self.beta_ub, self.l_bound,
                self.gap_thresh, self.grad_thresh, self.stop_on_certified,
                self.dynamic, self.cons_tol, self.viol_tol,
                self.stop_on_violation, self.attack_aware, self.cohort,
                None if self.neigh_mask is None
                else np.asarray(self.neigh_mask).tobytes())

    def collective_footprint(self, k: int, d: int, n_k: int,
                             itemsize: int = 4, comm: str = "dense",
                             conn: int = 1) -> dict:
        # scalar psums + the ONE stacked (2, d) Lemma-1 invariant psum
        # (``invariant_sums``) — still O(d) per device per record round
        scalars = (2 * len(self.labels) + 3) * itemsize + 2 * d * itemsize
        if comm == "ring":
            # 2*conn ppermute pushes of one (d,) gradient + scalar psums
            return {"all-gather": 0, "all-reduce": scalars,
                    "collective-permute": 2 * conn * d * itemsize}
        # dense fallback mirrors the round body's own gossip gather
        return {"all-gather": k * d * itemsize, "all-reduce": scalars,
                "collective-permute": 0}


@dataclasses.dataclass(frozen=True)
class ComposedRecorder:
    """Concatenate several recorders into one row; stop when ANY constituent
    recorder's stop condition fires. Labels must be pairwise disjoint."""

    parts: tuple

    def __post_init__(self):
        labels = self.labels
        if len(set(labels)) != len(labels):
            raise ValueError(f"composed recorder labels collide: {labels}")

    @property
    def labels(self):
        return tuple(lbl for p in self.parts for lbl in p.labels)

    @property
    def uses_schedule(self) -> bool:
        return any(getattr(p, "uses_schedule", False) for p in self.parts)

    def record_fn(self, state, sched=None) -> jax.Array:
        return jnp.concatenate([
            p.record_fn(state, sched)
            if getattr(p, "uses_schedule", False) else p.record_fn(state)
            for p in self.parts])

    @property
    def stop_fn(self) -> Callable | None:
        stops = []
        off = 0
        for p in self.parts:
            if p.stop_fn is not None:
                stops.append((off, off + len(p.labels), p.stop_fn))
            off += len(p.labels)
        if not stops:
            return None

        def stop(row):
            flags = [fn(row[a:b]) for a, b, fn in stops]
            out = flags[0]
            for f in flags[1:]:
                out = jnp.logical_or(out, f)
            return out

        return stop

    def cadence_ratio(self, row) -> jax.Array:
        """Min over constituent ratios: the recorder CLOSEST to stopping
        drives the cadence (any near part must tighten the whole row's
        cadence, since a single row serves every part)."""
        ratios = []
        off = 0
        for p in self.parts:
            if hasattr(p, "cadence_ratio"):
                try:
                    ratios.append(p.cadence_ratio(row[off:off + len(p.labels)]))
                except ValueError:  # e.g. gap part without eps: no opinion
                    pass
            off += len(p.labels)
        if not ratios:
            raise ValueError("adaptive cadence needs at least one part with "
                             "a cadence_ratio (gap-with-eps or certificate)")
        out = ratios[0]
        for r in ratios[1:]:
            out = jnp.minimum(out, r)
        return out

    def init_spec(self) -> dict:
        return {f"part{i}": p.init_spec() for i, p in enumerate(self.parts)}

    def cache_token(self):
        return ("ComposedRecorder",) + tuple(p.cache_token()
                                             for p in self.parts)

    def collective_footprint(self, k, d, n_k, itemsize=4, comm="dense",
                             conn=1) -> dict:
        out: dict = {}
        for p in self.parts:
            for kind, b in p.collective_footprint(
                    k, d, n_k, itemsize, comm, conn).items():
                out[kind] = out.get(kind, 0) + b
        return out


@dataclasses.dataclass(frozen=True)
class FnRecorder:
    """Ad-hoc recorder from a bare row function (the baselines' objective /
    consensus row, test probes). ``stop`` is an optional row -> bool."""

    labels: tuple
    fn: Callable
    stop: Callable | None = None

    def record_fn(self, state) -> jax.Array:
        return self.fn(state)

    @property
    def stop_fn(self) -> Callable | None:
        return self.stop

    def init_spec(self) -> dict:
        return {}

    def cache_token(self):
        # functions fingerprint by bytecode + closure via executor.fingerprint
        return ("FnRecorder", self.labels, self.fn, self.stop)

    def collective_footprint(self, k, d, n_k, itemsize=4, comm="dense",
                             conn=1) -> dict:
        return {"all-gather": 0, "all-reduce": 0, "collective-permute": 0}


def certificate_recorder(problem, part: Partition, env, neighbors,
                         eps: float, *, w=None,
                         sigma_k: jax.Array | None = None,
                         stop_on_certified: bool = True,
                         cons_tol: float = 1e-2, viol_tol: float = 0.1,
                         stop_on_violation: bool = False
                         ) -> CertificateRecorder:
    """Build a ``CertificateRecorder``, resolving every round-invariant input.

    Args:
      env: the ``ColaEnv`` (supplies a_parts / gp_parts / masks).
      neighbors: adjacency (or mixing matrix) whose support defines N_k.
      w: the mixing matrix used for the contraction bound beta; defaults to
        Metropolis weights over ``neighbors`` when it is a Topology.
      sigma_k: optional precomputed ``block_spectral_norms`` cache.
    """
    if isinstance(neighbors, topo.Topology):
        graph = neighbors
        neighbors = graph.adjacency
        if w is None:
            w = topo.metropolis_weights(graph)
    if w is None:
        w = np.asarray(neighbors, dtype=np.float64)
    l_bound = float(problem.l_bound)
    if not math.isfinite(l_bound):
        raise ValueError(
            f"problem {problem.name!r} has unbounded g_i support "
            "(l_bound=inf): Prop. 1 needs an L-bounded problem "
            "(lasso / box-constrained) — use the gap recorder instead")
    k = part.num_nodes
    sigma_k = block_spectral_norms(env.a_parts, cache=sigma_k)
    beta_ub = float(topo.beta(np.asarray(w)))
    mask = neighbor_mask(neighbors, k, dtype=env.a_parts.dtype)
    gap_thresh, grad_thresh = certificate_thresholds(
        env.masks, sigma_k, beta_ub, l_bound, eps, k)
    return CertificateRecorder(
        problem=problem, part=part, a_parts=env.a_parts,
        gp_parts=env.gp_parts, masks=env.masks, neigh_mask=mask,
        sigma_k=sigma_k, eps=float(eps), beta_ub=beta_ub, l_bound=l_bound,
        gap_thresh=float(gap_thresh), grad_thresh=float(grad_thresh),
        stop_on_certified=stop_on_certified, cons_tol=cons_tol,
        viol_tol=viol_tol, stop_on_violation=stop_on_violation)


def cohort_certificate_recorder(problem, part: Partition, env,
                                eps: float, *,
                                stop_on_certified: bool = True,
                                cons_tol: float = 1e-2,
                                viol_tol: float = 0.1,
                                stop_on_violation: bool = False
                                ) -> CertificateRecorder:
    """Build the client-sampling certificate (``cohort=True``): Prop.-1
    over the sampled subnetwork of a COMPLETE base graph. No (K, K) array
    is ever built — the neighbor mask is structural (the cohort) and the
    thresholds derive with the sampled-complete contraction factor
    beta = 0 (the induced mixing matrix is a rank-one projector)."""
    l_bound = float(problem.l_bound)
    if not math.isfinite(l_bound):
        raise ValueError(
            f"problem {problem.name!r} has unbounded g_i support "
            "(l_bound=inf): Prop. 1 needs an L-bounded problem "
            "(lasso / box-constrained) — use the gap recorder instead")
    k = part.num_nodes
    sigma_k = block_spectral_norms(env.a_parts)
    gap_thresh, grad_thresh = certificate_thresholds(
        env.masks, sigma_k, 0.0, l_bound, eps, k)
    return CertificateRecorder(
        problem=problem, part=part, a_parts=env.a_parts,
        gp_parts=env.gp_parts, masks=env.masks, neigh_mask=None,
        sigma_k=sigma_k, eps=float(eps), beta_ub=0.0, l_bound=l_bound,
        gap_thresh=float(gap_thresh), grad_thresh=float(grad_thresh),
        stop_on_certified=stop_on_certified, cons_tol=cons_tol,
        viol_tol=viol_tol, stop_on_violation=stop_on_violation,
        cohort=True)


def dynamize(recorder):
    """Churn-aware variant: every certificate part reads its Eq.-10
    neighborhood mask and threshold from the per-round schedule (see
    ``certificate_schedule``) instead of the static init-time graph — the
    static graph's denser mixing would make the threshold unsoundly loose
    during rounds where nodes have dropped."""
    if isinstance(recorder, ComposedRecorder):
        return dataclasses.replace(recorder, parts=tuple(
            dynamize(p) for p in recorder.parts))
    if isinstance(recorder, CertificateRecorder):
        return dataclasses.replace(recorder, dynamic=True)
    return recorder


def attackify(recorder, cons_tol: float = 0.25, viol_tol: float = 0.5):
    """Attack-harness variant: every certificate part audits the honest
    cohort, reading the attack schedule's ground-truth per-round dishonesty
    mask (``atk_dishonest``) — see ``CertificateRecorder.attack_aware``.
    Drivers apply this when ``apply_attacks`` reports payload-corrupting
    scenarios; a clean run's recorder is untouched.

    The default tolerances widen: when the attack begins at round S > 0,
    the cohort invariant inherits the boundary offset
    ``C = sum_L (K a_L x_L(S) - v_L(S))`` — the pre-onset entanglement of
    the liars' contributions with the honest states. A sound defense keeps
    C CONSTANT (the residual plateaus at ||C||-scale, ~0.1 for onsets in
    the first tenth of training, exactly 0 for round-0 onsets), while an
    undefended run absorbs new lie mass every round and the residual
    accumulates toward ~1. The (0.25, 0.5) band separates those regimes;
    the raw residual stays in the history for inspection."""
    if isinstance(recorder, ComposedRecorder):
        return dataclasses.replace(recorder, parts=tuple(
            attackify(p, cons_tol, viol_tol) for p in recorder.parts))
    if isinstance(recorder, CertificateRecorder):
        return dataclasses.replace(recorder, attack_aware=True,
                                   cons_tol=max(recorder.cons_tol, cons_tol),
                                   viol_tol=max(recorder.viol_tol, viol_tol))
    return recorder


def first_certificate(recorder) -> CertificateRecorder | None:
    if isinstance(recorder, CertificateRecorder):
        return recorder
    if isinstance(recorder, ComposedRecorder):
        for p in recorder.parts:
            found = first_certificate(p)
            if found is not None:
                return found
    inner = getattr(recorder, "_inner", None)
    return first_certificate(inner) if inner is not None else None


def certificate_round_inputs(cert: CertificateRecorder, w_t, active
                             ) -> tuple[np.ndarray, float]:
    """(neighbor mask, Eq.-10 threshold) for ONE churn round: the mask is
    the support of the reweighted W_t (self-inclusive — dropped neighbors
    have W_kj = 0 and leave the neighborhood, as the real exchange would),
    and the threshold re-derives with beta of the ACTIVE subnetwork's
    mixing submatrix (frozen nodes are fixed points of W_t, whose trivial
    eigenvalue-1 blocks say nothing about the survivors' contraction)."""
    w_t = np.asarray(w_t, np.float64)
    k = w_t.shape[0]
    mask = (w_t != 0) | np.eye(k, dtype=bool)
    act = np.asarray(active) > 0
    beta_t = topo.beta(w_t[np.ix_(act, act)]) if act.sum() > 1 else 0.0
    n_sizes = np.sum(np.asarray(cert.masks), axis=1)
    scale = float(np.sum(n_sizes ** 2 * np.asarray(cert.sigma_k)))
    thresh = (scale ** -0.5) * (1.0 - beta_t) / (
        2.0 * cert.l_bound * np.sqrt(float(k))) * cert.eps
    return mask, float(thresh)


def certificate_schedule(recorder, w_stack, actives,
                         record_mask: np.ndarray) -> dict:
    """Materialize the dynamic certificate's per-round schedule entries:
    ``cert_mask`` (T, K, K) and ``cert_grad_thresh`` (T,), evaluated only
    for record rounds (other rounds' slices are never read under the
    ``lax.cond`` record flag)."""
    cert = first_certificate(recorder)
    t, k = np.shape(w_stack)[0], np.shape(w_stack)[1]
    dtype = np.asarray(w_stack[:1]).dtype if t else np.float32
    masks = np.zeros((t, k, k), dtype=dtype)
    thresh = np.zeros((t,), dtype=dtype)
    for t_i in np.nonzero(np.asarray(record_mask, dtype=bool))[0]:
        m, th = certificate_round_inputs(cert, w_stack[t_i], actives[t_i])
        masks[t_i] = m
        thresh[t_i] = th
    return {"cert_mask": masks, "cert_grad_thresh": thresh}


def make_recorder(kind, problem, part: Partition, env, graph,
                  w, eps: float | None):
    """Resolve a driver's ``recorder=`` argument ("gap", "certificate",
    "gap+certificate", or an already-built Recorder instance).

    ``eps`` arms early stopping: the gap recorder stops at ``gap <= eps``,
    the certificate recorder at Prop.-1 certification of ``eps``. In the
    composed form only the certificate drives the stop (the gap columns are
    recorded for reference).
    """
    if not isinstance(kind, str):
        return kind
    if kind == "gap":
        return GapRecorder(problem, part, eps=eps)
    if kind in ("certificate", "gap+certificate"):
        if eps is None:
            raise ValueError(
                f"recorder={kind!r} needs eps=: the Prop.-1 conditions "
                "certify a specific accuracy")
        cert = certificate_recorder(problem, part, env, graph.adjacency,
                                    eps, w=w)
        if kind == "certificate":
            return cert
        return ComposedRecorder((GapRecorder(problem, part, eps=None), cert))
    raise ValueError(f"unknown recorder {kind!r} (want 'gap', 'certificate', "
                     "'gap+certificate' or a Recorder instance)")


def annotate_violation(history: dict) -> dict:
    """Surface tamper detection in the history: ``violated_round`` is the
    first RECORDED round whose ``certificate_violated`` flag fired (None when
    the flag never fired; absent when the recorder has no certificate part).
    """
    if "certificate_violated" in history:
        history["violated_round"] = next(
            (r for r, v in zip(history["round"],
                               history["certificate_violated"]) if v > 0),
            None)
    return history


def history_from(recorder, result) -> dict:
    """Build the driver history dict from a ``BlockRunResult``: one list per
    recorder label, the recorded round indices (truncated at early stop) and
    the stop round (None when the run used its full budget)."""
    history: dict = {"round": [int(t) for t in result.rounds]}
    for j, name in enumerate(recorder.labels):
        history[name] = [float(v) for v in result.metrics[:, j]]
    history["stop_round"] = result.stop_round
    return annotate_violation(history)


def render_footprints(k: int, d: int, n_k: int, itemsize: int = 4) -> str:
    """Human-readable per-record-round collective footprint of the stock
    recorders on a K-device node mesh (the ``dryrun --plan`` section)."""
    dummy_part = Partition(num_nodes=k, n=k * n_k, block=n_k)
    gap = GapRecorder(problem=None, part=dummy_part)
    # footprint needs no arrays — build the certificate entry structurally
    lines = [f"[cola recorder footprint] K={k} d={d} n_k={n_k} "
             f"itemsize={itemsize} (bytes per device per record round)"]
    rows = [("gap (gather)", "dense",
             gap.collective_footprint(k, d, n_k, itemsize)),
            ("certificate", "dense",
             CertificateRecorder.collective_footprint(
                 _FootprintOnly(), k, d, n_k, itemsize, "dense")),
            ("certificate", "ring",
             CertificateRecorder.collective_footprint(
                 _FootprintOnly(), k, d, n_k, itemsize, "ring"))]
    for name, comm, fp in rows:
        body = "  ".join(f"{kind}={fp[kind]:,}" for kind in
                         ("all-gather", "collective-permute", "all-reduce"))
        lines.append(f"  {name:<16} comm={comm:<6} {body}")
    return "\n".join(lines)


class _FootprintOnly:
    """Stand-in self for ``CertificateRecorder.collective_footprint`` so the
    plan can be rendered without materializing problem arrays."""

    labels = CERT_METRICS
