"""Decentralized duality machinery — the numeric core of both recording paths.

Two families of diagnostics live here:

* the **global** quantities of Lemmas 1/2 — H_A / H_B objectives (Eq. DA/DB),
  the decentralized duality gap (Eq. 6) and the consensus violation — computed
  by ``gap_report`` from the full stacked state (the gather-everything path
  behind ``repro.core.metrics.GapRecorder``);
* the **local** Prop.-1 certificates (Eqs. 9-10) — per-node conditions whose
  conjunction certifies ``G_H <= eps`` from one gossip exchange of neighbor
  gradients only. ``local_certificates`` is built from the reusable pieces
  (``node_subproblem_gaps``, ``neighborhood_mean``, ``certificate_thresholds``)
  that ``repro.core.metrics.CertificateRecorder`` assembles on-device inside
  the round-block scan, and that ``repro.dist.runtime`` re-assembles from a
  ``ppermute``/``psum`` of the local gradient (O(d) per device per record
  round — no (K, d) stack gathers).

The Eq.-10 neighborhood mean uses a masked-neighbor formulation: each node
averages exactly the gradient VALUES a gossip exchange delivers (its own plus
its neighbors'), selected by the 0/1 support of the adjacency — or of the
round's mixing matrix, which under churn reweighting excludes dropped
neighbors the way a real exchange would.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import Partition
from repro.core.precision import MATMUL


class GapReport(NamedTuple):
    primal: jax.Array             # F_A(x)
    hamiltonian: jax.Array        # H_A(x, {v_k})
    dual: jax.Array               # -H_B({w_k}) with w_k = grad f(v_k)
    gap: jax.Array                # G_H (Eq. 6)
    consensus_violation: jax.Array  # sum_k ||v_k - Ax||^2


def hamiltonian(problem, x_global: jax.Array, v_stack: jax.Array) -> jax.Array:
    """H_A(x, {v_k}) = (1/K) sum_k f(v_k) + g(x)   (Eq. DA)."""
    f_vals = jax.vmap(problem.f)(v_stack)
    return jnp.mean(f_vals) + problem.g(x_global)


def gap_report(problem, part: Partition, x_parts: jax.Array,
               v_stack: jax.Array) -> GapReport:
    """All Lemma-1/2 quantities at the optimality choice w_k = grad f(v_k)."""
    x = part.merge_vector(x_parts)
    ax = jnp.matmul(problem.a, x, precision=MATMUL)
    w_stack = jax.vmap(problem.grad_f)(v_stack)          # (K, d)
    w_bar = jnp.mean(w_stack, axis=0)
    f_vals = jax.vmap(problem.f)(v_stack)
    fc_vals = jax.vmap(problem.f_conj)(w_stack)
    g_val = problem.g(x)
    atw = jnp.matmul(problem.a.T, w_bar, precision=MATMUL)
    gc_val = jnp.sum(problem.g_conj_el(-atw, problem.g_params()))
    h_a = jnp.mean(f_vals) + g_val
    h_b = jnp.mean(fc_vals) + gc_val
    gap = h_a + h_b
    cv = jnp.sum((v_stack - ax[None, :]) ** 2)
    return GapReport(primal=problem.f(ax) + g_val, hamiltonian=h_a,
                     dual=-h_b, gap=gap, consensus_violation=cv)


def block_spectral_norms(a_parts: jax.Array, iters: int = 50,
                         seed: int = 0,
                         cache: jax.Array | None = None) -> jax.Array:
    """sigma_k = ||A_[k]||_2^2 (Eq. 7) for every node, by power iteration.

    ``cache`` short-circuits the power iteration with a previously computed
    ``(K,)`` result — the sigma_k of a run are round-invariant, so recorders
    compute them ONCE at init and record rounds never re-run the iteration.
    """
    k = a_parts.shape[0]
    if cache is not None:
        cache = jnp.asarray(cache)
        if cache.shape != (k,):
            raise ValueError(f"sigma_k cache has shape {cache.shape}, "
                             f"want ({k},)")
        return cache
    return _power_iteration(a_parts, iters, seed)


# jitted whole, so that a recorder built in every solve compiles it once per
# shape: eager, its fori_loop over a fresh closure compiled on every call
@functools.partial(jax.jit, static_argnames=("iters", "seed"))
def _power_iteration(a_parts: jax.Array, iters: int, seed: int) -> jax.Array:
    k, d, n_k = a_parts.shape
    key = jax.random.PRNGKey(seed)
    v0 = jax.random.normal(key, (k, n_k), dtype=a_parts.dtype)

    def body(_, v):
        u = jnp.einsum("kdn,kn->kd", a_parts, v, precision=MATMUL)
        w = jnp.einsum("kdn,kd->kn", a_parts, u, precision=MATMUL)
        return w / (jnp.linalg.norm(w, axis=1, keepdims=True) + 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v0)
    u = jnp.einsum("kdn,kn->kd", a_parts, v, precision=MATMUL)
    num = jnp.einsum("kn,kn->k",
                     jnp.einsum("kdn,kd->kn", a_parts, u, precision=MATMUL),
                     v, precision=MATMUL)
    den = jnp.einsum("kn,kn->k", v, v, precision=MATMUL)
    return num / (den + 1e-30)


class CertificateReport(NamedTuple):
    """Prop. 1: per-node booleans whose conjunction certifies G_H <= eps."""

    local_gap: jax.Array          # (K,) LHS of Eq. 9
    local_gap_ok: jax.Array       # (K,) Eq. 9 holds
    grad_disagreement: jax.Array  # (K,) LHS of Eq. 10
    grad_ok: jax.Array            # (K,) Eq. 10 holds
    certified: jax.Array          # scalar bool: all nodes pass both


def neighbor_mask(neighbors, k: int, dtype=jnp.float32) -> jax.Array:
    """Self-inclusive 0/1 neighborhood mask N_k ∪ {k} from either a boolean
    adjacency (no self loops) or a mixing matrix W (whose support is the
    round's actual exchange pattern — under churn reweighting a dropped
    neighbor has W_kj = 0 and leaves the neighborhood, exactly as the real
    gossip exchange it models)."""
    m = jnp.asarray(np.asarray(neighbors) != 0, dtype=dtype)
    return jnp.maximum(m, jnp.eye(k, dtype=dtype))


def neighborhood_mean(grads: jax.Array, mask: jax.Array) -> jax.Array:
    """Eq.-10 neighborhood mean, masked-neighbor formulation.

    Each node averages the gradient VALUES its gossip exchange delivers:
    ``where(mask)``-selected rows of ``grads``, summed over the neighborhood
    — not a dense (K, K) float matmul that weights every node's gradient
    (non-neighbors by 0.0 and any matrix entry by its magnitude). This is
    the stacked oracle the distributed ``ppermute`` exchange is checked
    against: identical inputs (own + neighbor gradients), identical mean.
    """
    sel = jnp.where(mask[:, :, None] > 0, grads[None, :, :], 0.0)  # (K, K, d)
    counts = jnp.sum(mask, axis=1, keepdims=True)
    return jnp.sum(sel, axis=1) / counts


def consensus_residual(v_sum: jax.Array, ax_sum: jax.Array,
                       k_nodes: int) -> jax.Array:
    """Relative Lemma-1 invariant residual: ||(1/K) sum_k v_k - A x|| scaled
    by (||A x|| + 1).

    Every HONEST CoLA dynamic — any dx, churn freezing, budgets, Fig.-6
    resets — preserves (1/K) sum_k v_k = A x exactly in exact arithmetic
    (the mean-v and Ax updates cancel algebraically), and doubly-stochastic
    linear mixing keeps the mean untouched. A Byzantine payload (the
    effective column-stochasticity of the mix is broken) or per-link
    corruption moves the mean without moving A x, so this residual is the
    certificate layer's tamper detector (``certificate_violated``). Robust
    NONLINEAR aggregation (trim/median/clip) drifts it benignly by the
    neighborhood spread, which vanishes near consensus — hence a tolerance
    band rather than an exact-zero check.

    Args:
      v_sum: (d,) sum over all K nodes of v_k (psum-able partial in dist).
      ax_sum: (d,) sum over all K nodes of A_[k] x_[k] (= A x).
    """
    rho = jnp.linalg.norm(v_sum / k_nodes - ax_sum)
    return rho / (jnp.linalg.norm(ax_sum) + 1.0)


def node_subproblem_gaps(problem, x_parts: jax.Array, v_stack: jax.Array,
                         a_parts: jax.Array, gp_parts: jax.Array,
                         masks: jax.Array, grads: jax.Array) -> jax.Array:
    """(K,) LHS of condition (9): each node's local subproblem duality gap,
    from node-local quantities only (no cross-node data at all)."""
    def node_gap(v_k, g_k, a_k, x_k, gp_k, m_k):
        conj = problem.g_conj_el(
            -jnp.matmul(a_k.T, g_k, precision=MATMUL), gp_k)
        prim = problem.g_el(x_k, gp_k)
        return (jnp.dot(v_k, g_k, precision=MATMUL)
                + jnp.sum((prim + conj) * m_k))

    return jax.vmap(node_gap)(v_stack, grads, a_parts, x_parts,
                              gp_parts, masks)


def certificate_thresholds(masks, sigma_k, beta_ub: float, l_bound: float,
                           eps: float, k_nodes: int):
    """(gap_thresh, grad_thresh): the Prop.-1 RHS of conditions (9), (10).

    Both are round-invariant — they depend only on the partition sizes, the
    per-block spectral norms sigma_k, the mixing contraction beta and the
    L-bound — so recorders evaluate this once at init and record rounds
    compare against baked scalars.
    """
    gap_thresh = eps / (2.0 * k_nodes)
    n_k_sizes = jnp.sum(jnp.asarray(masks), axis=1)
    scale = jnp.sum(n_k_sizes ** 2 * jnp.asarray(sigma_k))
    grad_thresh = (scale ** -0.5) * (1.0 - beta_ub) / (
        2.0 * l_bound * jnp.sqrt(float(k_nodes))) * eps
    return gap_thresh, grad_thresh


def local_certificates(problem, part: Partition, x_parts: jax.Array,
                       v_stack: jax.Array, a_parts: jax.Array,
                       gp_parts: jax.Array, masks: jax.Array,
                       neighbors, beta_ub: float,
                       sigma_k: jax.Array, eps: float,
                       l_bound: float,
                       grads: jax.Array | None = None,
                       neigh_mean: jax.Array | None = None
                       ) -> CertificateReport:
    """Evaluate the Prop.-1 conditions (9) and (10) from local quantities only.

    The only cross-node data each node uses is its neighbors' gradients
    grad f(v_j), j in N_k — exactly what one gossip exchange provides.

    Args:
      neighbors: (K, K) boolean adjacency OR the round's mixing matrix W;
        only the support is used (self always included, W_kk > 0 for
        Metropolis weights). Passing the churn-reweighted W restricts each
        neighborhood to the nodes that actually exchanged this round.
      grads / neigh_mean: optional precomputed (K, d) gradients and Eq.-10
        neighborhood means (e.g. from the gossip exchange the round already
        performed) — recomputed from ``v_stack`` when omitted.
    """
    k_nodes = v_stack.shape[0]
    if grads is None:
        grads = jax.vmap(problem.grad_f)(v_stack)        # (K, d)

    # -- condition (9): local subproblem duality gap ------------------------
    local_gap = node_subproblem_gaps(problem, x_parts, v_stack, a_parts,
                                     gp_parts, masks, grads)

    # -- condition (10): gradient agreement with the neighborhood -----------
    if neigh_mean is None:
        mask = neighbor_mask(neighbors, k_nodes, dtype=grads.dtype)
        neigh_mean = neighborhood_mean(grads, mask)
    disagree = jnp.linalg.norm(grads - neigh_mean, axis=1)

    gap_thresh, grad_thresh = certificate_thresholds(
        masks, sigma_k, beta_ub, l_bound, eps, k_nodes)
    cond9 = local_gap <= gap_thresh
    cond10 = disagree <= grad_thresh

    return CertificateReport(
        local_gap=local_gap, local_gap_ok=cond9,
        grad_disagreement=disagree, grad_ok=cond10,
        certified=jnp.all(cond9 & cond10))
