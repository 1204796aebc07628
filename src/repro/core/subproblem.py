"""The data-local quadratic subproblem G_k^{sigma'} (paper Eq. 1-2) and its
Theta-approximate block coordinate-descent solver (Assumption 1).

    G_k(dx; v_k, x_k) = (1/K) f(v_k) + <grad_f(v_k), A_k dx>
                        + sigma'/(2 tau) ||A_k dx||^2
                        + sum_{i in P_k} g_i(x_i + dx_i)

The CD solver performs ``kappa`` cyclic passes over the local coordinates; each
single-coordinate update has the closed form

    z      = x_i + dx_i
    grad_i = A_i^T (grad_f(v_k) + (sigma'/tau) r)        with r = A_k dx
    q_i    = (sigma'/tau) ||A_i||^2
    z_new  = prox_{g_i, 1/q_i}(z - grad_i / q_i)
    dx_i  += z_new - z;   r += A_i (z_new - z)

``kappa`` is the paper's knob for the local accuracy Theta (Fig. 1): more
passes => smaller Theta => fewer communication rounds.

Two formulations of the per-coordinate gradient, identical in exact
arithmetic:

* **residual** (the formula above): carry ``r = A_k dx`` (d,) and take
  ``A_i^T (grad + (sigma'/tau) r)`` — two O(d) ops per coordinate step.
* **Gram-cached**: with the node-local Gram block ``G = A_k^T A_k``
  (computed once per env build) and ``c = A_k^T grad_f(v_k)`` (once per
  round), carry ``h = G dx`` (n_k,) instead:

      grad_i = c_i + (sigma'/tau) h_i;   h += G[:, i] * delta

  — one O(n_k) op per coordinate step. ``gram_pays`` is the cost model:
  the Gram path wins when n_k < d AND the (n_k, n_k) block fits the
  VMEM/cache budget; otherwise the residual path is used.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import MATMUL

# VMEM we allow the cached (n_k, n_k) Gram block to occupy per node. A TPU
# core has ~16 MB of VMEM which the block shares with dx/h/x/scalars; half
# of it keeps headroom for double-buffered loads.
GRAM_VMEM_BUDGET = 8 * 2 ** 20


def gram_pays(d: int, n_k: int, itemsize: int = 4,
              vmem_budget: int = GRAM_VMEM_BUDGET) -> bool:
    """Cost model for the Gram-cached CD path.

    A residual coordinate step moves ~2 * d * itemsize bytes (column dot +
    rank-1 residual update); a Gram step moves ~n_k * itemsize (one Gram
    column axpy). Caching pays iff the per-step saving is real (n_k < d)
    and the (n_k, n_k) block actually fits on chip.
    """
    return n_k < d and n_k * n_k * itemsize <= vmem_budget


def block_gram(a_parts: jax.Array) -> jax.Array:
    """(K, d, n_k) column blocks -> (K, n_k, n_k) node-local Gram blocks."""
    return jnp.einsum("kdn,kdm->knm", a_parts, a_parts, precision=MATMUL)


class SubproblemSpec(NamedTuple):
    """Static pieces of G_k shared by all nodes."""

    sigma_over_tau: float  # sigma' / tau
    inv_k: float           # 1 / K


def eval_subproblem(problem, spec: SubproblemSpec, a_k: jax.Array,
                    x_k: jax.Array, dx_k: jax.Array, v_k: jax.Array,
                    grad_k: jax.Array, gp_k: jax.Array,
                    mask_k: jax.Array) -> jax.Array:
    """Evaluate G_k^{sigma'}(dx; v_k, x_k) for one node (used in tests/Theta probes)."""
    r = jnp.matmul(a_k, dx_k, precision=MATMUL)
    lin = jnp.dot(grad_k, r, precision=MATMUL)
    quad = 0.5 * spec.sigma_over_tau * jnp.sum(r ** 2)
    g_term = jnp.sum(problem.g_el(x_k + dx_k, gp_k) * mask_k)
    return spec.inv_k * problem.f(v_k) + lin + quad + g_term


def cd_solve(problem, spec: SubproblemSpec, a_k: jax.Array, x_k: jax.Array,
             grad_k: jax.Array, gp_k: jax.Array, mask_k: jax.Array,
             num_steps: int, step_budget: jax.Array | None = None
             ) -> jax.Array:
    """Theta-approximate solution of G_k by cyclic CD updates (one node).

    Args:
      problem: the GLM Problem (provides prox_g_el).
      spec: sigma'/tau and 1/K constants.
      a_k: (d, n_k) local columns.
      x_k: (n_k,) local iterate block.
      grad_k: (d,) gradient of f at this node's (mixed) local estimate v_k.
      gp_k: (n_k,) per-coordinate g parameters.
      mask_k: (n_k,) 1 for real coordinates, 0 for padding.
      num_steps: total single-coordinate updates — the paper's kappa knob
        (Fig. 1); may be less than one full pass over the block.
      step_budget: optional TRACED per-call budget <= num_steps — the
        node-specific Theta_k of Definition 5 (stragglers do fewer updates;
        budget 0 == Theta_k = 1, no update). num_steps stays static so all
        nodes share one compiled program.

    Returns:
      dx_k: (n_k,) the local update Delta x_[k].
    """
    n_k = a_k.shape[1]
    col_sq = jnp.sum(a_k * a_k, axis=0)  # (n_k,) ||A_i||^2
    q = spec.sigma_over_tau * col_sq
    q_safe = jnp.where(q > 0, q, 1.0)

    def coord_step(carry, idx):
        step_i, i = idx
        dx, r = carry
        a_i = lax.dynamic_index_in_dim(a_k, i, axis=1, keepdims=False)
        z = x_k[i] + dx[i]
        grad_i = jnp.dot(a_i, grad_k + spec.sigma_over_tau * r,
                         precision=MATMUL)
        step = 1.0 / q_safe[i]
        z_new = problem.prox_g_el(z - grad_i * step, step, gp_k[i])
        ok = (q[i] > 0) & (mask_k[i] > 0)
        if step_budget is not None:
            ok = ok & (step_i < step_budget)
        delta = jnp.where(ok, z_new - z, 0.0)
        return (dx.at[i].add(delta), r + a_i * delta), None

    # derive the zeros from the inputs so they inherit device-varying types
    # under shard_map (vma) — semantically identical to jnp.zeros.
    dx0 = x_k * 0.0
    r0 = a_k[:, 0] * 0.0
    passes = -(-num_steps // n_k)
    order = jnp.tile(jnp.arange(n_k), passes)[:num_steps]
    steps = jnp.arange(num_steps)
    (dx, _), _ = lax.scan(coord_step, (dx0, r0), (steps, order))
    return dx


def cd_solve_gram(problem, spec: SubproblemSpec, gram_k: jax.Array,
                  atg_k: jax.Array, x_k: jax.Array, gp_k: jax.Array,
                  mask_k: jax.Array, num_steps: int,
                  step_budget: jax.Array | None = None) -> jax.Array:
    """Gram-cached CD solve of G_k for one node (see module docstring).

    Args:
      gram_k: (n_k, n_k) node-local Gram block A_[k]^T A_[k].
      atg_k: (n_k,) A_[k]^T grad_f(v_k), precomputed once per round.
      Remaining args as in ``cd_solve``.
    """
    n_k = gram_k.shape[0]
    col_sq = jnp.diagonal(gram_k)  # ||A_i||^2
    q = spec.sigma_over_tau * col_sq
    q_safe = jnp.where(q > 0, q, 1.0)

    def coord_step(carry, idx):
        step_i, i = idx
        dx, h = carry
        g_col = lax.dynamic_index_in_dim(gram_k, i, axis=1, keepdims=False)
        z = x_k[i] + dx[i]
        grad_i = atg_k[i] + spec.sigma_over_tau * h[i]
        step = 1.0 / q_safe[i]
        z_new = problem.prox_g_el(z - grad_i * step, step, gp_k[i])
        ok = (q[i] > 0) & (mask_k[i] > 0)
        if step_budget is not None:
            ok = ok & (step_i < step_budget)
        delta = jnp.where(ok, z_new - z, 0.0)
        return (dx.at[i].add(delta), h + g_col * delta), None

    dx0 = x_k * 0.0
    h0 = x_k * 0.0
    passes = -(-num_steps // n_k)
    order = jnp.tile(jnp.arange(n_k), passes)[:num_steps]
    steps = jnp.arange(num_steps)
    (dx, _), _ = lax.scan(coord_step, (dx0, h0), (steps, order))
    return dx


def cd_solve_all(problem, spec: SubproblemSpec, a_parts: jax.Array,
                 x_parts: jax.Array, grads: jax.Array, gp_parts: jax.Array,
                 masks: jax.Array, num_steps: int,
                 step_budgets: jax.Array | None = None,
                 gram_parts: jax.Array | None = None) -> jax.Array:
    """vmap of cd_solve over the node axis (single-host simulator path).

    ``step_budgets``: optional (K,) per-node budgets (heterogeneous Theta_k).
    ``gram_parts``: optional (K, n_k, n_k) Gram blocks — when given, the
    O(n_k)-per-step Gram-cached formulation replaces the O(d) residual one
    (numerically equivalent up to float reassociation, see module docstring).
    """
    if gram_parts is not None:
        atg = jnp.einsum("kdn,kd->kn", a_parts, grads, precision=MATMUL)
        if step_budgets is None:
            fn = lambda g_k, c_k, x_k, gp_k, m_k: cd_solve_gram(
                problem, spec, g_k, c_k, x_k, gp_k, m_k, num_steps)
            return jax.vmap(fn)(gram_parts, atg, x_parts, gp_parts, masks)
        fn = lambda g_k, c_k, x_k, gp_k, m_k, b_k: cd_solve_gram(
            problem, spec, g_k, c_k, x_k, gp_k, m_k, num_steps, b_k)
        return jax.vmap(fn)(gram_parts, atg, x_parts, gp_parts, masks,
                            step_budgets)
    if step_budgets is None:
        fn = lambda a_k, x_k, g_k, gp_k, m_k: cd_solve(
            problem, spec, a_k, x_k, g_k, gp_k, m_k, num_steps)
        return jax.vmap(fn)(a_parts, x_parts, grads, gp_parts, masks)
    fn = lambda a_k, x_k, g_k, gp_k, m_k, b_k: cd_solve(
        problem, spec, a_k, x_k, g_k, gp_k, m_k, num_steps, b_k)
    return jax.vmap(fn)(a_parts, x_parts, grads, gp_parts, masks,
                        step_budgets)
