"""Pallas kernels for the CoLA local subproblem solver (paper Eq. 1-2).

Both keep a solve's whole working set in VMEM for all ``kappa * n_k``
coordinate updates, so a round's sequential recurrence costs no HBM
traffic inside its loop (the paper's "computation between communication
rounds", Fig. 1b, mapped onto the TPU memory hierarchy). See
``repro.core.subproblem`` for the two formulations and their cost model.

* ``cd_solve_blocks_gram`` — the Gram-cached solve of all K nodes in one
  call. Every node visits its coordinates in the same cyclic order, so the
  nodes run in lockstep: node k on sublane k, coordinate i on lane i of a
  ``gram_tile`` tile. Step i reads column i of every node's Gram block as
  one tile from the leading axis of the (n_k, K_pad, n_pad) column stack,
  reads coordinate i of each vector by a one-hot lane sum, and keeps
  ``h = G dx`` with one tile axpy. The prox is the problem's own
  ``prox_g_el``, so the arithmetic is ``cd_solve_gram``'s op for op. It
  compiles for a v5e: ``repro.core.subproblem.cd_solve_all`` runs it in
  every program lowered for a TPU whose working set fits
  (``subproblem.gram_kernel_fits``), ``run_cola`` and ``run_dist_cola``
  among them.
* ``cd_solve_blocks`` — the residual formulation, one grid program per node
  (grid = (K,)): VMEM holds the node's (d, n_k) column block, the residual
  r = A_[k] dx and dx; each step does an O(d) column dot and an O(d)
  rank-1 residual update. It runs interpreted only, and nothing on the
  solver's path calls it. The TPU compiler refuses it for a v5e for two
  reasons: the ``(1, n_k)`` / ``(1, d)`` block specs on ``(K, n_k)`` /
  ``(K, d)`` arrays break the (8, 128) tiling rule, and with
  ``(K, 1, n)`` arrays and ``(1, 1, n)`` blocks instead Mosaic has no
  lowering for the ``dynamic_slice`` of a column of a loaded value. Its
  prox is the generalized elastic-net family

      prox(z) = clip( soft(z - step*lin_i, step*l1) / (1 + step*l2), +-box )

  which covers every ``repro.core.problems`` instance; ``ops.py`` maps a
  Problem to its (l1, l2, box) scalars and per-coordinate ``lin`` vector.

``ref.py`` names the pure-jnp oracles (``cd_solve_all``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# a float32 vreg is (8, 128): the last two dimensions of a VMEM block tile
# by these
_SUBLANES, _LANES = 8, 128


def _cd_kernel(a_ref, x_ref, grad_ref, lin_ref, mask_ref, dx_ref, *,
               num_steps: int, sigma_over_tau: float, l1: float, l2: float,
               box: float):
    a = a_ref[0]          # (d, n_k) — the node's column block, in VMEM
    x = x_ref[0]          # (n_k,)
    grad = grad_ref[0]    # (d,)
    lin = lin_ref[0]      # (n_k,) linear term of g_i (ridge-dual labels)
    mask = mask_ref[0]    # (n_k,) 1 = real coordinate, 0 = padding

    n_k = a.shape[1]
    col_sq = jnp.sum(a * a, axis=0)                   # ||A_i||^2
    q = sigma_over_tau * col_sq
    q_safe = jnp.where(q > 0, q, 1.0)

    def coord_step(step_i, carry):
        dx, r = carry
        i = step_i % n_k                              # cyclic pass order
        a_i = lax.dynamic_slice_in_dim(a, i, 1, axis=1)[:, 0]
        z = x[i] + dx[i]
        grad_i = jnp.dot(a_i, grad + sigma_over_tau * r)
        step = 1.0 / q_safe[i]
        u = z - grad_i * step - step * lin[i]
        soft = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)
        z_new = jnp.clip(soft / (1.0 + step * l2), -box, box)
        delta = jnp.where((q[i] > 0) & (mask[i] > 0), z_new - z, 0.0)
        return dx.at[i].add(delta), r + a_i * delta

    dx0 = jnp.zeros_like(x)
    r0 = jnp.zeros_like(grad)
    dx, _ = lax.fori_loop(0, num_steps, coord_step, (dx0, r0))
    dx_ref[0] = dx


def gram_tile(k: int, n_k: int) -> tuple:
    """(K_pad, n_pad): the Gram kernel's per-node tile, K nodes on sublanes
    and n_k coordinates on lanes, padded to the (8, 128) f32 tiling."""
    return -(-k // _SUBLANES) * _SUBLANES, -(-n_k // _LANES) * _LANES


def _cd_kernel_gram(cols_ref, x_ref, atg_ref, gp_ref, diag_ref, mask_ref,
                    budget_ref, dx_ref, *, num_steps: int, n_k: int,
                    sigma_over_tau: float, prox):
    x = x_ref[...]          # (K_pad, n_pad): node k's coordinates on lanes
    atg = atg_ref[...]      # A_[k]^T grad_f(v_k), precomputed per round
    gp = gp_ref[...]        # per-coordinate g parameters
    budget = budget_ref[...]  # (K_pad, 1) step budgets (Definition 5)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    q = sigma_over_tau * diag_ref[...]                # diag(G) = ||A_i||^2
    q_safe = jnp.where(q > 0, q, 1.0)
    steps = 1.0 / q_safe
    live = ((q > 0) & (mask_ref[...] > 0)).astype(x.dtype)

    def coord_step(t, carry):
        dx, h = carry                                 # h = G dx
        i = t % n_k                                   # cyclic pass order
        sel = lane == i
        # coordinate i of every node, (K_pad, 1): a one-hot lane sum adds
        # only zeros, so it is exact
        pick = lambda v: jnp.sum(jnp.where(sel, v, 0.0), axis=1,
                                 keepdims=True)
        dx_i = pick(dx)
        z = pick(x) + dx_i
        grad_i = pick(atg) + sigma_over_tau * pick(h)
        step = pick(steps)
        z_new = prox(z - grad_i * step, step, pick(gp))
        ok = (pick(live) > 0) & (t < budget)
        delta = jnp.where(ok, z_new - z, 0.0)
        # cols_ref[i] holds column i of every node's Gram block
        return (jnp.where(sel, dx_i + delta, dx), h + cols_ref[i] * delta)

    zeros = jnp.zeros_like(x)
    dx, _ = lax.fori_loop(0, num_steps, coord_step, (zeros, zeros))
    dx_ref[...] = dx


def cd_solve_blocks_gram(problem, spec, gram_parts: jax.Array,
                         atg_parts: jax.Array, x_parts: jax.Array,
                         gp_parts: jax.Array, masks: jax.Array,
                         num_steps: int,
                         step_budgets: jax.Array | None = None, *,
                         interpret: bool = False) -> jax.Array:
    """All K nodes' Gram-cached CD solves as one kernel call; the same
    recurrence, op for op, as ``repro.core.subproblem.cd_solve_gram``
    vmapped over the nodes.

    Every node visits its coordinates in the same cyclic order, so the K
    nodes run in lockstep: node k sits on sublane k and coordinate i on
    lane i of a ``gram_tile(K, n_k)`` tile, and step i reads column i of
    every node's Gram block as one tile from the leading axis of the
    (n_k, K_pad, n_pad) column stack. Padded rows and lanes have mask 0
    and never update.

    Args:
      problem: the GLM Problem (its elementwise ``prox_g_el``).
      spec: sigma'/tau and 1/K constants.
      gram_parts: (K, n_k, n_k) node-local Gram blocks A_[k]^T A_[k].
      atg_parts: (K, n_k) per-node A_[k]^T grad_f(v_k).
      x_parts/gp_parts/masks: (K, n_k).
      num_steps: coordinate updates per node (kappa * n_k).
      step_budgets: optional (K,) per-node budgets <= num_steps.

    Returns dx_parts: (K, n_k).
    """
    k, n_k = x_parts.shape
    k_pad, n_pad = gram_tile(k, n_k)
    pad = lambda v: jnp.pad(v, ((0, k_pad - k), (0, n_pad - n_k)))
    cols = jnp.pad(jnp.transpose(gram_parts, (2, 0, 1)),
                   ((0, 0), (0, k_pad - k), (0, n_pad - n_k)))
    diag = jnp.diagonal(gram_parts, axis1=1, axis2=2)
    if step_budgets is None:
        step_budgets = jnp.full((k,), num_steps, jnp.int32)
    budgets = jnp.pad(step_budgets.astype(jnp.int32), (0, k_pad - k))
    args = (cols, pad(x_parts), pad(atg_parts), pad(gp_parts), pad(diag),
            pad(masks), budgets[:, None])
    kernel = functools.partial(
        _cd_kernel_gram, num_steps=num_steps, n_k=n_k,
        sigma_over_tau=spec.sigma_over_tau, prox=problem.prox_g_el)
    # under shard_map the result varies over the mesh axes its inputs do
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    with jax.named_scope("cola.cd_kernel"):
        dx = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((k_pad, n_pad), x_parts.dtype,
                                           vma=vma),
            interpret=interpret,
        )(*args)
    return dx[:k, :n_k]


@functools.partial(jax.jit, static_argnames=(
    "num_steps", "sigma_over_tau", "l1", "l2", "box", "interpret"))
def cd_solve_blocks(a_parts: jax.Array, x_parts: jax.Array,
                    grads: jax.Array, lin_parts: jax.Array,
                    masks: jax.Array, *, num_steps: int,
                    sigma_over_tau: float, l1: float, l2: float,
                    box: float, interpret: bool = True) -> jax.Array:
    """Solve all K node subproblems; one grid program per node.

    Args:
      a_parts: (K, d, n_k); x_parts/lin_parts/masks: (K, n_k); grads: (K, d).
      num_steps: total coordinate updates per node (kappa * n_k).
      sigma_over_tau / l1 / l2 / box: subproblem + prox scalars.

    Returns dx_parts: (K, n_k).
    """
    k, d, n_k = a_parts.shape
    kernel = functools.partial(
        _cd_kernel, num_steps=num_steps, sigma_over_tau=sigma_over_tau,
        l1=l1, l2=l2, box=box)
    return pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, d, n_k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (i, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n_k), x_parts.dtype),
        interpret=interpret,
    )(a_parts, x_parts, grads, lin_parts, masks)
