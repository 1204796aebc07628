"""Pallas kernels for the CoLA local subproblem solver (paper Eq. 1-2).

They run in interpret mode only. The TPU compiler refuses both for a v5e,
for two reasons:

  * the ``(1, n_k)`` / ``(1, d)`` block specs on ``(K, n_k)`` / ``(K, d)``
    arrays break the (8, 128) tiling rule: the last two dimensions of a
    block must divide by 8 and 128 or equal the array's;
  * with ``(K, 1, n)`` arrays and ``(1, 1, n)`` blocks instead, Mosaic
    has no lowering for the ``dynamic_slice`` that
    ``lax.dynamic_slice_in_dim`` makes of a column of a loaded value.

Compiling them means rewriting them. ``run_cola`` uses the pure-jnp
``repro.core.subproblem.cd_solve_all`` instead.

The design they were written to: the paper's wall-clock is dominated by the
Theta-approximate local solve (Fig. 1b communication/computation
trade-off), so the whole node-local working set stays in VMEM for all
``kappa * n_k`` coordinate updates:

  * the node's column block A_[k]  (d x n_k tile),
  * the residual  r = A_[k] dx     (d,),
  * the iterate block dx           (n_k,),

so a full CD pass costs exactly one HBM read of A_[k] (at tile load) and no
HBM traffic inside the loop — the adaptation of the paper's "computation
between communication rounds" model to the TPU memory hierarchy (DESIGN.md
§3.3). Each grid program owns one node k (grid = (K,)); the sequential
coordinate recurrence runs as a ``fori_loop`` whose carries (dx, r) the
compiler keeps in VMEM/VREGs.

The separable prox is the generalized elastic-net family

    prox(z) = clip( soft(z - step*lin_i, step*l1) / (1 + step*l2), +-box )

which covers every ``repro.core.problems`` instance (l2 / l1+box / elastic
net / ridge-dual-with-linear-term); ``ops.py`` maps a Problem to its
(l1, l2, box) scalars + per-coordinate ``lin`` vector, and ``ref.py`` is the
pure-jnp oracle (``cd_solve_all``).

Two kernel variants share the prox (see ``repro.core.subproblem`` for the
cost model):

* ``_cd_kernel`` — residual formulation: VMEM holds the (d, n_k) column
  block; each coordinate step does an O(d) column dot + O(d) rank-1
  residual update.
* ``_cd_kernel_gram`` — Gram-cached: VMEM holds the (n_k, n_k) Gram block
  ``A_[k]^T A_[k]`` and the precomputed ``c = A_[k]^T grad``; each step
  maintains ``h = G dx`` with one O(n_k) column axpy. Preferred by
  ``repro.core.subproblem.gram_pays`` when n_k < d and the Gram block fits
  the VMEM budget; otherwise the residual kernel runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


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


def _cd_kernel_gram(gram_ref, x_ref, atg_ref, lin_ref, mask_ref, dx_ref, *,
                    num_steps: int, sigma_over_tau: float, l1: float,
                    l2: float, box: float):
    gram = gram_ref[0]    # (n_k, n_k) — the node's Gram block, in VMEM
    x = x_ref[0]          # (n_k,)
    atg = atg_ref[0]      # (n_k,) A_[k]^T grad_f(v_k), precomputed per round
    lin = lin_ref[0]      # (n_k,) linear term of g_i (ridge-dual labels)
    mask = mask_ref[0]    # (n_k,) 1 = real coordinate, 0 = padding

    n_k = gram.shape[0]
    # diag(G) = ||A_i||^2, via an iota mask (TPU-safe diagonal extraction)
    rows = lax.broadcasted_iota(jnp.int32, (n_k, n_k), 0)
    cols = lax.broadcasted_iota(jnp.int32, (n_k, n_k), 1)
    col_sq = jnp.sum(jnp.where(rows == cols, gram, 0.0), axis=0)
    q = sigma_over_tau * col_sq
    q_safe = jnp.where(q > 0, q, 1.0)

    def coord_step(step_i, carry):
        dx, h = carry                                 # h = G dx
        i = step_i % n_k                              # cyclic pass order
        g_col = lax.dynamic_slice_in_dim(gram, i, 1, axis=1)[:, 0]
        z = x[i] + dx[i]
        grad_i = atg[i] + sigma_over_tau * h[i]
        step = 1.0 / q_safe[i]
        u = z - grad_i * step - step * lin[i]
        soft = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)
        z_new = jnp.clip(soft / (1.0 + step * l2), -box, box)
        delta = jnp.where((q[i] > 0) & (mask[i] > 0), z_new - z, 0.0)
        return dx.at[i].add(delta), h + g_col * delta

    dx0 = jnp.zeros_like(x)
    h0 = jnp.zeros_like(x)
    dx, _ = lax.fori_loop(0, num_steps, coord_step, (dx0, h0))
    dx_ref[0] = dx


@functools.partial(jax.jit, static_argnames=(
    "num_steps", "sigma_over_tau", "l1", "l2", "box", "interpret"))
def cd_solve_blocks_gram(gram_parts: jax.Array, x_parts: jax.Array,
                         atg_parts: jax.Array, lin_parts: jax.Array,
                         masks: jax.Array, *, num_steps: int,
                         sigma_over_tau: float, l1: float, l2: float,
                         box: float, interpret: bool = True) -> jax.Array:
    """Gram-cached variant of ``cd_solve_blocks``; one grid program per node.

    Args:
      gram_parts: (K, n_k, n_k) node-local Gram blocks A_[k]^T A_[k].
      atg_parts: (K, n_k) per-node A_[k]^T grad_f(v_k).
      x_parts/lin_parts/masks: (K, n_k).

    Returns dx_parts: (K, n_k).
    """
    k, n_k, _ = gram_parts.shape
    kernel = functools.partial(
        _cd_kernel_gram, num_steps=num_steps, sigma_over_tau=sigma_over_tau,
        l1=l1, l2=l2, box=box)
    return pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, n_k, n_k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
            pl.BlockSpec((1, n_k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n_k), x_parts.dtype),
        interpret=interpret,
    )(gram_parts, x_parts, atg_parts, lin_parts, masks)


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
