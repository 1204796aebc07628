"""jit'd wrappers connecting the Pallas kernels to the framework APIs.

* ``cd_solve_pallas`` — drop-in replacement for
  ``repro.core.subproblem.cd_solve_all`` (the CoLA local solver), dispatching
  a Problem's generalized prox scalars to the cd_glm kernel.
* ``flash_attention_ops`` — drop-in for
  ``repro.models.attention.chunked_attention``.

Both interpret the kernel body off the TPU and compile it with Mosaic on a
TPU (``interpret_mode``); neither can be told to interpret on a TPU. The
flash-attention kernel compiles for a v5e. The TPU compiler refuses both
cd_glm kernels (see ``repro.kernels.cd_glm``), so on a chip
``cd_solve_pallas`` raises that error; ``run_cola`` never calls it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.partition import Partition
from repro.core.problems import Problem
from repro.core.subproblem import SubproblemSpec, gram_pays
from repro.kernels import cd_glm
from repro.kernels.flash_attention import flash_attention


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: everywhere but a TPU."""
    return jax.default_backend() != "tpu"


def cd_solve_pallas(problem: Problem, spec: SubproblemSpec,
                    a_parts: jax.Array, x_parts: jax.Array,
                    grads: jax.Array, gp_parts: jax.Array,
                    masks: jax.Array, num_steps: int, *,
                    gram_parts: jax.Array | None = None,
                    cd_mode: str = "residual") -> jax.Array:
    """Same signature/semantics as ``cd_solve_all`` but on the Pallas kernel.

    ``cd_mode``: "residual" (default, the O(d)-per-step kernel), "gram"
    (force the O(n_k)-per-step Gram-cached kernel) or "auto" (pick by
    ``subproblem.gram_pays``). ``gram_parts`` may pass precomputed Gram
    blocks (e.g. ``ColaEnv.gram_parts``); otherwise they are built on the
    fly when the Gram kernel is selected.
    """
    l1, l2, box = problem.prox_spec
    k, d, n_k = a_parts.shape
    use_gram = (cd_mode == "gram"
                or (cd_mode == "auto"
                    and gram_pays(d, n_k, a_parts.dtype.itemsize)))
    if use_gram:
        if gram_parts is None:
            gram_parts = jnp.einsum("kdn,kdm->knm", a_parts, a_parts)
        atg = jnp.einsum("kdn,kd->kn", a_parts, grads)
        return cd_glm.cd_solve_blocks_gram(
            gram_parts, x_parts, atg, gp_parts, masks,
            num_steps=num_steps, sigma_over_tau=float(spec.sigma_over_tau),
            l1=float(l1), l2=float(l2), box=float(box),
            interpret=interpret_mode())
    return cd_glm.cd_solve_blocks(
        a_parts, x_parts, grads, gp_parts, masks,
        num_steps=num_steps, sigma_over_tau=float(spec.sigma_over_tau),
        l1=float(l1), l2=float(l2), box=float(box),
        interpret=interpret_mode())


def flash_attention_ops(q, k, v, q_pos, kv_pos, *, mode: str,
                        window: int = 0, block_q: int = 128,
                        block_kv: int = 128):
    """Drop-in for chunked_attention (same argument convention)."""
    return flash_attention(q, k, v, q_pos, kv_pos, mode=mode, window=window,
                           block_q=block_q, block_kv=block_kv,
                           interpret=interpret_mode())
