"""jit'd wrappers connecting the Pallas kernels to the framework APIs.

* ``cd_solve_pallas`` — drop-in replacement for
  ``repro.core.subproblem.cd_solve_all``'s residual path (the CoLA local
  solver) on the residual cd_glm kernel, a Problem mapped to its
  generalized prox scalars. The Gram kernel has no wrapper here:
  ``cd_solve_all`` calls it itself.
* ``flash_attention_ops`` — drop-in for
  ``repro.models.attention.chunked_attention``.

Both interpret the kernel body off the TPU and compile it with Mosaic on a
TPU (``interpret_mode``); neither can be told to interpret on a TPU. The
flash-attention kernel compiles for a v5e. The TPU compiler refuses the
residual CD kernel (see ``repro.kernels.cd_glm``), so on a chip
``cd_solve_pallas`` raises that error; ``run_cola`` never calls it.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax

from repro.kernels import cd_glm
from repro.kernels.flash_attention import flash_attention

if TYPE_CHECKING:
    from repro.core.problems import Problem
    from repro.core.subproblem import SubproblemSpec


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: everywhere but a TPU."""
    return jax.default_backend() != "tpu"


def cd_solve_pallas(problem: Problem, spec: SubproblemSpec,
                    a_parts: jax.Array, x_parts: jax.Array,
                    grads: jax.Array, gp_parts: jax.Array,
                    masks: jax.Array, num_steps: int) -> jax.Array:
    """Same signature/semantics as ``cd_solve_all`` without Gram blocks, on
    the residual Pallas kernel (O(d) per coordinate step)."""
    l1, l2, box = problem.prox_spec
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
