"""Kernel microbenchmarks: Pallas kernels vs their jnp oracles, us/call.

  PYTHONPATH=src:. python benchmarks/kernel_bench.py

Every row names the backend it ran on and the kernels' mode: ``interpret``
off a TPU, where the Pallas interpreter executes the kernel body and the
timings only check the plumbing, and ``mosaic`` on a TPU. There the
residual cd_glm row raises the TPU compiler's refusal (see
``repro.kernels.cd_glm``)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row
from repro.core import problems
from repro.core.cola import build_env
from repro.core.partition import make_partition
from repro.core.precision import MATMUL
from repro.core.subproblem import (SubproblemSpec, block_gram, cd_solve_all,
                                   cd_solve_gram)
from repro.data import synthetic
from repro.kernels import cd_glm
from repro.kernels.ops import (cd_solve_pallas, flash_attention_ops,
                               interpret_mode)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.attention import chunked_attention


def _time(fn, iters=3):
    fn()  # compile
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.time() - t0) / iters * 1e6


def run(fast: bool = True):
    backend = jax.default_backend()
    mode = "interpret" if interpret_mode() else "mosaic"
    pallas, oracle = f"pallas-{mode}@{backend}", f"jnp@{backend}"
    csv_row("fig", "kernel", "case", "us_per_call")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, kvh, hd = 1, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kvh, hd))
    v = jax.random.normal(ks[2], (b, s, kvh, hd))
    pos = jnp.tile(jnp.arange(s), (b, 1)).astype(jnp.int32)
    csv_row("kernels", f"flash_attention({pallas})", f"{s}x{s}",
            f"{_time(lambda: flash_attention_ops(q, k, v, pos, pos, mode='causal', block_q=64, block_kv=64)):.0f}")
    csv_row("kernels", f"chunked_attention({oracle})", f"{s}x{s}",
            f"{_time(lambda: chunked_attention(q, k, v, pos, pos, mode='causal', kv_chunk=64)):.0f}")

    x, y, _ = synthetic.regression(256, 128, seed=0)
    prob = problems.ridge_primal(jnp.asarray(x), jnp.asarray(y), 1e-2)
    kk = 8
    part = make_partition(prob.n, kk)
    env = build_env(prob, part)
    grads = jax.vmap(prob.grad_f)(jnp.zeros((kk, prob.d)))
    xp = jnp.zeros((kk, part.block))
    spec = SubproblemSpec(sigma_over_tau=kk / prob.tau, inv_k=1.0 / kk)
    csv_row("kernels", f"cd_glm({pallas})", f"K={kk},pass=1",
            f"{_time(lambda: cd_solve_pallas(prob, spec, env.a_parts, xp, grads, env.gp_parts, env.masks, part.block)):.0f}")
    csv_row("kernels", f"cd_glm({oracle})", f"K={kk},pass=1",
            f"{_time(lambda: cd_solve_all(prob, spec, env.a_parts, xp, grads, env.gp_parts, env.masks, part.block)):.0f}")

    # Gram-cached CD: O(n_k) per coordinate step vs the residual path's O(d)
    gram = env.gram_parts if env.gram_parts is not None else block_gram(
        env.a_parts)
    # c = A_k^T grad as cd_solve_all computes it; the oracle is its scan
    atg = jnp.einsum("kdn,kd->kn", env.a_parts, grads, precision=MATMUL)
    scan = jax.vmap(lambda g, c, x, gp, m: cd_solve_gram(
        prob, spec, g, c, x, gp, m, part.block))
    csv_row("kernels", f"cd_glm_gram({pallas})", f"K={kk},pass=1",
            f"{_time(lambda: cd_glm.cd_solve_blocks_gram(prob, spec, gram, atg, xp, env.gp_parts, env.masks, part.block, interpret=interpret_mode())):.0f}")
    csv_row("kernels", f"cd_glm_gram({oracle})", f"K={kk},pass=1",
            f"{_time(lambda: scan(gram, atg, xp, env.gp_parts, env.masks)):.0f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
