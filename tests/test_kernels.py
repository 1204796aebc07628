"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import problems
from repro.core.cola import build_env
from repro.core.partition import make_partition
from repro.core.subproblem import SubproblemSpec, cd_solve_all
from repro.data import synthetic
from repro.kernels import cd_glm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import cd_solve_pallas
from repro.models.attention import chunked_attention, reference_attention

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (b, sq, skv, h, kvh, hd, mode, window)
    (2, 32, 32, 4, 2, 16, "causal", 0),
    (2, 32, 32, 4, 2, 16, "sliding", 8),
    (2, 32, 32, 4, 4, 16, "chunked_local", 8),
    (2, 8, 24, 4, 2, 16, "cross", 0),
    (1, 1, 40, 8, 2, 32, "causal", 0),      # decode shape
    (2, 17, 23, 8, 2, 32, "causal", 0),     # non-multiples of block
    (1, 64, 64, 2, 1, 64, "sliding", 16),
    (3, 5, 37, 6, 3, 8, "chunked_local", 4),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(case, dtype):
    b, sq, skv, h, kvh, hd, mode, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, skv, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, skv, kvh, hd), dtype)
    q_pos = jnp.tile(jnp.arange(skv - sq, skv), (b, 1)).astype(jnp.int32)
    kv_pos = jnp.tile(jnp.arange(skv), (b, 1)).astype(jnp.int32)
    out = flash_attention(q, k, v, q_pos, kv_pos, mode=mode, window=window,
                          block_q=16, block_kv=16)
    ref = reference_attention(q, k, v, q_pos, kv_pos, mode=mode,
                              window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_ring_buffer_positions():
    """Rotated (ring-buffer) kv_pos with empty (-1) slots."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, skv, kvh, hd = 2, 24, 2, 16
    q = jax.random.normal(ks[0], (b, 1, 4, hd))
    k = jax.random.normal(ks[1], (b, skv, kvh, hd))
    v = jax.random.normal(ks[2], (b, skv, kvh, hd))
    kv_pos = jnp.tile((jnp.arange(skv) + 7) % skv, (b, 1)).astype(jnp.int32)
    kv_pos = kv_pos.at[:, -4:].set(-1)  # empty slots
    q_pos = jnp.full((b, 1), skv + 2, jnp.int32)
    out = flash_attention(q, k, v, q_pos, kv_pos, mode="sliding", window=10,
                          block_q=8, block_kv=8)
    ref = reference_attention(q, k, v, q_pos, kv_pos, mode="sliding",
                              window=10)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(skv=st.integers(8, 48), sq_frac=st.floats(0.05, 1.0),
       g=st.sampled_from([1, 2, 4]), seed=st.integers(0, 100))
def test_flash_attention_property_shapes(skv, sq_frac, g, seed):
    sq = max(1, int(sq_frac * skv))  # queries are the suffix of the kv span
    kvh, hd = 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, sq, kvh * g, hd))
    k = jax.random.normal(ks[1], (1, skv, kvh, hd))
    v = jax.random.normal(ks[2], (1, skv, kvh, hd))
    q_pos = jnp.arange(skv - sq, skv).reshape(1, -1).astype(jnp.int32)
    kv_pos = jnp.arange(skv).reshape(1, -1).astype(jnp.int32)
    out = flash_attention(q, k, v, q_pos, kv_pos, mode="causal",
                          block_q=16, block_kv=16)
    ref = reference_attention(q, k, v, q_pos, kv_pos, mode="causal")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_chunked_attention_matches_reference_all_modes():
    """The scan-based oracle itself vs the naive reference."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s, h, kvh, hd = 2, 40, 4, 2, 16
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kvh, hd))
    v = jax.random.normal(ks[2], (b, s, kvh, hd))
    pos = jnp.tile(jnp.arange(s), (b, 1)).astype(jnp.int32)
    for mode, window in [("causal", 0), ("sliding", 8),
                         ("chunked_local", 8), ("cross", 0)]:
        out = chunked_attention(q, k, v, pos, pos, mode=mode, window=window,
                                kv_chunk=16)
        ref = reference_attention(q, k, v, pos, pos, mode=mode, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=mode)


# ---------------------------------------------------------------------------
# CD GLM kernel
# ---------------------------------------------------------------------------

def _problem(name, seed=0):
    x, y, _ = synthetic.regression(64, 36, seed=seed)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    if name.startswith("logistic"):
        yj = jnp.sign(yj) + (jnp.sign(yj) == 0)
    return problems.PROBLEMS[name](xj, yj, 1e-2)


@pytest.mark.parametrize("name", sorted(problems.PROBLEMS))
@pytest.mark.parametrize("k,steps_mult", [(2, 1), (4, 2), (6, 3)])
def test_cd_kernel_matches_oracle(name, k, steps_mult):
    prob = _problem(name)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    key = jax.random.PRNGKey(k)
    x_parts = 0.1 * jax.random.normal(key, (k, part.block))
    vs = 0.3 * jax.random.normal(key, (k, prob.d))
    grads = jax.vmap(prob.grad_f)(vs)
    spec = SubproblemSpec(sigma_over_tau=k / prob.tau, inv_k=1.0 / k)
    steps = steps_mult * part.block
    ref = cd_solve_all(prob, spec, env.a_parts, x_parts, grads,
                       env.gp_parts, env.masks, steps)
    out = cd_solve_pallas(prob, spec, env.a_parts, x_parts, grads,
                          env.gp_parts, env.masks, steps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), frac=st.floats(0.2, 1.0))
def test_cd_kernel_partial_pass_property(seed, frac):
    """Fractional kappa (< one pass) still matches the oracle exactly."""
    prob = _problem("lasso", seed=seed)
    k = 4
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    grads = jax.vmap(prob.grad_f)(
        0.2 * jax.random.normal(jax.random.PRNGKey(seed), (k, prob.d)))
    x_parts = jnp.zeros((k, part.block))
    spec = SubproblemSpec(sigma_over_tau=k / prob.tau, inv_k=1.0 / k)
    steps = max(1, int(frac * part.block))
    ref = cd_solve_all(prob, spec, env.a_parts, x_parts, grads,
                       env.gp_parts, env.masks, steps)
    out = cd_solve_pallas(prob, spec, env.a_parts, x_parts, grads,
                          env.gp_parts, env.masks, steps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_cd_kernel_decreases_subproblem_objective():
    """The kernel's dx must decrease G_k (Assumption 1 with Theta < 1)."""
    from repro.core.subproblem import eval_subproblem
    prob = _problem("ridge_primal")
    k = 4
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    vs = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (k, prob.d))
    grads = jax.vmap(prob.grad_f)(vs)
    x_parts = jnp.zeros((k, part.block))
    spec = SubproblemSpec(sigma_over_tau=k / prob.tau, inv_k=1.0 / k)
    dx = cd_solve_pallas(prob, spec, env.a_parts, x_parts, grads,
                         env.gp_parts, env.masks, part.block)
    for i in range(k):
        g0 = eval_subproblem(prob, spec, env.a_parts[i], x_parts[i],
                             jnp.zeros_like(dx[i]), vs[i], grads[i],
                             env.gp_parts[i], env.masks[i])
        g1 = eval_subproblem(prob, spec, env.a_parts[i], x_parts[i], dx[i],
                             vs[i], grads[i], env.gp_parts[i], env.masks[i])
        assert float(g1) <= float(g0) + 1e-6


# ---------------------------------------------------------------------------
# Gram-path CD kernel: all K nodes in one call, against cd_solve_all's scan
# ---------------------------------------------------------------------------

def _gram_problem(name, n, d=32):
    """``name`` with n partitioned columns (samples for the ridge dual)."""
    key = jax.random.PRNGKey(n)
    y_len = n if name == "ridge_dual" else d
    x = jax.random.normal(key, (y_len, d) if name == "ridge_dual"
                          else (d, n))
    y = jax.random.normal(jax.random.fold_in(key, 1), (y_len,))
    if name.startswith("logistic"):
        y = jnp.sign(y) + (jnp.sign(y) == 0)
    return problems.PROBLEMS[name](x, y, 1e-2)


@pytest.mark.parametrize("steps", ["partial", "three_passes", "budgets"])
@pytest.mark.parametrize("n_k", [63, 125, 130])
@pytest.mark.parametrize("k", [4, 6, 16])
@pytest.mark.parametrize("name", sorted(problems.PROBLEMS))
def test_gram_kernel_matches_gram_scan(name, k, n_k, steps):
    """K below, above and not a multiple of 8 sublanes; n_k within one
    128-lane tile and past it; the last node's last coordinate is padding."""
    prob = _gram_problem(name, k * n_k - 1)
    part = make_partition(prob.n, k)
    assert part.block == n_k
    env = build_env(prob, part, with_gram=True)
    key = jax.random.PRNGKey(k + n_k)
    x_parts = 0.1 * jax.random.normal(key, (k, n_k))
    grads = jax.vmap(prob.grad_f)(
        0.3 * jax.random.normal(jax.random.fold_in(key, 1), (k, prob.d)))
    spec = SubproblemSpec(sigma_over_tau=k / prob.tau, inv_k=1.0 / k)
    num_steps = n_k // 2 + 1 if steps == "partial" else 3 * n_k
    budgets = None
    if steps == "budgets":
        budgets = jnp.asarray([(0, 1, n_k // 2, num_steps, 2 * n_k + 5)[i % 5]
                               for i in range(k)], jnp.int32)
    ref = cd_solve_all(prob, spec, env.a_parts, x_parts, grads, env.gp_parts,
                       env.masks, num_steps, step_budgets=budgets,
                       gram_parts=env.gram_parts)
    atg = jnp.einsum("kdn,kd->kn", env.a_parts, grads,
                     precision=jax.lax.Precision.HIGHEST)
    out = cd_glm.cd_solve_blocks_gram(prob, spec, env.gram_parts, atg,
                                      x_parts, env.gp_parts, env.masks,
                                      num_steps, budgets, interpret=True)
    ref, out = np.asarray(ref), np.asarray(out)
    assert np.abs(ref).max() > 0
    # the same arithmetic on the same values: bitwise on the CPU
    np.testing.assert_array_equal(out, ref)
    if budgets is not None:
        assert not out[0].any()
