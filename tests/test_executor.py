"""Round-block engine vs the retained per-round loop (bitwise), and the
Gram-cached CD formulation vs the residual one (oracle + Pallas-interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as bl, problems, topology as topo
from repro.core.cola import ColaConfig, build_env, run_cola
from repro.core.executor import record_flags, run_round_blocks
from repro.core.partition import make_partition
from repro.core.precision import MATMUL
from repro.core.subproblem import (SubproblemSpec, block_gram, cd_solve_all,
                                   gram_pays)
from repro.data import synthetic
from repro.kernels import cd_glm
from repro.kernels.ops import cd_solve_pallas

K = 8


@pytest.fixture(scope="module")
def ridge():
    x, y, _ = synthetic.regression(200, 64, seed=0)
    return problems.ridge_primal(jnp.asarray(x), jnp.asarray(y), 1e-2)


@pytest.fixture(scope="module")
def lasso_prob():
    x, y, _ = synthetic.regression(200, 64, seed=1, sparsity_solution=0.2)
    return problems.lasso(jnp.asarray(x), jnp.asarray(y), 1e-2)


def _drop(t, rng):
    return rng.random(K) < 0.7


def _budgets(t, rng):
    b = np.full(K, 16)
    b[rng.random(K) < 0.5] = 4
    return b


SCHEDULES = {
    "plain": {},
    "record7": dict(record_every=7),
    "churn": dict(active_schedule=_drop),
    "churn_reset": dict(active_schedule=_drop, leave_mode="reset"),
    "budgets": dict(budget_schedule=_budgets),
    "churn_budgets_reset": dict(active_schedule=_drop,
                                budget_schedule=_budgets, leave_mode="reset"),
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_block_executor_bitwise_matches_loop(ridge, case):
    """The scan engine must reproduce the make_round loop bit for bit, for
    every schedule feature (churn, heterogeneous budgets, reset-on-leave)."""
    kwargs = SCHEDULES[case]
    loop = run_cola(ridge, topo.ring(K), ColaConfig(kappa=1.0), 31,
                    executor="loop", seed=3, **kwargs)
    block = run_cola(ridge, topo.ring(K), ColaConfig(kappa=1.0), 31,
                     executor="block", block_size=10, seed=3, **kwargs)
    np.testing.assert_array_equal(np.asarray(loop.state.x_parts),
                                  np.asarray(block.state.x_parts))
    np.testing.assert_array_equal(np.asarray(loop.state.v_stack),
                                  np.asarray(block.state.v_stack))
    assert loop.history["round"] == block.history["round"]
    # metric values are computed by the same gap_report, but standalone-jitted
    # in the loop vs fused into the scan — identical up to fusion rounding
    for name in ("primal", "hamiltonian", "dual", "gap",
                 "consensus_violation"):
        np.testing.assert_allclose(loop.history[name], block.history[name],
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_block_executor_single_vs_many_blocks(ridge):
    """Block boundaries are invisible: one big block == many small ones."""
    a = run_cola(ridge, topo.ring(K), ColaConfig(kappa=1.0), 24,
                 executor="block", block_size=24)
    b = run_cola(ridge, topo.ring(K), ColaConfig(kappa=1.0), 24,
                 executor="block", block_size=5)
    np.testing.assert_array_equal(np.asarray(a.state.x_parts),
                                  np.asarray(b.state.x_parts))
    np.testing.assert_array_equal(np.asarray(a.state.v_stack),
                                  np.asarray(b.state.v_stack))


def test_record_flags_match_loop_condition():
    rec = record_flags(10, 4)
    assert list(np.nonzero(rec)[0]) == [0, 4, 8, 9]
    assert record_flags(1, 1).tolist() == [True]


def test_zero_rounds_matches_loop(ridge):
    """T=0 returns the initial state and an empty history on both drivers."""
    for ex in ("loop", "block"):
        res = run_cola(ridge, topo.ring(K), ColaConfig(kappa=1.0), 0,
                       executor=ex)
        assert res.history["round"] == []
        assert res.history["primal"] == []
        assert float(jnp.abs(res.state.x_parts).max()) == 0.0


def test_forced_cd_modes_build_matching_env(ridge):
    """cd_mode='gram' must materialize Gram blocks even where the heuristic
    declines; cd_mode='residual' must not pay for them (run_cola wiring)."""
    # wide blocks (n_k > d): heuristic says residual, forcing gram must work
    x, y, _ = synthetic.regression(16, 120, seed=7)
    wide = problems.ridge_primal(jnp.asarray(x), jnp.asarray(y), 1e-2)
    assert not gram_pays(wide.d, make_partition(wide.n, 2).block, 4)
    forced = run_cola(wide, topo.ring(2), ColaConfig(kappa=1.0,
                      cd_mode="gram"), 10, record_every=9)
    auto = run_cola(wide, topo.ring(2), ColaConfig(kappa=1.0), 10,
                    record_every=9)
    np.testing.assert_allclose(np.asarray(forced.state.x_parts),
                               np.asarray(auto.state.x_parts), atol=2e-5)


def test_executor_generic_aux_and_metrics():
    """The engine stacks per-round aux outputs and applies the record mask."""
    from repro.core.metrics import FnRecorder

    def step(s, _ctx, sched_t):
        s = s + sched_t["inc"]
        return s, s * 2.0

    state = jnp.zeros(())
    sched = {"inc": np.arange(1.0, 8.0, dtype=np.float32)}
    rec = np.array([True, False, False, True, False, False, True])
    res = run_round_blocks(step, state, sched,
                           recorder=FnRecorder(("x",),
                                               lambda s: jnp.stack([s])),
                           record_mask=rec, block_size=3)
    totals = np.cumsum(np.arange(1.0, 8.0))
    assert float(res.state) == totals[-1]
    np.testing.assert_allclose(res.aux[:, ...], 2.0 * totals)
    np.testing.assert_allclose(res.metrics[:, 0], totals[rec])
    assert list(res.rounds) == [0, 3, 6]
    assert res.stop_round is None


def test_executor_early_stop_truncates_and_freezes_state():
    """A recorder stop condition turns the rest of the block into no-ops and
    skips later blocks: final state == the full run's state at the stop
    round, metrics truncate at the certifying row."""
    from repro.core.metrics import FnRecorder

    def step(s, _ctx, sched_t):
        return s + sched_t["inc"], None

    sched = {"inc": np.ones((20,), dtype=np.float32)}
    recorder = FnRecorder(("x",), lambda s: jnp.stack([s]),
                          stop=lambda row: row[0] >= 7.0)
    res = run_round_blocks(step, jnp.zeros(()), sched, recorder=recorder,
                           block_size=6)
    # rounds are 1-indexed in value: after round t state == t+1; 7 at t=6
    assert res.stop_round == 6
    assert float(res.state) == 7.0
    assert list(res.rounds) == list(range(7))
    np.testing.assert_allclose(res.metrics[:, 0], np.arange(1.0, 8.0))


@pytest.mark.parametrize("recorder", ["gap", "gap+certificate"])
def test_recorder_arrays_are_block_program_arguments(recorder):
    """The arrays a recorder closes over (the data matrix, its node blocks)
    enter the block program as arguments: at the paper's data sizes an
    executable cannot embed them as constants."""
    import warnings

    x, y, _ = synthetic.regression(1024, 256, seed=4, sparsity_solution=0.2)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), 1e-2)  # A: 1 MiB
    limit = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 64 * 1024)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            res = run_cola(prob, topo.ring(K), ColaConfig(), 6,
                           recorder=recorder, eps=1e-9)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", limit)
    assert len(res.history["round"]) == 6
    captured = [str(w.message) for w in caught
                if "constants were captured" in str(w.message)]
    assert not captured, captured


def test_make_block_runner_binds_recorder():
    """make_block_runner: the bound runner reproduces run_round_blocks."""
    from repro.core.executor import make_block_runner
    from repro.core.metrics import FnRecorder

    def step(s, _ctx, sched_t):
        return s + sched_t["inc"], None

    run = make_block_runner(step, recorder=FnRecorder(
        ("x",), lambda s: jnp.stack([s]), stop=lambda row: row[0] >= 3.0),
        block_size=4)
    res = run(jnp.zeros(()), {"inc": np.ones((10,), np.float32)})
    assert res.stop_round == 2 and float(res.state) == 3.0


# ---------------------------------------------------------------------------
# Gram-cached CD vs residual CD
# ---------------------------------------------------------------------------

def _cd_inputs(prob, k=4, seed=0):
    part = make_partition(prob.n, k)
    env = build_env(prob, part, with_gram=True)
    key = jax.random.PRNGKey(seed)
    x_parts = 0.1 * jax.random.normal(key, (k, part.block))
    grads = jax.vmap(prob.grad_f)(
        0.3 * jax.random.normal(key, (k, prob.d)))
    spec = SubproblemSpec(sigma_over_tau=k / prob.tau, inv_k=1.0 / k)
    return part, env, x_parts, grads, spec


@pytest.mark.parametrize("name", sorted(problems.PROBLEMS))
def test_gram_oracle_matches_residual_oracle(name):
    x, y, _ = synthetic.regression(64, 36, seed=0)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    if name.startswith("logistic"):
        yj = jnp.sign(yj) + (jnp.sign(yj) == 0)
    prob = problems.PROBLEMS[name](xj, yj, 1e-2)
    part, env, x_parts, grads, spec = _cd_inputs(prob)
    steps = 2 * part.block
    res = cd_solve_all(prob, spec, env.a_parts, x_parts, grads,
                       env.gp_parts, env.masks, steps)
    grm = cd_solve_all(prob, spec, env.a_parts, x_parts, grads,
                       env.gp_parts, env.masks, steps,
                       gram_parts=env.gram_parts)
    np.testing.assert_allclose(np.asarray(grm), np.asarray(res), atol=2e-5)


def test_gram_oracle_matches_residual_with_budgets(ridge):
    part, env, x_parts, grads, spec = _cd_inputs(ridge)
    steps = 2 * part.block
    budgets = jnp.asarray([steps, 3, 0, steps // 2], jnp.int32)
    res = cd_solve_all(ridge, spec, env.a_parts, x_parts, grads,
                       env.gp_parts, env.masks, steps, step_budgets=budgets)
    grm = cd_solve_all(ridge, spec, env.a_parts, x_parts, grads,
                       env.gp_parts, env.masks, steps, step_budgets=budgets,
                       gram_parts=env.gram_parts)
    np.testing.assert_allclose(np.asarray(grm), np.asarray(res), atol=2e-5)
    # budget 0 still means "no update" on the Gram path
    assert float(jnp.abs(grm[2]).max()) == 0.0


@pytest.mark.parametrize("name", ["ridge_primal", "lasso", "ridge_dual"])
def test_pallas_gram_kernel_matches_oracles(name):
    x, y, _ = synthetic.regression(64, 36, seed=2)
    prob = problems.PROBLEMS[name](jnp.asarray(x), jnp.asarray(y), 1e-2)
    part, env, x_parts, grads, spec = _cd_inputs(prob)
    steps = 2 * part.block
    pl_res = cd_solve_pallas(prob, spec, env.a_parts, x_parts, grads,
                             env.gp_parts, env.masks, steps)
    atg = jnp.einsum("kdn,kd->kn", env.a_parts, grads, precision=MATMUL)
    pl_grm = cd_glm.cd_solve_blocks_gram(prob, spec, env.gram_parts, atg,
                                         x_parts, env.gp_parts, env.masks,
                                         steps, interpret=True)
    oracle_grm = cd_solve_all(prob, spec, env.a_parts, x_parts, grads,
                              env.gp_parts, env.masks, steps,
                              gram_parts=env.gram_parts)
    # the Pallas gram kernel is the same recurrence as the jnp gram oracle
    np.testing.assert_allclose(np.asarray(pl_grm), np.asarray(oracle_grm),
                               atol=1e-6)
    # and both agree with the residual formulation to float tolerance
    np.testing.assert_allclose(np.asarray(pl_grm), np.asarray(pl_res),
                               atol=2e-5)


def test_gram_heuristic_boundaries():
    assert gram_pays(d=1000, n_k=64, itemsize=4)       # tall block: cache it
    assert not gram_pays(d=64, n_k=1000, itemsize=4)   # wide block: residual
    assert not gram_pays(d=10 ** 6, n_k=3000, itemsize=4)  # Gram > VMEM
    assert not gram_pays(d=8, n_k=8, itemsize=4)       # no per-step saving


def test_run_cola_gram_vs_residual_full_run(lasso_prob):
    """End-to-end: forcing either CD formulation converges to the same run."""
    grm = run_cola(lasso_prob, topo.ring(K), ColaConfig(kappa=1.0,
                   cd_mode="gram"), 40, record_every=39)
    res = run_cola(lasso_prob, topo.ring(K), ColaConfig(kappa=1.0,
                   cd_mode="residual"), 40, record_every=39)
    np.testing.assert_allclose(grm.history["primal"][-1],
                               res.history["primal"][-1], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(grm.state.x_parts),
                               np.asarray(res.state.x_parts), atol=1e-4)


def test_build_env_gram_follows_heuristic(ridge):
    part = make_partition(ridge.n, K)
    env_auto = build_env(ridge, part)  # n_k=8 << d=200: gram pays
    assert env_auto.gram_parts is not None
    np.testing.assert_allclose(np.asarray(env_auto.gram_parts),
                               np.asarray(block_gram(env_auto.a_parts)),
                               atol=1e-6)
    env_off = build_env(ridge, part, with_gram=False)
    assert env_off.gram_parts is None


# ---------------------------------------------------------------------------
# baselines on the block engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cons():
    x, y, _ = synthetic.regression(200, 32, seed=5)
    return bl.make_consensus_problem(x, y, K, loss="square", reg="l2",
                                     lam=1e-2)


@pytest.mark.parametrize("runner,kwargs", [
    (bl.run_dgd, dict(step=0.3)),
    (bl.run_diging, dict(step=0.3)),
    (bl.run_dadmm, dict(rho=1.0)),
])
def test_baseline_block_matches_loop(cons, runner, kwargs):
    loop = runner(cons, topo.ring(K), rounds=37, record_every=10,
                  executor="loop", **kwargs)
    block = runner(cons, topo.ring(K), rounds=37, record_every=10,
                   executor="block", block_size=16, **kwargs)
    np.testing.assert_array_equal(np.asarray(loop.w_stack),
                                  np.asarray(block.w_stack))
    assert loop.history["round"] == block.history["round"]
    np.testing.assert_allclose(loop.history["objective"],
                               block.history["objective"], rtol=1e-6)
    np.testing.assert_allclose(loop.history["consensus"],
                               block.history["consensus"],
                               rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# gossip-DP on the block engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gossip_block_runner_matches_step_loop():
    from repro.configs.base import get_config, smoke_variant
    from repro.optim import gossip as gsp
    from repro.train.data import TokenBatches
    from repro.train.steps import TrainHParams, init_train_state, \
        make_train_step

    cfg = smoke_variant(get_config("xlstm_125m"))
    hp = TrainHParams(lr=1e-3)
    state0 = init_train_state(cfg, jax.random.PRNGKey(0), hp)
    local = make_train_step(cfg, hp)
    pipe = TokenBatches(cfg.vocab_size, 2, 16, corpus_tokens=1 << 12)
    k, rounds = 4, 6
    gcfg = gsp.GossipConfig(num_nodes=k)
    w = jnp.asarray(gcfg.weights(), jnp.float32)
    act = jnp.ones((k,), jnp.float32)

    def stacked(step):
        return jax.tree.map(
            jnp.asarray, jax.tree.map(lambda *xs: np.stack(xs),
                                      *[pipe(step, shard=j)
                                        for j in range(k)]))

    batches = [stacked(t) for t in range(rounds)]
    states = gsp.replicate_state(state0, k)
    step = gsp.make_gossip_step(local, gcfg)
    losses = []
    for t in range(rounds):
        states, m = step(states, batches[t], w, act)
        losses.append(float(jnp.mean(m["loss"])))

    runner = gsp.make_gossip_block_runner(local, gcfg)
    states2 = gsp.replicate_state(state0, k)
    bat_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    states2, metrics = runner(
        states2, bat_stack, jnp.broadcast_to(w, (rounds, k, k)),
        jnp.broadcast_to(act, (rounds, k)), gsp.mix_schedule(rounds, 1),
        block_size=4)
    losses2 = np.asarray(metrics["loss"]).mean(axis=1)
    np.testing.assert_allclose(losses, losses2, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(states.params),
                    jax.tree.leaves(states2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


def test_gossip_mix_schedule():
    from repro.optim.gossip import mix_schedule
    np.testing.assert_array_equal(mix_schedule(6, 2),
                                  [False, True, False, True, False, True])
    assert mix_schedule(4, 1).all()
