"""shard_map CoLA runtime == single-host simulator, bit-for-bit per round.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps the single real CPU device (per the dry-run
isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.data import synthetic
    from repro.core import problems, topology as topo
    from repro.core.cola import ColaConfig, run_cola
    from repro.dist.runtime import run_dist_cola

    x, y, w = synthetic.regression(160, 64, seed=0)
    mesh = jax.make_mesh((8,), ("data",))
    graph = topo.ring(8)
    for pname, lam in (("ridge_primal", 1e-2), ("lasso", 1e-3)):
        prob = problems.PROBLEMS[pname](jnp.asarray(x), jnp.asarray(y), lam)
        for cfg in (ColaConfig(kappa=1.0), ColaConfig(kappa=0.5, gossip_steps=2)):
            sim = run_cola(prob, graph, cfg, rounds=8)
            for comm in ("dense", "ring"):
                hist = run_dist_cola(prob, graph, cfg, mesh, rounds=8,
                                     comm=comm).history
                assert np.allclose(hist["primal"][-1],
                                   sim.history["primal"][-1], rtol=1e-5), (
                    pname, comm, hist["primal"][-1], sim.history["primal"][-1])
                assert np.allclose(hist["gap"][-1], sim.history["gap"][-1],
                                   rtol=1e-4, atol=1e-5)
    print("DIST_OK")
""")


def _run_isolated(script: str, token: str) -> None:
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert token in out.stdout, out.stdout + "\n" + out.stderr


@pytest.mark.slow
def test_shardmap_runtime_matches_simulator():
    _run_isolated(SCRIPT, "DIST_OK")


# the mesh/ppermute gossip-DP path on the shared round-block engine: block
# runner == per-round shard_map driver (same contract the dense vmap path
# pins in test_executor.test_gossip_block_runner_matches_step_loop)
GOSSIP_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config, smoke_variant
    from repro.optim import gossip as gsp
    from repro.train.data import TokenBatches
    from repro.train.steps import TrainHParams, init_train_state, \\
        make_train_step

    cfg = smoke_variant(get_config("xlstm_125m"))
    hp = TrainHParams(lr=1e-3)
    state0 = init_train_state(cfg, jax.random.PRNGKey(0), hp)
    local = make_train_step(cfg, hp)
    pipe = TokenBatches(cfg.vocab_size, 2, 16, corpus_tokens=1 << 12)
    k, rounds = 4, 6
    mesh = jax.make_mesh((4,), ("nodes",))
    gcfg = gsp.GossipConfig(num_nodes=k)
    w = jnp.asarray(gcfg.weights(), jnp.float32)
    act = jnp.ones((k,), jnp.float32)

    def stacked(step):
        return jax.tree.map(jnp.asarray,
                            jax.tree.map(lambda *xs: np.stack(xs),
                                         *[pipe(step, shard=j)
                                           for j in range(k)]))
    batches = [stacked(t) for t in range(rounds)]
    sh = NamedSharding(mesh, P("nodes"))
    put = lambda tree: jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    states = put(gsp.replicate_state(state0, k))
    step = gsp.make_gossip_step(local, gcfg, mesh=mesh, axis="nodes", conn=1)
    losses = []
    for t in range(rounds):
        states, m = step(states, batches[t], w, act)
        losses.append(float(jnp.mean(m["loss"])))

    runner = gsp.make_gossip_block_runner(local, gcfg, mesh=mesh,
                                          axis="nodes", conn=1)
    states2 = put(gsp.replicate_state(state0, k))
    bat_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    states2, metrics = runner(
        states2, bat_stack, jnp.broadcast_to(w, (rounds, k, k)),
        jnp.broadcast_to(act, (rounds, k)), gsp.mix_schedule(rounds, 1),
        block_size=3)
    losses2 = np.asarray(metrics["loss"]).mean(axis=1)
    np.testing.assert_allclose(losses, losses2, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(states.params),
                    jax.tree.leaves(states2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
    print("GOSSIP_MESH_BLOCK_OK")
""")


@pytest.mark.slow
def test_gossip_mesh_block_runner_matches_step_loop():
    _run_isolated(GOSSIP_MESH_SCRIPT, "GOSSIP_MESH_BLOCK_OK")


# the env built on the mesh: each device builds and holds its own nodes'
# blocks only, and the arrays are build_env's bit for bit
ENV_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import problems, topology as topo
    from repro.core.cola import ColaConfig, build_env
    from repro.core.partition import make_partition
    from repro.dist.runtime import (build_env_on_mesh, problem_on_mesh,
                                    run_dist_cola)
    from repro.dist.sharding import auto_mesh

    case = sys.argv[1]
    rng = np.random.default_rng(0)

    def mesh_of(m):
        return auto_mesh(jax.make_mesh((m,), ("data",),
                                       devices=jax.devices()[:m]))

    def problem(name, d, n):
        a = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
        y = jnp.asarray(np.sign(rng.standard_normal(d)), jnp.float32)
        if name == "ridge_dual":   # A's columns are samples: g_param (n,)
            return problems.ridge_dual(a.T, jnp.asarray(
                rng.standard_normal(n), jnp.float32), 0.1)
        return problems.PROBLEMS[name](a, y, 1.0)

    # (d, n, K, M): n a multiple of K, and two that pad the last nodes
    SHAPES = [(40, 64, 8, 4), (40, 61, 8, 4), (33, 97, 16, 4),
              (20, 30, 4, 2), (20, 30, 4, 1)]

    if case == "bitwise":
        for d, n, k, m in SHAPES:
            for name in ("logistic_l2", "lasso", "ridge_dual"):
                prob = problem(name, d, n)
                part = make_partition(prob.n, k)
                ref = build_env(prob, part, with_gram=True)
                got = build_env_on_mesh(prob, part, mesh_of(m), "data",
                                        with_gram=True)
                assert got.gram_parts is not None
                for field in ref._fields:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(ref, field)),
                        np.asarray(getattr(got, field)),
                        err_msg=f"{field} {name} {(d, n, k, m)}")
            got = build_env_on_mesh(prob, part, mesh_of(m), "data",
                                    with_gram=False)
            assert got.gram_parts is None
    elif case == "placement":
        for d, n, k, m in SHAPES:
            mesh = mesh_of(m)
            part = make_partition(n, k)
            env = build_env_on_mesh(problem("logistic_l2", d, n), part,
                                    mesh, "data", with_gram=True)
            ln = k // m
            for field in ("a_parts", "gram_parts", "gp_parts", "masks"):
                arr = getattr(env, field)
                assert arr.sharding.is_equivalent_to(
                    NamedSharding(mesh, P("data")), arr.ndim), field
                shards = arr.addressable_shards
                assert len(shards) == m, (field, len(shards))
                for j, dev in enumerate(mesh.devices.flat):
                    (mine,) = [s for s in shards if s.device == dev]
                    assert mine.index[0] == slice(j * ln, (j + 1) * ln) \\
                        or m == 1, (field, j, mine.index)
                    assert mine.data.shape[0] == ln
            # the recorder's A: laid out by columns once per problem and
            # mesh, where the nodes need no padding
            prob = problem("logistic_l2", d, n)
            placed = problem_on_mesh(prob, part, mesh, "data")
            if part.pad_width():
                assert placed is prob
            else:
                assert placed.a.sharding.is_equivalent_to(
                    NamedSharding(mesh, P(None, "data")), 2)
                assert problem_on_mesh(prob, part, mesh, "data") is placed
                np.testing.assert_array_equal(np.asarray(placed.a),
                                              np.asarray(prob.a))
    elif case == "guard":
        d, n, k, m = 40, 64, 8, 4
        mesh = mesh_of(m)
        part = make_partition(n, k)
        a = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
        y = jnp.ones((d,), jnp.float32)
        placed = jax.device_put(a, NamedSharding(mesh, P(None, "data")))
        build_env_on_mesh(problems.logistic_l2(placed, y, 1.0), part, mesh,
                          "data", with_gram=True)          # compiled
        # explicit device_puts too, which "disallow" lets pass
        with jax.transfer_guard_device_to_device("disallow_explicit"):
            env = build_env_on_mesh(problems.logistic_l2(placed, y, 1.0),
                                    part, mesh, "data", with_gram=True)
            jax.block_until_ready(env)
            # the same A on one device has to move: the guard is live
            one = jax.device_put(a, jax.devices()[0])
            try:
                jax.block_until_ready(build_env_on_mesh(
                    problems.logistic_l2(one, y, 1.0), part, mesh, "data",
                    with_gram=True))
            except Exception as e:
                assert "transfer" in str(e).lower(), e
            else:
                raise AssertionError("moving A between devices passed "
                                     "the guard")
        ref = build_env(problems.logistic_l2(a, y, 1.0), part,
                        with_gram=True)
        np.testing.assert_array_equal(np.asarray(ref.a_parts),
                                      np.asarray(env.a_parts))
    elif case == "reference":
        # the 2x2 cell's problem at small size: run_dist_cola(comm="plan")
        # against the plain reference, within the cell's limits
        sys.path.insert(0, os.getcwd())
        from bench.reference import cola as ref
        d, n, k, rounds = 400, 64, 16, 60
        a = jnp.asarray(rng.standard_normal((d, n)) / np.sqrt(d),
                        jnp.float32)
        y = jnp.asarray(np.sign(rng.standard_normal(d)), jnp.float32)
        prob = problems.logistic_l2(a, y, 1.0)
        res = run_dist_cola(prob, topo.ring(k), ColaConfig(kappa=1.0),
                            mesh_of(4), rounds, comm="plan", record_every=10,
                            block_size=25)
        inst = ref.Instance(a, y, {"nodes": k, "topology": "ring",
                                   "kappa": 1},
                            {"name": "logistic_l2", "lam": 1.0}, "highest")
        out = ref.run(inst, rounds, record_every=10, keep=[rounds])
        x, x_ref = (np.asarray(v, np.float64)
                    for v in (res.state.x_parts, out["kept"][rounds]))
        x_rel = np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref)
        said = inst.gap(jax.device_put(np.asarray(res.state.x_parts)),
                        jax.device_put(np.asarray(res.state.v_stack)))
        assert x_rel <= 3e-5, x_rel
        assert said["invariant"] <= 1e-3, said
    print("ENV_OK", case)
""")


@pytest.mark.parametrize("case", ["bitwise", "placement", "guard",
                                  "reference"])
def test_env_built_on_its_own_devices(case):
    """``build_env_on_mesh`` on four virtual devices: ``build_env``'s
    arrays bit for bit (padding, a g parameter, no Gram), every node block
    on its own device only, no transfer between devices for a column-laid
    A, and ``run_dist_cola`` within the 2x2 cell's limits of the plain
    reference."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", ENV_SCRIPT, case], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert f"ENV_OK {case}" in out.stdout, out.stdout + "\n" + out.stderr
