"""The main path compiled for a TPU v5e that is described, not attached.

jaxlib ships the TPU compiler, so these compile ``chip_smoke.py``'s
phase (a) at full size — L2 logistic regression on 400,000 x 2,000, K=16 —
for one chip and for a four-chip 2x2 host, and the Fig.-1 lasso's round
block (10,000 x 1,000, K=16, kappa 8) for one chip. Each must run its
local CD solve as the Gram kernel of ``repro.kernels.cd_glm``. They catch what only the TPU
compiler refuses: a program over the chip's memory, a layout it cannot
tile, a collective the comm plan did not promise. A compile that passes is
not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os
import re

import numpy as np
import pytest

EPS_SHAPE = (400_000, 2_000)   # chip_smoke.py phase (a): samples x features
LASSO_SHAPE = (10_000, 1_000)  # the paper's Fig. 1
K, CHIPS, BLOCK = 16, 4, 10
HBM_BYTES = 16 * 2 ** 30       # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU library otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def epsilon_problem():
    """Phase (a)'s problem with only the shape of its data matrix."""
    import jax
    import jax.numpy as jnp
    from repro.core import problems

    d, n = EPS_SHAPE
    return problems.logistic_l2(jax.ShapeDtypeStruct((d, n), jnp.float32),
                                jnp.ones((d,), jnp.float32), 1.0)


def _solve_ops(hlo: str) -> tuple:
    """(Mosaic kernel calls, while loops) of the compiled HLO whose op_name
    lies in the round's local solve."""
    ops = [line for line in hlo.splitlines()
           if re.search(r'op_name="[^"]*cola\.local_solve', line)]
    kernels = [op for op in ops if 'custom_call_target="tpu_custom_call"' in op]
    loops = [op for op in ops if re.search(r"= \S.* while\(", op)]
    return kernels, loops


def _assert_cd_kernel(hlo: str):
    kernels, loops = _solve_ops(hlo)
    assert len(kernels) == 1, kernels
    assert "cola.local_solve/cond/branch_0_fun/cola.cd_kernel/" in kernels[0]
    # the kappa * n_k coordinate steps run inside the kernel, not as a scan
    assert not loops, loops


def test_epsilon_round_block_fits_one_chip(topo, epsilon_problem):
    from repro.analysis import drivers
    from repro.core import topology as graphs
    from repro.core.cola import ColaConfig

    compiled = drivers.sim_block_compiled(
        epsilon_problem, graphs.ring(K), ColaConfig(), BLOCK,
        device=topo.devices[0])
    mem = compiled.memory_analysis()
    # the data matrix and its (K, d, n_k) node blocks are the arguments
    d, n = EPS_SHAPE
    assert mem.argument_size_in_bytes >= 2 * d * n * 4
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem
    # the carried state is donated: x_parts and v_stack alias their outputs
    assert mem.alias_size_in_bytes >= K * d * 4
    _assert_cd_kernel(compiled.as_text())


def test_lasso_round_block_runs_the_cd_kernel(topo):
    import jax
    import jax.numpy as jnp
    from repro.analysis import drivers
    from repro.core import problems, topology as graphs
    from repro.core.cola import ColaConfig

    d, n = LASSO_SHAPE
    prob = problems.lasso(jax.ShapeDtypeStruct((d, n), jnp.float32),
                          jnp.ones((d,), jnp.float32), 0.05, box=5.0)
    compiled = drivers.sim_block_compiled(
        prob, graphs.ring(K), ColaConfig(kappa=8.0, cd_mode="gram"), BLOCK,
        device=topo.devices[0])
    _assert_cd_kernel(compiled.as_text())


@pytest.mark.parametrize("k", [10, K])
def test_budgeted_solve_runs_the_cd_kernel(topo, k):
    """Per-node step budgets (Definition 5), over all K nodes and over a
    cohort's gathered K' = 10, which is no multiple of 8 sublanes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core import problems
    from repro.core.subproblem import SubproblemSpec, cd_solve_all

    d, n = LASSO_SHAPE
    n_k = n // K
    prob = problems.lasso(jax.ShapeDtypeStruct((d, k * n_k), jnp.float32),
                          jnp.ones((d,), jnp.float32), 0.05, box=5.0)
    spec = SubproblemSpec(sigma_over_tau=float(k), inv_k=1.0 / k)

    def solve(a_parts, gram_parts, x_parts, grads, gp_parts, masks,
              budgets):
        with jax.named_scope("cola.local_solve"):
            return cd_solve_all(prob, spec, a_parts, x_parts, grads,
                                gp_parts, masks, 8 * n_k,
                                step_budgets=budgets, gram_parts=gram_parts)

    on_chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=on_chip)
    compiled = jax.jit(solve).lower(
        arg(k, d, n_k), arg(k, n_k, n_k), arg(k, n_k), arg(k, d),
        arg(k, n_k), arg(k, n_k), arg(k, dtype=jnp.int32)).compile()
    _assert_cd_kernel(compiled.as_text())


def test_plan_round_on_four_chips(topo, epsilon_problem):
    import jax
    from repro.analysis import drivers
    from repro.core import topology as graphs
    from repro.launch import hlo_analysis

    mesh = jax.make_mesh((CHIPS,), ("data",), devices=topo.devices)
    hlo, plan = drivers.block_round_hlo(epsilon_problem, graphs.ring(K), K,
                                        CHIPS, mesh=mesh)
    counts = hlo_analysis.analyze(hlo)["collective_counts"]
    # a ring of 16 on 4 chips: block neighbors exchange by ppermute, one
    # per block color, and nothing gathers the (K, d) stack
    assert 0 < counts["collective-permute"] <= plan.num_colors, counts
    assert counts["all-gather"] == 0, counts
    assert np.isclose(counts["all-reduce"], 0), counts
    # each chip solves its K / 4 nodes in the kernel
    _assert_cd_kernel(hlo)


def test_env_built_on_each_of_four_chips(topo):
    """``run_dist_cola``'s env build for the 2x2 host: from A laid out by
    columns, each chip holds a quarter of A and writes a quarter of the
    node blocks, with no whole-env buffer anywhere. Layouts are pinned to
    the descending ones a chip's client gives its arrays."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.partition import make_partition
    from repro.dist import runtime
    from repro.dist.sharding import auto_mesh
    from repro.launch import hlo_analysis

    mesh = auto_mesh(jax.make_mesh((CHIPS,), ("data",),
                                   devices=topo.devices))
    d, n = EPS_SHAPE
    part = make_partition(n, K)

    def fmt(ndim, spec):
        return Format(Layout(major_to_minor=tuple(range(ndim))),
                      NamedSharding(mesh, spec))

    build = jax.jit(runtime._env_build.__wrapped__,
                    static_argnames=("part", "mesh", "axis", "with_gram"),
                    out_shardings=runtime.ColaEnv(
                        fmt(3, P("data")), fmt(2, P("data")),
                        fmt(2, P("data")), fmt(3, P("data"))))
    a = jax.ShapeDtypeStruct((d, n), jnp.float32,
                             sharding=fmt(2, P(None, "data")))
    compiled = build.lower(a, None, part=part, mesh=mesh, axis="data",
                           with_gram=True).compile()
    mem = compiled.memory_analysis()
    quarter = d * n * 4 // CHIPS
    # A's columns in, the node blocks out (lanes padded 500 -> 512 and
    # 125 -> 128), plus the Gram blocks and masks
    assert quarter <= mem.argument_size_in_bytes <= 1.05 * quarter, mem
    assert quarter <= mem.output_size_in_bytes <= 1.05 * quarter, mem
    # at most one node's columns in flight: never a relayout of the whole
    assert mem.temp_size_in_bytes <= quarter / 2, mem
    hlo = compiled.as_text()
    assert "cola.env_build" in hlo
    # each chip builds from its own columns: nothing crosses a link
    counts = hlo_analysis.analyze(hlo)["collective_counts"]
    assert not any(counts.values()), counts


def test_gap_record_takes_a_by_columns_on_four_chips(topo):
    """The gap recorder's row on the 2x2 host: the compiler lays an A it is
    handed on one device out by columns, and an A laid out so by
    ``problem_on_mesh`` compiles to the same ops, so the recorder's rows
    do not depend on where A arrives."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import problems
    from repro.core.duality import gap_report
    from repro.core.partition import make_partition
    from repro.dist.sharding import auto_mesh

    mesh = auto_mesh(jax.make_mesh((CHIPS,), ("data",),
                                   devices=topo.devices))
    d, n = EPS_SHAPE
    part = make_partition(n, K)

    def row(a, y, x_parts, v_stack):
        rep = gap_report(problems.logistic_l2(a, y, 1.0), part, x_parts,
                         v_stack)
        return jnp.stack([rep.gap, rep.primal, rep.consensus_violation])

    def compiled(a_sharding):
        node = NamedSharding(mesh, P("data"))
        sds = jax.ShapeDtypeStruct
        return jax.jit(row).lower(
            sds((d, n), jnp.float32, sharding=a_sharding),
            sds((d,), jnp.float32), sds((K, n // K), jnp.float32,
                                        sharding=node),
            sds((K, d), jnp.float32, sharding=node)).compile()

    def ops(c):
        return [re.sub(r", (metadata|sharding|frontend_attributes)=\{[^}]*\}",
                       "", line) for line in c.as_text().splitlines()
                if "%" in line and " = " in line]

    by_columns = NamedSharding(mesh, P(None, "data"))
    arrives = compiled(None)
    assert arrives.input_shardings[0][0].is_equivalent_to(by_columns, 2)
    assert ops(arrives) == ops(compiled(by_columns))
