"""The main path compiled for a TPU v5e that is described, not attached.

jaxlib ships the TPU compiler, so these compile ``chip_smoke.py``'s
phase (a) at full size — L2 logistic regression on 400,000 x 2,000, K=16 —
for one chip and for a four-chip 2x2 host. They catch what only the TPU
compiler refuses: a program over the chip's memory, a layout it cannot
tile, a collective the comm plan did not promise. A compile that passes is
not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import numpy as np
import pytest

EPS_SHAPE = (400_000, 2_000)   # chip_smoke.py phase (a): samples x features
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
