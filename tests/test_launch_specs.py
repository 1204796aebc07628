"""Launch-layer unit tests that don't need the 512-device mesh: input specs,
collective-byte parsing, replica-group materialization, Opts tagging."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Initialize the backend on the single real device BEFORE anything imports
# repro.launch.dryrun (which prepends the 512-device XLA flag for new
# processes; jax locks the device count on first init, so this pin wins).
jax.devices()

from repro.configs.base import ARCHS, SHAPES, get_config
from repro.launch.hlo_analysis import _replica_groups, _spans_pods
from repro.launch.specs import cache_specs, input_specs, params_specs


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_shapes(arch, shape_name):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        pytest.skip("documented long-context skip")
    batch = input_specs(cfg, shape)
    b = shape.global_batch
    assert batch["tokens"].dtype == jnp.int32
    assert batch["tokens"].shape[0] == b
    if shape.kind == "decode":
        assert batch["tokens"].shape == (b, 1)
    if shape.kind == "train":
        assert batch["labels"].shape == batch["tokens"].shape
    if cfg.family == "vlm" and shape.kind != "decode":
        assert batch["patches"].shape[1] == cfg.num_prefix_tokens


@pytest.mark.parametrize("arch", ["h2o_danube3_4b", "llama4_maverick_400b",
                                  "zamba2_7b", "xlstm_125m"])
def test_decode_cache_is_bounded_for_subquadratic(arch):
    """long_500k decode state must NOT scale with the 524288-token context."""
    cfg = get_config(arch)
    cache = cache_specs(cfg, 1, SHAPES["long_500k"].seq_len)
    import jax
    total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))
    assert total < 3e9, f"{arch}: decode state {total/1e9:.1f} GB"


def test_replica_groups_iota_format():
    g = _replica_groups("replica_groups=[2,4]<=[8]")
    np.testing.assert_array_equal(np.asarray(g),
                                  [[0, 1, 2, 3], [4, 5, 6, 7]])
    # transposed iota: [4,2]<=[2,4]T(1,0) -> groups of stride-4 pairs
    g = _replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)")
    np.testing.assert_array_equal(np.asarray(g),
                                  [[0, 4], [1, 5], [2, 6], [3, 7]])


def test_replica_groups_explicit_format():
    g = _replica_groups("replica_groups={{0,1},{2,3}}")
    assert g == [[0, 1], [2, 3]]


def test_spans_pods():
    # pod size 4: group [0..3] stays, [2,6] crosses
    assert not _spans_pods("replica_groups=[2,4]<=[8]", 4)
    assert _spans_pods("replica_groups={{2,6},{3,7}}", 4)
    assert _spans_pods("no groups here", 4)  # conservative default


def test_opts_tag():
    from repro.launch import dryrun  # noqa: deferred heavy import
    # NOTE: importing dryrun sets XLA_FLAGS for NEW processes only; this
    # process already initialized jax with 1 device.
    assert dryrun.Opts().tag() == "baseline"
    t = dryrun.Opts(attn_bf16=True, microbatches=4, moe_grouped=True).tag()
    assert "attnbf16" in t and "mb4" in t and "moegrp" in t


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache sits
    at the fixed .jax_cache/ in the checkout. Restores the config, so no
    test compiles into a cache."""
    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert got == after == str(compile_cache.CACHE_DIR)
        assert compile_cache.CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CACHE_DIR.parent / "chip_smoke.py").exists()
    else:
        assert got == env_dir and after == before


def test_chip_peaks_are_keyed_by_device_kind():
    from repro.launch import mesh

    assert mesh.chip_peaks(mesh.PRODUCTION_DEVICE_KIND)["flops_bf16"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        mesh.chip_peaks("cpu")
