"""Sharding-rule tests on host meshes (the dry-run itself runs the 512-dev
production meshes in a separate process; these tests run on 1 device)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from jax.sharding import set_mesh
from repro.distribution.sharding import clean_spec, constrain
from repro.launch.mesh import data_axes, make_host_mesh
from repro.launch.specs import (
    INPUT_SHAPES,
    batch_shardings,
    batch_specs,
    cache_shardings,
    cache_specs,
    config_for_shape,
    long_context_variant,
    params_shardings,
    params_specs,
)
from repro.models.zoo import Model


def test_constrain_is_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, "data", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_clean_spec_drops_unknown_axes():
    mesh = make_host_mesh()
    with set_mesh(mesh):
        spec = clean_spec(("pod", "data", "bogus"))
        assert spec == P(None, "data", None)
        spec2 = clean_spec((("pod", "data"), "model"))
        assert spec2 == P(("data",), "model")


def test_constrain_under_host_mesh():
    mesh = make_host_mesh()
    with set_mesh(mesh):
        @jax.jit
        def f(x):
            return constrain(x * 2, "data", "model")
        out = f(jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4, 4)))


def test_param_shardings_cover_every_leaf():
    mesh = make_host_mesh()
    for arch in ("smollm-135m", "deepseek-v3-671b", "mamba2-130m", "zamba2-7b"):
        cfg = get_config(arch).reduced()
        model = Model(cfg)
        specs = params_specs(model)
        with set_mesh(mesh):
            sh = params_shardings(specs, cfg, mesh)
        n_leaves = len(jax.tree.leaves(specs))
        n_shardings = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
        assert n_leaves == n_shardings


def test_batch_shardings_divisibility_guard():
    mesh = make_host_mesh()
    spec = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
    sh = batch_shardings(spec, mesh)
    # batch=1 divisible by 1 on host mesh: sharded spec exists, no crash
    assert sh["tokens"] is not None


def test_long_context_variant_rules():
    # SSM/hybrid unchanged; attention archs get the window
    assert long_context_variant(get_config("mamba2-130m")).sliding_window is None
    assert long_context_variant(get_config("zamba2-7b")).sliding_window is None
    assert long_context_variant(get_config("yi-9b")).sliding_window == 8192
    assert long_context_variant(get_config("deepseek-v3-671b")).sliding_window == 8192
    # base configs never carry the window
    assert get_config("yi-9b").sliding_window is None


def test_input_shape_matrix():
    assert set(INPUT_SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    s = INPUT_SHAPES["train_4k"]
    assert (s.seq_len, s.global_batch, s.kind) == (4096, 256, "train")
    s = INPUT_SHAPES["long_500k"]
    assert (s.seq_len, s.global_batch, s.kind) == (524288, 1, "decode")


def test_batch_specs_per_modality():
    shape = INPUT_SHAPES["train_4k"]
    vlm = get_config("internvl2-26b")
    specs = batch_specs(vlm, shape)
    assert specs["tokens"].shape == (256, 4096 - vlm.num_frontend_tokens)
    assert specs["patch_embeds"].shape == (256, vlm.num_frontend_tokens, vlm.d_model)
    enc = get_config("seamless-m4t-large-v2")
    specs = batch_specs(enc, shape)
    assert specs["src_embeds"].shape == (256, 1024, enc.d_model)


def test_cache_specs_sub_quadratic_sizes():
    """long_500k: the SSM cache is O(1) in seq len; the windowed dense cache
    is O(window); a full cache would be O(500k)."""
    shape = INPUT_SHAPES["long_500k"]
    ssm_cfg = config_for_shape(get_config("mamba2-130m"), shape)
    dense_cfg = config_for_shape(get_config("qwen3-1.7b"), shape)
    m_ssm = Model(ssm_cfg)
    m_dense = Model(dense_cfg)
    c_ssm = cache_specs(m_ssm, shape)
    c_dense = cache_specs(m_dense, shape)
    ssm_bytes = sum(np.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(c_ssm))
    dense_bytes = sum(np.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(c_dense))
    full_estimate = dense_cfg.num_layers * 2 * shape.seq_len * dense_cfg.num_kv_heads * dense_cfg.resolved_head_dim * 2
    assert dense_bytes < 0.05 * full_estimate     # window 8192 << 524288
    assert ssm_bytes < 64 * 1024 * 1024           # state cache is tiny


def test_cache_shardings_build(tmp_path):
    mesh = make_host_mesh()
    shape = INPUT_SHAPES["decode_32k"]
    for arch in ("yi-9b", "deepseek-v3-671b", "zamba2-7b", "seamless-m4t-large-v2"):
        cfg = config_for_shape(get_config(arch), shape)
        model = Model(cfg)
        cs = cache_specs(model, shape)
        with set_mesh(mesh):
            sh = cache_shardings(cs, cfg, mesh)
        assert len(jax.tree.leaves(cs)) == len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))


def test_data_axes():
    mesh = make_host_mesh()
    assert data_axes(mesh) == ("data",)
