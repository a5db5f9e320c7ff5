"""Compile the served path for a TPU v5e chip that is described, not
attached: the TPU compiler refuses what interpret mode accepts (untiled
blocks, unlowerable primitives, programs over the chip's memory).

The topology is described inside a module fixture, never at import, so
that only the worker running this file loads the TPU library. Nothing
here runs on a device: results and times need the chip itself.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.ssd_scan import ssd_scan
from repro.models import Model

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no topology"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-2.7b"])
def test_ssd_scan_compiles_at_real_widths(one_chip, arch):
    cfg = get_config(arch)
    b, s = 1, 2 * cfg.ssm.chunk_size
    h, p, n = cfg.n_ssm_heads, cfg.ssm.head_dim, cfg.ssm.state_dim
    f32 = jnp.float32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((b, s, h, p), f32),
        jax.ShapeDtypeStruct((b, s, h), f32),
        jax.ShapeDtypeStruct((h,), f32),
        jax.ShapeDtypeStruct((b, s, n), f32),
        jax.ShapeDtypeStruct((b, s, n), f32)))
    compiled = jax.jit(
        lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C,
                                        chunk=cfg.ssm.chunk_size)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_prefill_compiles_at_granite_widths(one_chip):
    """granite-4.0-h-micro's attention prefill: GQA 32/8 heads of 64, the
    muP scale; the custom call is named for the trace's reader."""
    cfg = get_config("granite-4.0-h-micro")
    s, hd = 2048, cfg.resolved_head_dim
    f32 = jnp.float32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((1, cfg.n_heads, s, hd), f32),
        jax.ShapeDtypeStruct((1, cfg.n_kv_heads, s, hd), f32),
        jax.ShapeDtypeStruct((1, cfg.n_kv_heads, s, hd), f32)))
    text = jax.jit(
        lambda q, k, v: flash_prefill(q, k, v, scale=cfg.attn_scale)
    ).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%flash_prefill" in text


def test_granite_decode_step_fits_one_chip(one_chip):
    """The benchmark's cut of granite-4.0-h-micro (one period of ten
    layers, float32) with its 8 x 32768-position pool: the undonated decode
    step fits one chip, with room for what the window holds besides."""
    cfg = get_config("granite-4.0-h-micro")
    cfg = cfg.with_(n_layers=10, layer_types=cfg.layer_types[:10])
    model = Model(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=jnp.float32))
    cache = jax.eval_shape(lambda: model.init_cache(8, 32768,
                                                    dtype=jnp.float32))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    compiled = jax.jit(model.decode_step).lower(
        *_on(one_chip, (params, tokens, cache))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert mem.argument_size_in_bytes > 4.8e9   # 3.81 GB weights + pool
    assert total < 0.5 * V5E_HBM_BYTES, mem


def test_mamba2_decode_step_fits_one_chip(one_chip):
    """The engine's float32 decode step (8 slots x 1024 positions, no
    donation) at full width and depth fits one v5e chip's HBM."""
    cfg = get_config("mamba2-1.3b")
    model = Model(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=jnp.float32))
    cache = jax.eval_shape(lambda: model.init_cache(8, 1024,
                                                    dtype=jnp.float32))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    compiled = jax.jit(model.decode_step).lower(
        *_on(one_chip, (params, tokens, cache))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert mem.argument_size_in_bytes > 5e9      # 1.45 B float32 params
    assert total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "granite-4.0-h-micro"])
def test_prepared_decode_step_reads_its_weights_in_place(one_chip, arch):
    """With its dot-only weights rounded once (``Model.serving_params``,
    as the engine prepares them where the float32 dot is one bfloat16
    pass), the decode step converts and copies no weight: the float32
    weights' program converts each stack (and slices granite's into runs)
    in every call, into temporaries as large as the bfloat16 stacks."""
    cfg = get_config(arch)
    if cfg.layer_types:
        cfg = cfg.with_(n_layers=10, layer_types=cfg.layer_types[:10])
    model = Model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(8, 1024,
                                                    dtype=jnp.float32))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    got = {}
    for bf16 in (False, True):
        params = jax.eval_shape(lambda: model.serving_params(
            model.init(jax.random.PRNGKey(0), dtype=jnp.float32), bf16))
        compiled = jax.jit(model.decode_step).lower(
            *_on(one_chip, (params, tokens, cache))).compile()
        got[bf16] = (len(re.findall(r"(?:convert|slice)\(%params",
                                    compiled.as_text())),
                     compiled.memory_analysis().temp_size_in_bytes)
    (n_f32, temp_f32), (n_bf16, temp_bf16) = got[False], got[True]
    assert n_f32 >= 2 and n_bf16 == 0
    assert temp_bf16 < temp_f32 - 0.5e9
