"""Compile-only checks for one described TPU v5e chip: the Pallas kernels
at real model widths and the full-width h2o-danube-1.8b decode step.
Nothing runs; the TPU compiler refuses here what the chip would refuse
(misaligned blocks, too much fast memory, a program that does not fit).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.serve import serving_model, serving_steps
from repro.models.common import tree_shapes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def shaped(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def on_chip(tree, sharding):
    return jax.tree.map(lambda x: shaped(x.shape, x.dtype, sharding), tree)


def assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen3-4b"])
def test_flash_attention_compiles(one_chip, arch):
    cfg = get_config(arch)
    S = 4096
    q = shaped((1, S, cfg.n_heads, cfg.dh), jnp.bfloat16, one_chip)
    kv = shaped((1, S, cfg.n_kv_heads, cfg.dh), jnp.bfloat16, one_chip)
    assert_kernel(flash_attention_fwd.lower(
        q, kv, kv, causal=True, window=cfg.window).compile())


def test_rmsnorm_compiles(one_chip):
    x = shaped((8, 2048, 2560), jnp.bfloat16, one_chip)
    w = shaped((2560,), jnp.float32, one_chip)
    assert_kernel(rmsnorm.lower(x, w).compile())


def test_ssd_scan_compiles_at_zamba2_widths(one_chip):
    cfg = get_config("zamba2-2.7b")
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    H = cfg.ssm_expand * cfg.d_model // P
    B, S = 1, 2048
    x = shaped((B, S, H, P), jnp.bfloat16, one_chip)
    dt = shaped((B, S, H), jnp.float32, one_chip)
    a_log = shaped((H,), jnp.float32, one_chip)
    bc = shaped((B, S, N), jnp.bfloat16, one_chip)
    assert_kernel(ssd_scan.lower(x, dt, a_log, bc, bc,
                                 chunk=cfg.ssm_chunk).compile())


def test_danube_decode_step_compiles_at_full_width(one_chip):
    """The decode program ``launch.serve`` builds, at 8 slots over a
    4096-token cache, all 24 layers in bfloat16, fits one chip."""
    cfg = get_config("h2o-danube-1.8b")
    slots, s_max = 8, 4096
    model = serving_model(cfg)
    _, decode = serving_steps(model, s_max)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    caches = on_chip(tree_shapes(model.cache_specs(slots, s_max),
                                 dtype=cfg.dtype), one_chip)
    compiled = decode.lower(params, caches,
                            shaped((slots, 1), jnp.int32, one_chip),
                            shaped((slots,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used           # one v5e chip holds 16 GB
