"""Compile-only checks for one described TPU v5e chip: the Pallas kernels
at real model widths, the full-width prefill and decode steps of
h2o-danube-1.8b and the decode of DeepSeek-V2-Lite's one-chip expert
share.
Nothing runs; the TPU compiler refuses here what the chip would refuse
(misaligned blocks, too much fast memory, a program that does not fit).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file."""
import math
import os
import re

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


def test_danube_prefill_compiles_at_full_width(one_chip):
    """The prefill program ``launch.serve`` builds for a 4096-token cache,
    on the chat traffic's longest prompt (2048 tokens), all 24 layers in
    bfloat16, fits one chip."""
    cfg = get_config("h2o-danube-1.8b")
    s_max, prompt = 4096, 2048
    model = serving_model(cfg)
    prefill, _ = serving_steps(model, s_max)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    compiled = prefill.lower(
        params, {"tokens": shaped((1, prompt), jnp.int32, one_chip)}).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2 ** 30, used   # one v5e chip holds 16 GiB


def _hlo_instructions(text):
    """(computation, name, opcode, dims, operand names) of every
    instruction of a compiled module's text, and the set of fused
    computations (the bodies of ``fusion`` instructions)."""
    out, fused, comp = [], set(), None
    head = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                      r"([\w\-]+)\(([^)]*)\)")
    for line in text.splitlines():
        m = head.match(line)
        if m:
            comp = m.group(1)
            continue
        if " fusion(" in line:          # array- or tuple-shaped
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
        m = inst.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            out.append((comp, m.group(1), m.group(3), dims,
                        re.findall(r"%([\w.\-]+)", m.group(4))))
    return out, fused


def test_danube_decode_writes_its_cache_in_place(one_chip):
    """At 16 slots over a 4096-token cache the decode program writes each
    layer's new K/V rows into the donated cache and reads the layer where
    it lies: no instruction outside a fusion copies or slices out a
    layer's K or V, no update writes that much, and the program allocates
    less than one layer's K besides the cache it aliases."""
    cfg = get_config("h2o-danube-1.8b")
    slots, s_max = 16, 4096
    model = serving_model(cfg)
    _, decode = serving_steps(model, s_max)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    caches = on_chip(tree_shapes(model.cache_specs(slots, s_max),
                                 dtype=cfg.dtype), one_chip)
    compiled = decode.lower(params, caches,
                            shaped((slots, 1), jnp.int32, one_chip),
                            shaped((slots,), jnp.int32, one_chip)).compile()
    layer = slots * s_max * cfg.n_kv_heads * cfg.dh   # one layer's K or V
    layer_bytes = layer * jnp.dtype(cfg.dtype).itemsize
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    insts, fused = _hlo_instructions(compiled.as_text())
    shape_of = {(c, n): d for c, n, _, d, _ in insts}
    moved = [n for c, n, op, d, _ in insts if c not in fused
             and op not in ("parameter", "get-tuple-element", "bitcast")
             and (math.prod(d) == layer
                  or op == "copy" and math.prod(d) >= layer)]
    written = [n for c, n, op, _, args in insts
               if op == "dynamic-update-slice"
               and math.prod(shape_of.get((c, args[1]), ())) >= layer]
    assert not moved, moved
    assert not written, written
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    fresh = (mem.output_size_in_bytes - mem.alias_size_in_bytes
             + mem.temp_size_in_bytes)
    assert fresh < layer_bytes, fresh


def test_latent_decode_writes_its_cache_in_place(one_chip):
    """DeepSeek-V2-Lite as the benchmark cuts it (8 of 64 experts held a
    layer), 16 slots over an 8192-token latent cache: the decode program
    reads each layer's latents where they lie and writes the step's rows
    into the donated cache (no instruction outside a fusion copies or
    slices out a layer's latents, no update writes that much), runs the
    held experts as the few-rows grouped-matmul kernel (``moe_stack_gmm``),
    and allocates less than one layer's latents besides the cache it
    aliases."""
    import dataclasses
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), experts_held=8)
    slots, s_max = 16, 8192
    model = serving_model(cfg)
    _, decode = serving_steps(model, s_max)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    caches = on_chip(tree_shapes(model.cache_specs(slots, s_max),
                                 dtype=cfg.dtype), one_chip)
    compiled = decode.lower(params, caches,
                            shaped((slots, 1), jnp.int32, one_chip),
                            shaped((slots,), jnp.int32, one_chip)).compile()
    layer = slots * s_max * cfg.kv_lora_rank          # one layer's latents
    layer_bytes = layer * jnp.dtype(cfg.dtype).itemsize
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    text = compiled.as_text()
    insts, fused = _hlo_instructions(text)
    shape_of = {(c, n): d for c, n, _, d, _ in insts}
    # the dense first layer's stacked cache is one layer's size: its row
    # writes (updates of the aliased cache) are checked by ``written``
    moved = [n for c, n, op, d, _ in insts if c not in fused
             and op not in ("parameter", "get-tuple-element", "bitcast",
                            "dynamic-update-slice")
             and (math.prod(d) == layer
                  or op == "copy" and math.prod(d) >= layer)]
    written = [n for c, n, op, _, args in insts
               if op == "dynamic-update-slice"
               and math.prod(shape_of.get((c, args[1]), ())) >= layer]
    assert not moved, moved
    assert not written, written
    assert "moe_stack_gmm" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    fresh = (mem.output_size_in_bytes - mem.alias_size_in_bytes
             + mem.temp_size_in_bytes)
    assert fresh < layer_bytes, fresh


@pytest.mark.parametrize("arch,slots,s_max,prompt", [
    ("deepseek-v2-lite", 16, 8192, 4096),
    ("deepseek-moe-16b", 16, 2048, 2048)])
def test_grouped_matmuls_read_the_expert_stacks_in_place(one_chip, arch,
                                                         slots, s_max,
                                                         prompt):
    """One chip's share of an 8-way expert-parallel group (8 of 64
    experts held a layer), in the decode program and a prefill: every
    grouped matmul takes its weights as the stacked parameter, through
    bitcasts only, and no instruction outside a fusion produces one
    layer's 8 held experts (a copy out of the stack). Decode's few rows
    run the ``moe_stack_gmm`` kernel, the prefill's many ``ragged-dot``."""
    import dataclasses
    cfg = dataclasses.replace(get_config(arch), experts_held=8)
    held, d, f = 8, cfg.d_model, cfg.moe_d_ff
    L = cfg.n_layers - cfg.first_dense_layers
    model = serving_model(cfg)
    prefill, decode = serving_steps(model, s_max)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    caches = on_chip(tree_shapes(model.cache_specs(slots, s_max),
                                 dtype=cfg.dtype), one_chip)
    kernels = {"decode": "moe_stack_gmm", "prefill": "ragged-dot"}
    programs = {
        "decode": decode.lower(params, caches,
                               shaped((slots, 1), jnp.int32, one_chip),
                               shaped((slots,), jnp.int32, one_chip)),
        "prefill": prefill.lower(
            params, {"tokens": shaped((1, prompt), jnp.int32, one_chip)})}
    for name, lowered in programs.items():
        insts, fused = _hlo_instructions(lowered.compile().as_text())
        by_name = {(c, n): (op, dims, args) for c, n, op, dims, args in insts}
        layer_copies = [n for c, n, op, dims, _ in insts if c not in fused
                        and dims in ((held, d, f), (held, f, d))]
        assert not layer_copies, (name, layer_copies)
        dots = [(c, n, args[-1]) for c, n, op, _, args in insts
                if op == "custom-call"
                and n.startswith(("ragged-dot", "moe_stack_gmm"))
                and "metadata" not in n]
        assert len(dots) >= 3, (name, dots)
        assert all(n.startswith(kernels[name]) for _, n, _ in dots), \
            (name, dots)
        for c, n, w in dots:
            op, dims, args = by_name[(c, w)]
            while op == "bitcast":
                op, dims, args = by_name[(c, args[0])]
            assert op in ("parameter", "get-tuple-element"), (name, n, op)
            assert dims in ((L, held, d, f), (L, held, f, d)), (name, n, dims)
