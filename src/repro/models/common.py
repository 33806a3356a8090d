"""Shared model-configuration & parameter machinery for every registry architecture.

One ``ModelConfig`` covers the dense / MoE / hybrid-SSM / xLSTM / VLM / audio
families; per-arch files in ``repro/configs`` fill it in. Parameters are
described by ``ParamSpec`` (global padded shape + PartitionSpec + init rule),
from which each distribution path derives what it needs: GSPMD shardings,
shard_map in_specs, local shard shapes, and abstract ShapeDtypeStructs.

GQA head layout under TP
------------------------
Query heads are padded *per KV group* so that (a) every model shard holds an
equal number of heads and (b) each query head's KV head lives on the same
shard (no cross-shard attention reductions). KV heads are replicated to
``kv_eff = replicated_kv_heads(kv, tp)``; each effective KV head serves
``gq = ceil(n_q / kv_eff)`` query-head slots, of which the trailing ones may
be padding (zero-initialized, zero-masked). See ``gqa_layout``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel import axes as A


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling (DeepSeek-V2's ``rope_scaling``, type "yarn"):
    the rotary frequencies ramp from interpolated (divided by ``factor``)
    to extrapolated between the correction dimensions that ``beta_fast``
    and ``beta_slow`` rotations over ``original_max`` positions give,
    and attention scores are scaled by ``mscale(factor, mscale_all_dim)``
    squared (see ``layers.yarn_inv_freq`` and ``yarn_mscale``)."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    kind: str                       # dense | moe | hybrid | xlstm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # --- attention ---
    head_dim: int = 0               # 0 => d_model // n_heads
    causal: bool = True             # False => encoder-only (hubert)
    window: int = 0                 # sliding-window size; 0 => full attention
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0           # fraction of head_dim that is rotated
    rope_yarn: YaRN | None = None   # YaRN-scaled rotary frequencies
    # --- latent attention (MLA, DeepSeek-V2); kv_lora_rank > 0 selects it
    kv_lora_rank: int = 0           # width of the cached latent c
    qk_nope_head_dim: int = 0       # per-head query/key part without rotary
    qk_rope_head_dim: int = 0       # rotary part; one key shared by all heads
    v_head_dim: int = 0
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0               # per-expert hidden size
    dense_residual: bool = False    # arctic: dense FFN in parallel with MoE
    first_dense_layers: int = 0     # deepseek: leading dense layers
    capacity_factor: float = 1.25
    experts_held: int = 0           # routed experts this layer holds; 0: all
    norm_topk_prob: bool = True     # renormalise the top-k router weights
    router_aux_coef: float = 0.01
    # --- hybrid (zamba2-style Mamba2 + shared attention) ---
    ssm_state: int = 0              # N (d_state)
    ssm_head_dim: int = 64          # P (head dim of SSD)
    ssm_expand: int = 2
    ssm_chunk: int = 128
    attn_every: int = 0             # one shared attn+MLP block per this many layers
    # --- xLSTM ---
    slstm_every: int = 0            # every k-th layer is sLSTM (0 => none)
    proj_factor: float = 2.0        # mLSTM up-projection factor
    # --- VLM ---
    cross_attn_every: int = 0       # a cross-attn layer per this many layers
    n_image_tokens: int = 0
    vision_d: int = 0
    # --- frontend ---
    input_mode: str = "tokens"      # tokens | frames (precomputed embeddings stub)
    # --- misc ---
    act: str = "swiglu"             # swiglu | gelu
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attn_impl: str = "xla"          # xla | pallas
    init_std: float = 0.02

    # ---- derived ----
    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_encoder(self) -> bool:
        return not self.causal

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    def validate(self) -> "ModelConfig":
        if self.kind == "moe":
            assert self.n_experts > 0 and self.top_k > 0 and self.moe_d_ff > 0
            if not 0 <= self.experts_held <= self.n_experts:
                raise ValueError("experts_held must lie in [0, n_experts]")
        if self.mla and (self.window or self.attn_impl != "xla"
                         or self.n_kv_heads != self.n_heads):
            raise ValueError("latent attention runs full-context on the "
                             "xla path, one key head per query head")
        if self.kind == "hybrid":
            assert self.ssm_state > 0 and self.attn_every > 0
        if self.n_heads % max(self.n_kv_heads, 1):
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        return self


@dataclasses.dataclass(frozen=True)
class GQALayout:
    """Head bookkeeping under a given TP degree (see module docstring)."""
    n_q: int            # true query heads
    n_kv: int           # true KV heads
    n_q_pad: int        # stored query-head slots (multiple of tp)
    kv_eff: int         # stored KV heads incl. replication (multiple of tp)
    gq: int             # query-head slots per effective KV head
    rep: int            # replication factor kv_eff / ceil-padded kv

    def q_real_mask(self) -> np.ndarray:
        """(n_q_pad,) bool -- which stored query-head slots are real."""
        gq0 = self.n_q // self.n_kv           # true q heads per true kv head
        mask = np.zeros(self.n_q_pad, bool)
        for j in range(self.kv_eff):          # effective kv head j
            orig = j // self.rep
            if orig >= self.n_kv:
                continue                      # padded kv head: all slots dead
            start_in_group = (j % self.rep) * self.gq
            n_real = min(max(gq0 - start_in_group, 0), self.gq)
            mask[j * self.gq:j * self.gq + n_real] = True
        return mask

    def kv_source(self) -> np.ndarray:
        """(kv_eff,) -> original kv head index feeding each stored head
        (padded kv heads point at head 0 but their q slots are dead)."""
        return np.minimum(np.arange(self.kv_eff) // self.rep, self.n_kv - 1)


def gqa_layout(n_q: int, n_kv: int, tp: int) -> GQALayout:
    kv_eff = A.replicated_kv_heads(n_kv, tp)
    rep = max(kv_eff // n_kv, 1) if n_kv < kv_eff else 1
    # when n_kv >= tp, kv_eff == pad_to(n_kv, tp) and rep == 1
    if n_kv >= tp:
        rep = 1
    gq = max(math.ceil(n_q / kv_eff), 1)
    n_q_pad = kv_eff * gq
    assert n_q_pad % tp == 0 and kv_eff % tp == 0
    return GQALayout(n_q, n_kv, n_q_pad, kv_eff, gq, rep)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    pspec: P = P()
    init: str = "normal"      # normal | zeros | ones | scaled
    fan_in: int = 0           # for init == "scaled": std = init_std/sqrt(2L)
    col_mask: np.ndarray | None = None  # zero-mask applied to the last dim
    row_mask: np.ndarray | None = None  # zero-mask applied to dim -2
    dtype: Any = None         # None => the model compute dtype

    def instantiate(self, key, std: float, dtype) -> jax.Array:
        dtype = self.dtype or dtype
        if self.init == "zeros":
            w = jnp.zeros(self.shape, dtype)
        elif self.init == "ones":
            w = jnp.ones(self.shape, dtype)
        else:
            s = std if self.init == "normal" else std / math.sqrt(
                2.0 * max(self.fan_in, 1))
            w = (jax.random.normal(key, self.shape, jnp.float32) * s
                 ).astype(dtype)
        if self.col_mask is not None:
            w = w * jnp.asarray(self.col_mask, dtype)
        if self.row_mask is not None:
            m = jnp.asarray(self.row_mask, dtype)
            w = w * m[..., :, None]
        return w


def head_mask(layout: GQALayout, dh: int) -> np.ndarray:
    """(n_q_pad*dh,) column mask zeroing padded query-head slots."""
    return np.repeat(layout.q_real_mask(), dh).astype(np.float32)


def tree_instantiate(specs, key, std: float, dtype):
    """Materialize a full (global) parameter pytree from ParamSpecs."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [s.instantiate(k, std, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def tree_pspecs(specs):
    return jax.tree.map(lambda s: s.pspec, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def tree_shapes(specs, axes: A.MeshAxes | None = None, dtype=jnp.bfloat16):
    """ShapeDtypeStructs (global shapes) for abstract lowering; if ``axes`` is
    given, shapes are validated to shard evenly."""
    def leaf(s: ParamSpec):
        if axes is not None:
            A.local_shape(s.shape, s.pspec, axes)  # raises if indivisible
        return jax.ShapeDtypeStruct(s.shape, s.dtype or dtype)
    return jax.tree.map(leaf, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def tree_local_shapes(specs, axes: A.MeshAxes):
    return jax.tree.map(
        lambda s: A.local_shape(s.shape, s.pspec, axes), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


# Convenience constructors -----------------------------------------------------

def dense_col(d_in: int, d_out: int, *, mask=None) -> ParamSpec:
    """Column-parallel weight (out dim sharded over model, FSDP on in dim)."""
    return ParamSpec((d_in, d_out), P(A.DATA_AXIS, A.MODEL_AXIS),
                     col_mask=mask)


def dense_row(d_in: int, d_out: int, *, fan_in: int = 0, mask=None) -> ParamSpec:
    """Row-parallel weight (in dim sharded over model, FSDP on out dim)."""
    return ParamSpec((d_in, d_out), P(A.MODEL_AXIS, A.DATA_AXIS),
                     init="scaled" if fan_in else "normal", fan_in=fan_in,
                     row_mask=mask)


def replicated(*shape, init="ones") -> ParamSpec:
    return ParamSpec(tuple(shape), P(), init=init)


def stacked(n: int, spec: ParamSpec) -> ParamSpec:
    """Prepend an unsharded layer dimension for lax.scan stacking."""
    return dataclasses.replace(
        spec, shape=(n,) + spec.shape, pspec=P(None, *spec.pspec))
