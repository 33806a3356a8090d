"""Mesh-axis vocabulary + padding rules shared by both distribution paths.

Axis names are fixed across the framework:

- ``pod``   : cross-pod data parallelism (multi-pod meshes only).
- ``data``  : in-pod axis used for batch DP *and* FSDP parameter sharding
              (ZeRO-3: the FSDP dim of every weight is sharded here).
- ``model`` : tensor/expert parallelism (Megatron-style TP; MoE experts and
              the vocab dimension also live here).

Hardware-alignment padding (recorded in DESIGN.md; ``Model.n_params``
leaves the waste these introduce out of the useful parameter count):

- attention heads are padded up to a multiple of the TP degree
  (e.g. arctic-480b 56 -> 64 query heads on a 16-way model axis);
- KV heads are *replicated* up to the TP degree when kv < tp
  (qwen3: 8 kv heads on 16 shards => each head stored twice);
- the vocabulary is padded to a multiple of ``VOCAB_ALIGN * tp``.
"""
from __future__ import annotations

import dataclasses
import math

from jax.sharding import PartitionSpec as P

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"

VOCAB_ALIGN = 32  # vocab padded to a multiple of tp * VOCAB_ALIGN


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Static description of the mesh a program is being built for."""
    data: int = 1
    model: int = 1
    pod: int = 1

    @property
    def dp_total(self) -> int:
        return self.data * self.pod

    @property
    def n_devices(self) -> int:
        return self.data * self.model * self.pod

    @property
    def batch_axes(self) -> tuple[str, ...]:
        """Mesh axes a global-batch dimension is sharded over."""
        return (POD_AXIS, DATA_AXIS) if self.pod > 1 else (DATA_AXIS,)

    @staticmethod
    def from_mesh(mesh) -> "MeshAxes":
        shape = dict(mesh.shape)
        return MeshAxes(data=shape.get(DATA_AXIS, 1),
                        model=shape.get(MODEL_AXIS, 1),
                        pod=shape.get(POD_AXIS, 1))


def pad_to(n: int, mult: int) -> int:
    return (n + mult - 1) // mult * mult


def padded_heads(n_heads: int, tp: int) -> int:
    """Query heads padded so each model shard holds an equal head count."""
    return pad_to(n_heads, tp)


def replicated_kv_heads(n_kv: int, tp: int) -> int:
    """Effective stored KV heads: replicate each KV head ceil(tp/n_kv) times
    when tp > n_kv so the cache shards evenly; otherwise pad to tp multiple."""
    if n_kv >= tp:
        return pad_to(n_kv, tp)
    rep = math.ceil(tp / n_kv)
    return pad_to(n_kv * rep, tp)


def padded_vocab(vocab: int, tp: int) -> int:
    return pad_to(vocab, VOCAB_ALIGN * tp)


def batch_spec(axes: MeshAxes, *trailing) -> P:
    """PartitionSpec for a tensor whose leading dim is the global batch."""
    if axes.pod > 1:
        return P((POD_AXIS, DATA_AXIS), *trailing)
    return P(DATA_AXIS, *trailing)


def divisible(n: int, d: int, what: str) -> int:
    if n % d:
        raise ValueError(f"{what}={n} not divisible by {d}")
    return n


def local_dim(size: int, spec_entry, axes: MeshAxes) -> int:
    """Size of one shard of a dimension sharded per ``spec_entry``."""
    if spec_entry is None:
        return size
    names = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
    denom = 1
    for name in names:
        denom *= {POD_AXIS: axes.pod, DATA_AXIS: axes.data,
                  MODEL_AXIS: axes.model}[name]
    return divisible(size, denom, "sharded dim")


def local_shape(shape: tuple[int, ...], spec: P, axes: MeshAxes
                ) -> tuple[int, ...]:
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    return tuple(local_dim(s, e, axes) for s, e in zip(shape, entries))
