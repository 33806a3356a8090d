"""Mamba-2 SSD chunked scan for TPU (pl.pallas_call + BlockSpec tiling).

TPU adaptation of the GPU SSD kernel: the warp-level scan becomes the
matmul block decomposition -- per (batch, head) the sequence is walked
chunk by chunk on the innermost grid dimension; the (N x P) inter-chunk
state lives in VMEM scratch and persists across chunks, while all
intra-chunk work (decay matrix, C B^T scores, local outputs) is dense
(Q x Q)/(Q x N)/(Q x P) matmuls shaped for the MXU (Q=128, N=64, P=64
for zamba2-2.7b).

Grid: (B, H, S/Q), chunk index innermost. Inputs arrive pre-discretized
exactly like models.ssm.ssd_chunked: x (B,S,H,P), dt (B,S,H) (softplus
applied), a_log (H,), Bm/Cm (B,S,N) (groups already broadcast).

Layout. The TPU compiler takes a block only if each of its last two
dims is a multiple of (8, 128) or the whole array dim, so the wrapper
puts the sequence next to the feature dims: x and y as (B, H, S, P)
blocks of (Q, P); dt as a (B, H, S, 1) column and a (B, H, 1, S) row, so
the kernel has the chunk's decays in both orientations without a
transpose; B as (B, N, S) so C B^T and the state update are plain
matmuls. a_log is one whole (H,) array in scalar memory. The kernel
holds no 1-D value: the within-chunk cumulative sums are masked
reductions of (Q, Q) tiles. On the TPU the chunk Q must therefore be a
multiple of 128 or the whole sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dtc_ref, dtr_ref, alog_ref, bt_ref, c_ref, y_ref,
            state_ref, *, chunk: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    Q = chunk
    x = x_ref[0, 0].astype(jnp.float32)                 # (Q, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)          # (Q, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)          # (1, Q)
    a_h = -jnp.exp(jnp.full((1, 1), alog_ref[h], jnp.float32))
    bt = bt_ref[0].astype(jnp.float32)                  # (N, Q)
    cm = c_ref[0].astype(jnp.float32)                   # (Q, N)

    a_col = dt_col * a_h                                # (Q, 1) log-decays
    a_row = dt_row * a_h                                # (1, Q)
    row = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = row >= col
    # inclusive within-chunk cumsum, in both orientations
    cum_col = jnp.sum(jnp.where(tri, jnp.broadcast_to(a_row, (Q, Q)), 0.0),
                      axis=1, keepdims=True)            # (Q, 1)
    cum_row = jnp.sum(jnp.where(row <= col,
                                jnp.broadcast_to(a_col, (Q, Q)), 0.0),
                      axis=0, keepdims=True)            # (1, Q)
    total = jnp.sum(a_col, axis=0, keepdims=True)       # (1, 1)
    xdt = x * dt_col                                    # (Q, P)

    # ---- intra-chunk (lower-triangular decay kernel) ----
    # seg[i, j] = sum(a[j+1..i]) for i >= j
    L = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)     # (Q, Q)
    scores = jnp.dot(cm, bt, preferred_element_type=jnp.float32)
    y = jnp.dot(L * scores, xdt, preferred_element_type=jnp.float32)

    # ---- inter-chunk contribution from the carried state (N, P) ----
    y += jnp.exp(cum_col) * jnp.dot(cm, state_ref[...],
                                    preferred_element_type=jnp.float32)

    # ---- state update to chunk end ----
    wt = jnp.exp(total - cum_row) * bt                  # (N, Q)
    state_ref[...] = state_ref[...] * jnp.exp(total) + jnp.dot(
        wt, xdt, preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, Bm, Cm, *, chunk: int = 128,
             interpret: bool = False):
    """Returns y (B,S,H,P), matching models.ssm.ssd_chunked's y. The
    final state is recomputed by the XLA path when needed (prefill); the
    kernel emits y only (training hot path)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, "sequence must divide into SSD chunks"
    grid = (B, H, S // Q)

    xt = x.transpose(0, 2, 1, 3)                        # (B, H, S, P)
    dtt = dt.transpose(0, 2, 1)                         # (B, H, S)
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, N, Q), lambda b, h, c: (b, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt[..., None], dtt[:, :, None, :], a_log.astype(jnp.float32),
      Bm.transpose(0, 2, 1), Cm)
    return y.transpose(0, 2, 1, 3)
