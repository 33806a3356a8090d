"""Operations and bytes the algorithm needs, from a configuration's
widths alone. This is the benchmark's yardstick: the program may change,
these functions do not follow it.

Parameter counting follows the 6*N*D convention: N counts the weights
that multiply activations (attention, MLP, norms, the output head); the
embedding table is a gather and is left out of N. Attention's quadratic
part is left out of the FLOPs, as in the usual model-FLOPs count.
"""
from __future__ import annotations


def layer_params(m: dict) -> int:
    """Weights of one dense pre-norm attention + SwiGLU block."""
    d, dh = m["d_model"], m["head_dim"]
    q = m["n_heads"] * dh
    kv = m["n_kv_heads"] * dh
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * m["d_ff"]
    return attn + mlp + 2 * d


def active_params(m: dict) -> int:
    """N of 6*N*D: every block, the final norm and the head."""
    return m["n_layers"] * layer_params(m) + m["d_model"] + \
        m["d_model"] * m["vocab"]


def train_flops(m: dict, tokens: int) -> float:
    """Forward and backward of ``tokens`` trained tokens; recomputation
    (remat) is not counted."""
    return 6.0 * active_params(m) * tokens


def infer_flops(m: dict, tokens: int) -> float:
    """One forward pass over ``tokens`` tokens (prompt or decoded)."""
    return 2.0 * active_params(m) * tokens


def kv_bytes_per_position(m: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of one cached position across all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * dtype_bytes


def decode_weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Weights one decode step has to read once: all but the embedding
    table, of which only the batch's rows are read (left out here)."""
    return active_params(m) * dtype_bytes


def decode_bytes(m: dict, steps: int, live_positions: int,
                 active_slots: int, dtype_bytes: int = 2) -> int:
    """Least HBM traffic of ``steps`` batched decode steps: every weight
    once a step, the keys and values of each active slot's live
    positions (those inside the window, up to and including the new
    token), and each active slot's new keys and values written once.
    ``live_positions`` and ``active_slots`` are summed over the steps."""
    kv = kv_bytes_per_position(m, dtype_bytes)
    return (steps * decode_weight_bytes(m, dtype_bytes)
            + kv * (live_positions + active_slots))


def live_positions(pos: int, window: int) -> int:
    """Positions a query at ``pos`` attends to under a sliding window."""
    return min(pos + 1, window) if window else pos + 1
