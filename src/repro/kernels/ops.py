"""Keyword-argument entry the models call for the Pallas flash kernel.

The kernels compile for the TPU. Interpret mode (the Pallas body run in
Python, for checking on a CPU) is never chosen here: a caller that wants
it calls the kernel module with ``interpret=True``, as the kernel tests
do. Importing this module starts no JAX backend.
"""
from __future__ import annotations

from .flash_attention import flash_attention as _flash


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128):
    return _flash(q, k, v, causal, window, q_offset, block_q, block_k)

