"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes each Pallas body on CPU)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention, flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


ATTN_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window, dtype
    (2, 128, 128, 4, 2, 64, True, 0, jnp.float32),
    (1, 256, 256, 4, 4, 64, False, 0, jnp.float32),
    (2, 256, 256, 8, 2, 128, True, 64, jnp.float32),
    (1, 128, 384, 2, 1, 64, True, 0, jnp.float32),      # chunked prefill
    (1, 192, 192, 2, 2, 64, True, 0, jnp.float32),      # non-multiple of 128
    (2, 128, 128, 4, 1, 64, True, 0, jnp.bfloat16),
    (1, 128, 128, 2, 2, 96, True, 48, jnp.bfloat16),    # odd head dim
]


@pytest.mark.parametrize("case", ATTN_CASES,
                         ids=[f"attn{i}" for i in range(len(ATTN_CASES))])
def test_flash_attention_matches_oracle(case):
    B, Sq, Sk, Hq, Hkv, D, causal, window, dtype = case
    q = rand(KEY, (B, Sq, Hq, D), dtype)
    k = rand(jax.random.fold_in(KEY, 1), (B, Sk, Hkv, D), dtype)
    v = rand(jax.random.fold_in(KEY, 2), (B, Sk, Hkv, D), dtype)
    qoff = Sk - Sq
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              q_offset=qoff, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=qoff)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=tol, rtol=tol)


def test_flash_attention_grads_flow():
    q = rand(KEY, (1, 128, 2, 64), jnp.float32)
    k = rand(jax.random.fold_in(KEY, 1), (1, 128, 2, 64), jnp.float32)
    v = rand(jax.random.fold_in(KEY, 2), (1, 128, 2, 64), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, True, 0, 0, 128, 128, True).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: ref.attention_ref(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


SSD_CASES = [
    (2, 256, 4, 64, 64, 128, jnp.float32),
    (1, 128, 2, 32, 16, 64, jnp.float32),
    (2, 512, 3, 16, 8, 128, jnp.float32),
    (1, 256, 2, 64, 32, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES,
                         ids=[f"ssd{i}" for i in range(len(SSD_CASES))])
def test_ssd_scan_matches_sequential_oracle(case):
    B, S, H, P, N, Q, dtype = case
    x = rand(KEY, (B, S, H, P), dtype) * 0.5
    dt = jax.nn.softplus(rand(jax.random.fold_in(KEY, 1), (B, S, H),
                              jnp.float32))
    a_log = rand(jax.random.fold_in(KEY, 2), (H,), jnp.float32) * 0.3
    Bm = rand(jax.random.fold_in(KEY, 3), (B, S, N), dtype) * 0.5
    Cm = rand(jax.random.fold_in(KEY, 4), (B, S, N), dtype) * 0.5
    y = ssd_scan(x, dt, a_log, Bm, Cm, chunk=Q, interpret=True)
    want, state_ref = ref.ssd_ref(x, dt, a_log, Bm, Cm)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(y.astype(np.float32),
                               want.astype(np.float32), atol=tol, rtol=tol)
    # the XLA chunk decomposition must agree too (and provides the state)
    y2, state = ssd_chunked(x, dt, a_log, Bm, Cm, chunk=Q)
    np.testing.assert_allclose(y2.astype(np.float32),
                               want.astype(np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(state, state_ref, atol=tol, rtol=tol)


@pytest.mark.parametrize("shape,dtype", [
    ((4, 96, 160), jnp.bfloat16),
    ((2, 33, 256), jnp.float32),
    ((1, 1, 64), jnp.float32),
    ((512, 128), jnp.bfloat16),
])
def test_rmsnorm_matches_oracle(shape, dtype):
    x = rand(KEY, shape, dtype)
    w = rand(jax.random.fold_in(KEY, 9), (shape[-1],), jnp.float32)
    out = rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=2e-2, rtol=2e-2)


def test_kernel_ops_import_starts_no_backend():
    """Interpret mode is the caller's choice, so importing the kernels
    probes no device (a process may import them before it picks one)."""
    code = ("import repro.kernels.ops, repro.kernels.rmsnorm\n"
            "import repro.kernels.ssd_scan\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
