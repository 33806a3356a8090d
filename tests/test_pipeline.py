"""GPipe on PeerComm.shift: pipelined forward (and autodiff backward)
must equal the unpipelined stack. Runs in a subprocess (needs 4 forced
host devices)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.comm import PeerComm
from repro.parallel.pipeline import gpipe, stack_stages

S, L, M, B, D = 4, 8, 6, 2, 16          # stages, layers, microbatches
key = jax.random.PRNGKey(0)
Ws = jax.random.normal(key, (L, D, D), jnp.float32) * (0.5 / D ** 0.5)
xs = jax.random.normal(jax.random.fold_in(key, 1), (M, B, D), jnp.float32)

def layer(w, x):
    return jnp.tanh(x @ w)

# ---- reference: plain stacked forward ----
def ref_forward(Ws, xs):
    ys = []
    for m in range(M):
        x = xs[m]
        for l in range(L):
            x = layer(Ws[l], x)
        ys.append(x)
    return jnp.stack(ys)

want = ref_forward(Ws, xs)

# ---- pipelined: stages over a 4-way pipe axis ----
mesh = jax.make_mesh((S,), ("pipe",))
comm = PeerComm.world("pipe", S)
staged = stack_stages(Ws, S)            # (S, L/S, D, D)

def stage_fn(params, x):
    for i in range(L // S):
        x = layer(params[i], x)
    return x

def run(staged, xs):
    # local shard keeps a size-1 leading `pipe` dim; drop it
    out = gpipe(comm, stage_fn, staged[0], xs, n_stages=S)
    # outputs live on the last stage; broadcast makes them replicated
    return comm.broadcast(out, root=S - 1)

piped = jax.jit(jax.shard_map(
    run, mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P(),
    check_vma=False))
with jax.set_mesh(mesh):
    got = piped(staged, xs)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=1e-5, rtol=1e-5)
print("fwd ok")

# ---- backward through the pipeline ----
def loss_pipe(staged, xs):
    out = gpipe(comm, stage_fn, staged[0], xs, n_stages=S)
    # per-device local loss: shard_map AD seeds every device, so the
    # differentiated objective is the sum over stages -- which equals the
    # true loss because only the last stage banks non-zero outputs.
    return jnp.sum(out ** 2)

gfn = jax.jit(jax.shard_map(
    jax.grad(loss_pipe), mesh=mesh, in_specs=(P("pipe"), P()),
    out_specs=P("pipe"), check_vma=False))

def loss_ref(Ws):
    return jnp.sum(ref_forward(Ws, xs) ** 2)

gref = jax.grad(loss_ref)(Ws)
with jax.set_mesh(mesh):
    gpiped = gfn(staged, xs)
np.testing.assert_allclose(np.asarray(gpiped).reshape(L, D, D),
                           np.asarray(gref), atol=1e-4, rtol=1e-4)
print("bwd ok")
print("PIPELINE OK")
"""


@pytest.mark.timeout(600)
def test_gpipe_subprocess():
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=550,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "PIPELINE OK" in r.stdout
