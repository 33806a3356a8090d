"""Training driver: the program's sharded train step on the cell's mesh.

Set-up builds the step with ``repro.launch.train.build`` and the
parameters and optimizer state directly on the mesh (the program's own
``Model.init`` and ``init_opt_state``, jitted with the step's shardings,
so no device holds a whole copy). It then drives that same step through
its first ``check.steps`` steps with the window's own feed, keeping what
the comparison needs, and hands it on to the window. The window runs
whole steps until ``--seconds`` have passed; the rate is every token of
those steps over the time to the last one's end. After it the program's
state is freed and the reference (``reference.py``, sharded over the same
devices by the compiler) trains the same weights on the same batches.
"""
from __future__ import annotations

import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.chip import reference, traffic
from benchmarks.chip.drivers.serve import program_config
from benchmarks.chip.runtime import peak_bytes

F32 = jnp.float32


def _norms(tree) -> list[float]:
    return [float(x) for x in jax.jit(reference.leaf_norms)(tree)]


def build(cell):
    """The program's step for the cell's mesh, and its initialisers jitted
    with the step's shardings. Nothing here depends on the seed, so one
    build serves every seed of a process."""
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build as build_step
    from repro.parallel.ops import ParallelConfig
    from repro.train.optim import OptConfig
    from repro.train.step import init_opt_state

    conf, mix = cell.config, cell.traffic
    dep = conf["deployment"]
    cfg = program_config(conf)
    mesh = make_test_mesh(data=dep["data"], model=dep["model"])
    pcfg = ParallelConfig(path=dep["path"], backend=dep["backend"],
                          sequence_parallel=dep["sequence_parallel"],
                          remat=dep["remat"],
                          microbatches=dep["microbatches"])
    model, opt, step, ps = build_step(cfg, mesh, pcfg,
                                      OptConfig(**conf["optimizer"]),
                                      int(mix["global_batch"]))
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    return types.SimpleNamespace(
        step=step, mesh=mesh,
        batch_sharding=NamedSharding(mesh, ps["batch"]["tokens"]),
        init=jax.jit(model.init, out_shardings=ns(ps["params"])),
        init_opt=jax.jit(lambda p: init_opt_state(model, opt, p),
                         out_shardings=ns(ps["opt"])))


def first_steps(b, cell, seed: int, span):
    """Parameters and optimizer state for ``seed``, made on the mesh (no
    device holds a whole copy), driven through the checked first steps by
    the window's own call and feed. Returns the state the window goes on
    with and the readings the comparison needs."""
    conf, mix = cell.config, cell.traffic
    vocab = conf["model"]["vocab"]
    b1 = conf["optimizer"]["b1"]
    key = reference.weights_key(seed)

    def feed(k):
        with span("batch_put"):
            return {"tokens": jax.device_put(
                traffic.token_batch(mix, seed, k, vocab), b.batch_sharding)}

    params = b.init(key)
    opt_state = b.init_opt(params)
    losses, g1 = [], None
    batch = feed(0)
    with jax.set_mesh(b.mesh):
        for k in range(conf["check"]["steps"]):
            params, opt_state, metrics = b.step(params, opt_state, batch)
            batch = feed(k + 1)
            losses.append(float(metrics["loss"]))
            if k == 0:
                g1 = [x / (1 - b1) for x in _norms(opt_state["m"])]
        p0 = b.init(key)
        change = _norms(jax.tree.map(lambda a, b: a - b.astype(F32),
                                     opt_state["master"], p0))
        del p0
    state = types.SimpleNamespace(params=params, opt_state=opt_state,
                                  batch=batch, k=k, feed=feed)
    return state, {"losses": losses, "g1": g1, "change": change}


def run(cell, seed: int, seconds: float, ctx) -> dict:
    conf, mix = cell.config, cell.traffic
    check = conf["check"]
    tokens_per_step = int(mix["global_batch"]) * int(mix["seq"])
    b = build(cell)
    s, prog = first_steps(b, cell, seed, ctx.tracer.span)
    c_setup, hits_setup = ctx.compiles.snapshot()
    ctx.log(f"first steps: loss {prog['losses']}")

    setup_done = time.perf_counter()
    ctx.tracer.start()
    t0 = time.perf_counter()
    steps = 0
    with jax.set_mesh(b.mesh):
        while time.perf_counter() - t0 < seconds:
            s.k += 1
            with ctx.tracer.span("train_step"):
                s.params, s.opt_state, metrics = b.step(s.params, s.opt_state,
                                                        s.batch)
            s.batch = s.feed(s.k + 1)
            loss = float(metrics["loss"])
            steps += 1
    elapsed = time.perf_counter() - t0
    c_window = ctx.compiles.snapshot()[0]
    ctx.tracer.stop()
    peak = peak_bytes(jax.local_devices()[:cell.chips])
    ctx.log(f"window: {steps} steps in {elapsed:.3f} s, last loss {loss}")
    finite = bool(np.isfinite(loss))
    devices = list(b.mesh.devices.flat)
    del s, metrics, b
    gc.collect()

    t1 = time.perf_counter()
    ref = reference_run(cell, seed, devices, check["steps"])
    ctx.log(f"reference: loss {ref['losses']}, "
            f"{time.perf_counter() - t1:.3f} s")
    checks = compare(check, prog, ref)
    correct = finite and all(c["value"] <= c["limit"]
                             for c in checks.values())
    return {"setup_end": setup_done,
            "metrics": {"train_tokens_per_s":
                        steps * tokens_per_step / elapsed},
            "record": {"model": conf["model"], "steps": steps,
                       "tokens": steps * tokens_per_step,
                       "window_s": elapsed},
            "checks": checks, "correct": correct,
            "attempted": steps, "failed": 0 if finite else steps,
            "memory_peak_bytes": peak,
            "compiles": {"setup": c_setup, "setup_hits": hits_setup,
                         "window": c_window - c_setup,
                         "after": ctx.compiles.snapshot()[0] - c_window}}


def readings(prog: dict, ref: dict) -> dict:
    """The numbers a run can compare: the widest per-step loss gap, and
    by the worst leaf the first gradient's norm (as the optimizer got it)
    and the norm of the parameters' change over the checked steps.
    Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's) move by round-off alone and are
    left out of the change."""
    loss = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    med = float(np.median(ref["g1"]))
    keep = [i for i, g in enumerate(ref["g1"]) if g >= 1e-3 * med]
    return {"loss_gap": loss,
            "grad_gap": reference.worst_leaf_gap(prog["g1"], ref["g1"]),
            "change_gap": reference.worst_leaf_gap(
                [prog["change"][i] for i in keep],
                [ref["change"][i] for i in keep])}


def compare(check: dict, prog: dict, ref: dict) -> dict:
    """Each reading that the configuration gives a limit, beside it."""
    return {k: {"value": v, "limit": check[k + "_limit"]}
            for k, v in readings(prog, ref).items() if k + "_limit" in check}


def _spread(shape, n: int, stacked: bool) -> P:
    """Split a leaf's largest dimension that ``n`` divides over the
    devices (never a stacked leaf's layer dimension)."""
    dims = [i for i in range(1 if stacked else 0, len(shape))
            if shape[i] % n == 0]
    if not dims:
        return P()
    best = max(dims, key=lambda i: shape[i])
    return P(*[("x" if i == best else None) for i in range(len(shape))])


def reference_run(cell, seed: int, devices, steps: int, quant=None,
                  rows=None) -> dict:
    """The reference's losses, first clipped gradient norms per leaf and
    change norms after ``steps`` AdamW steps, from the weights the seed
    gives, over ``devices``. ``rows`` keeps only the first rows of every
    batch (a fault reading), ``quant`` computes in the control's
    precision."""
    conf, mix = cell.config, cell.traffic
    m, opt = conf["model"], conf["optimizer"]
    key = reference.weights_key(seed)
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)
    shapes = jax.eval_shape(
        lambda k: reference.make_weights(m, k), key)
    wsh = jax.tree_util.tree_map_with_path(
        lambda kp, s: NamedSharding(mesh, _spread(
            s.shape, n, str(getattr(kp[0], "key", "")) == "blocks")), shapes)
    tsh = NamedSharding(mesh, P("x", None))

    def weights0(k):
        w = reference.make_weights(m, k)
        return jax.tree.map(lambda x: x.astype(F32), w)

    weights0 = jax.jit(weights0, out_shardings=wsh)
    zeros = jax.jit(lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, F32),
                                         shapes), out_shardings=wsh)

    def one(w, m1, m2, toks, t):
        # one sequence a device at a time: the batch's mean loss is the
        # mean of the slices' means, its gradient the mean of theirs
        parts = toks.reshape((-1, n) + toks.shape[1:])

        def part(acc, tk):
            tk = jax.lax.with_sharding_constraint(tk, tsh)
            loss, g = jax.value_and_grad(reference.mean_nll)(w, tk, m, quant)
            return jax.tree.map(jnp.add, acc, (loss, g)), None
        zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, w))
        (loss, g), _ = jax.lax.scan(part, zero, parts)
        k = parts.shape[0]
        loss, g = loss / k, jax.tree.map(lambda x: x / k, g)
        w, m1, m2, g, _ = reference.adamw_step(opt, w, g, m1, m2, t)
        return w, m1, m2, loss, reference.leaf_norms(g)

    step_fn = jax.jit(one, donate_argnums=(0, 1, 2),
                      out_shardings=(wsh, wsh, wsh, None, None))
    w, m1, m2 = weights0(key), zeros(), zeros()
    losses, g1 = [], None
    for k in range(steps):
        toks = traffic.token_batch(mix, seed, k, m["vocab"])
        if rows is not None:
            toks = toks[:rows]
        toks = jax.device_put(toks, NamedSharding(mesh, P()))
        w, m1, m2, loss, gn = step_fn(w, m1, m2, toks, jnp.int32(k + 1))
        losses.append(float(loss))
        if k == 0:
            g1 = [float(x) for x in gn]
    del m1, m2
    w0 = weights0(key)
    change = _norms(jax.tree.map(lambda a, b: a - b, w, w0))
    del w, w0
    return {"losses": losses, "g1": g1, "change": change}
