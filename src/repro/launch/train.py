"""Fault-tolerant training driver.

Runs the step loop under a supervisor implementing the paper's recovery
story (DESIGN.md / train/ft.py): on (injected) node failure, restore the
latest checkpoint and rebuild the train step with the *degraded*
master-relay comm backend (paper phase-1 "linear"), run a recovery
window, then swap back to the fast backend -- demonstrating the comm-mode
degrade <-> restore cycle end to end. Stragglers are detected with an
EWMA step-time monitor.

CPU-scale by default (smoke configs); the same driver builds a larger
mesh (``--data``, ``--model-par``) when more devices exist.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
from jax.sharding import NamedSharding

from ..configs import get_config
from ..data.pipeline import SyntheticTokens, make_batch
from ..models.model import Model
from ..parallel import axes as A
from ..parallel.ops import ParallelConfig
from ..train import checkpoint as CKPT
from ..train import ft
from ..train.optim import OptConfig, Optimizer
from ..train.step import init_opt_state, make_train_step
from .cache import use_compile_cache


def build(cfg, mesh, pcfg, opt_cfg, global_batch):
    axes = A.MeshAxes.from_mesh(mesh)
    model = Model(cfg, axes, pcfg)
    opt = Optimizer(opt_cfg)
    step, ps = make_train_step(model, opt, mesh, global_batch)
    return model, opt, step, ps


def shard_tree(tree, mesh, pspecs):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, pspecs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--parallel-path", dest="path", default="mpignite")
    ap.add_argument("--backend", default="native")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--recovery-steps", type=int, default=5)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    n_dev = args.data * args.model_par
    if n_dev > len(jax.devices()):
        raise SystemExit(f"need {n_dev} devices, have {len(jax.devices())} "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    from .mesh import make_test_mesh
    mesh = make_test_mesh(data=args.data, model=args.model_par)
    pcfg = ParallelConfig(path=args.path, backend=args.backend,
                          sequence_parallel=args.model_par > 1,
                          remat="block")
    opt_cfg = OptConfig(lr_peak=args.lr, warmup_steps=5,
                        total_steps=args.steps)
    policy = ft.RecoveryPolicy(recovery_steps=args.recovery_steps)
    injector = ft.FailureInjector(frozenset(args.fail_at))
    detector = ft.StragglerDetector()
    sup = ft.SupervisorState()

    model, opt, step_fn, ps = build(cfg, mesh, pcfg, opt_cfg,
                                    args.global_batch)
    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = init_opt_state(model, opt, params)
    start = 0
    if args.resume and CKPT.latest_step(args.ckpt_dir) is not None:
        flat, meta, start = CKPT.load(args.ckpt_dir)
        params = CKPT.restore_sharded(params, flat_sub(flat, "params"),
                                      mesh, ps["params"])
        opt_state = CKPT.restore_sharded(opt_state, flat_sub(flat, "opt"),
                                         mesh, ps["opt"])
        print(f"[train] resumed from step {start}")
    params = shard_tree(params, mesh, ps["params"])
    opt_state = shard_tree(opt_state, mesh, ps["opt"])

    source = SyntheticTokens(cfg.vocab, args.seq, args.global_batch,
                             args.seed)
    ckpter = CKPT.AsyncCheckpointer(args.ckpt_dir)
    cur_backend = args.backend
    step = start
    losses = []
    while step < args.steps:
        try:
            batch = make_batch(cfg, source, step)
            batch = {k: jax.device_put(v, NamedSharding(
                mesh, model.batch_specs(args.global_batch, args.seq)[1][k]))
                for k, v in batch.items()}
            injector.check(step)
            t0 = time.time()
            with jax.set_mesh(mesh):
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch)
            dt = time.time() - t0
            if detector.observe(step, dt):
                sup.straggler_events += 1
                print(f"[ft] straggler at step {step}: {dt:.2f}s vs "
                      f"ewma {detector.ewma:.2f}s", flush=True)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % 5 == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['gnorm']):.3f} "
                      f"backend={cur_backend} {dt*1000:.0f}ms", flush=True)
            step += 1
            if step % args.ckpt_every == 0:
                ckpter.submit(step, {"params": params, "opt": opt_state},
                              {"arch": cfg.name})
            # restore fast backend after the recovery window
            want = sup.backend_for(step, args.backend, policy)
            if want != cur_backend:
                print(f"[ft] backend {cur_backend} -> {want}", flush=True)
                cur_backend = want
                pcfg2 = pcfg.replace(backend=want)
                model, opt, step_fn, ps = build(cfg, mesh, pcfg2, opt_cfg,
                                                args.global_batch)
        except ft.SimulatedFailure as e:
            print(f"[ft] {e}; restoring + degrading comm to "
                  f"{policy.degrade_backend}", flush=True)
            cur_backend = sup.on_failure(step, policy)
            pcfg2 = pcfg.replace(backend=cur_backend)
            model, opt, step_fn, ps = build(cfg, mesh, pcfg2, opt_cfg,
                                            args.global_batch)
            last = CKPT.latest_step(args.ckpt_dir)
            if last is not None:
                flat, _, step = CKPT.load(args.ckpt_dir)
                params = CKPT.restore_sharded(
                    model.init(jax.random.PRNGKey(args.seed)),
                    flat_sub(flat, "params"), mesh, ps["params"])
                opt_state = CKPT.restore_sharded(
                    init_opt_state(model, opt, params),
                    flat_sub(flat, "opt"), mesh, ps["opt"])
                print(f"[ft] restored step {step}", flush=True)
            else:
                print("[ft] no checkpoint yet; restarting from init",
                      flush=True)
                params = shard_tree(model.init(
                    jax.random.PRNGKey(args.seed)), mesh, ps["params"])
                opt_state = shard_tree(init_opt_state(model, opt, params),
                                       mesh, ps["opt"])
                step = 0
    ckpter.finish()
    print(f"[train] done: {len(losses)} steps, loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}, restarts={sup.restarts}, "
          f"stragglers={sup.straggler_events}")
    return 0


def flat_sub(flat: dict, prefix: str) -> dict:
    pl = prefix + CKPT.SEP
    return {k[len(pl):]: v for k, v in flat.items() if k.startswith(pl)}


if __name__ == "__main__":
    sys.exit(main())
