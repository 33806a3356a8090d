import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import/initialization: jax locks the device count
# on first backend init; the dry-run (and only the dry-run) runs with 512
# placeholder host devices so the production meshes can be built.

import argparse          # noqa: E402
import gzip              # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from ..configs import SHAPES, get_config, skip_reason, cell_plan  # noqa: E402
from ..core.comm import cost_log                                  # noqa: E402
from ..models.model import Model                                  # noqa: E402
from ..parallel import axes as A                                  # noqa: E402
from ..parallel.ops import ParallelConfig                         # noqa: E402
from ..train.optim import OptConfig, Optimizer                    # noqa: E402
from ..train.step import (init_opt_state, make_decode_step,       # noqa: E402
                          make_prefill_step, make_train_step)
from . import hlo_analysis as H                                   # noqa: E402
from .mesh import make_production_mesh                            # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline inputs from the compiled artifact. No arrays are ever
allocated (ShapeDtypeStruct end to end); `memory_analysis()` proves the
program fits 16 GB/chip and `cost_analysis()` + the trip-count-aware HLO
parser (hlo_analysis.py) provide FLOPs/bytes/collective terms.

One cell per process (the --all driver spawns subprocesses): XLA compile
state for 512-way SPMD programs is large, and process isolation makes the
sweep resumable (existing artifact => skipped)."""


def _sds_with(tree_sds, tree_ps, mesh):
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        tree_sds, tree_ps)


def opt_for(arch: str, lean: bool = False) -> Optimizer:
    # arctic-480b: Adam state (2 fp32 moments) would need ~7.5 GB/chip on
    # top of master+grads at 256 chips; Adafactor's factored stats fit.
    # ``lean`` additionally drops the fp32 master (T5X-style bf16 train).
    name = "adafactor" if arch == "arctic-480b" else "adamw"
    return Optimizer(OptConfig(name=name, master=not lean))


def build_lowerable(arch: str, shape_name: str, mesh, path: str,
                    backend: str, remat: str = "full",
                    seq_override: int | None = None,
                    compression: str = "none", microbatches: int = 1,
                    quant_gather: bool = False, fsdp: bool = True,
                    lean_opt: bool = False):
    """Returns (lower_fn, meta). lower_fn() -> lowered."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    axes = A.MeshAxes.from_mesh(mesh)
    pcfg = ParallelConfig(path=path, backend=backend,
                          sequence_parallel=(shape.step != "decode"),
                          remat=remat, grad_compression=compression,
                          microbatches=microbatches, fsdp=fsdp,
                          microbatch_dtype="bfloat16" if lean_opt
                          else "float32",
                          weight_gather_quant="int8" if quant_gather
                          else "none")
    model = Model(cfg, axes, pcfg)
    seq = seq_override or shape.seq_len
    gb = shape.global_batch

    params_sds = _sds_with(model.param_shapes(),
                           model.pspecs, mesh)

    if shape.step == "train":
        opt = opt_for(arch, lean=lean_opt)
        step, ps = make_train_step(model, opt, mesh, gb,
                                   use_compression=(compression == "int8"))
        opt_sds_raw = jax.eval_shape(
            lambda p: init_opt_state(model, opt, p, compression == "int8"),
            params_sds)
        opt_sds = _sds_with(opt_sds_raw, ps["opt"], mesh)
        batch_raw, batch_ps = model.batch_specs(gb, seq)
        batch_sds = _sds_with(batch_raw, batch_ps, mesh)
        tokens = gb * seq

        def lower():
            return step.lower(params_sds, opt_sds, batch_sds)
        mf = model.model_flops(tokens, train=True)
    elif shape.step == "prefill":
        step = make_prefill_step(model, mesh, gb, s_max=seq)
        batch_raw, batch_ps = model.batch_specs(gb, seq)
        batch_sds = _sds_with(batch_raw, batch_ps, mesh)

        def lower():
            return step.lower(params_sds, batch_sds)
        mf = model.model_flops(gb * seq, train=False)
    else:  # decode
        step = make_decode_step(model, mesh, gb, s_max=seq)
        from ..models.common import tree_shapes, tree_pspecs
        cache_specs = model.cache_specs(gb, seq)
        # per-leaf dtypes come from the specs (KV bf16, recurrent states f32)
        cache_sds = _sds_with(tree_shapes(cache_specs, axes),
                              tree_pspecs(cache_specs), mesh)
        bsp = model._bspec(gb)
        from jax.sharding import PartitionSpec as P
        tok_sds = jax.ShapeDtypeStruct((gb, 1), jnp.int32,
                                       sharding=NamedSharding(mesh, P(bsp, None)))
        pos_sds = jax.ShapeDtypeStruct((gb,), jnp.int32,
                                       sharding=NamedSharding(mesh, P(bsp)))

        def lower():
            return step.lower(params_sds, cache_sds, tok_sds, pos_sds)
        mf = model.model_flops(gb, train=False)

    meta = {"arch": arch, "shape": shape_name, "step": shape.step,
            "path": path, "backend": backend, "remat": remat,
            "seq": seq, "global_batch": gb,
            "n_devices": axes.n_devices,
            "n_params": model.n_params(),
            "n_params_active": model.n_params(active_only=True),
            "model_flops": mf}
    return lower, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool, path: str,
             backend: str, out_path: str, remat: str = "full",
             save_hlo: bool = False, compression: str = "none",
             mesh_shape: str = "", microbatches: int = 1,
             quant_gather: bool = False, fsdp: bool = True,
             lean_opt: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = skip_reason(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    if skip:
        art = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skip": skip}
        _write(out_path, art)
        return art
    if mesh_shape:
        dims = tuple(int(x) for x in mesh_shape.split(","))
        names = ("pod", "data", "model")[-len(dims):]
        from .mesh import _mk
        mesh = _mk(dims, names)
        mesh_name = "custom" + mesh_shape.replace(",", "x")
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    lower_fn, meta = build_lowerable(arch, shape_name, mesh, path, backend,
                                     remat, compression=compression,
                                     microbatches=microbatches,
                                     quant_gather=quant_gather, fsdp=fsdp,
                                     lean_opt=lean_opt)
    t0 = time.time()
    with cost_log() as clog:
        with jax.set_mesh(mesh):
            lowered = lower_fn()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    ndev = meta["n_devices"]
    summary = H.summarize(txt, ndev)
    sched = H.collective_schedule(txt, ndev)
    sched.sort(key=lambda r: -r["wire_bytes"])

    analytic = {}
    for rec in clog:
        k = f"{rec.op}:{rec.backend}"
        analytic[k] = analytic.get(k, 0) + rec.bytes_per_device

    art = {
        **meta, "mesh": mesh_name, "skip": None,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "xla_cost": {"flops_static": ca.get("flops", -1.0),
                     "bytes_static": ca.get("bytes accessed", -1.0)},
        "hlo": summary.as_dict(),
        "collective_schedule_top": sched[:40],
        "analytic_comm_bytes": analytic,
        "hlo_text_bytes": len(txt),
    }
    _write(out_path, art)
    if save_hlo:
        with gzip.open(out_path.replace(".json", ".hlo.txt.gz"), "wt") as f:
            f.write(txt)
    return art


def _write(path: str, art: dict):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(art, f, indent=1)


def artifact_name(arch, shape, mesh_name, path, backend, remat="full",
                  compression="none", extra: str = ""):
    tag = f"{arch}__{shape}__{mesh_name}__{path}__{backend}"
    if remat != "full":
        tag += f"__remat-{remat}"
    if compression != "none":
        tag += f"__comp-{compression}"
    if extra:
        tag += f"__{extra}"
    return tag + ".json"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--parallel-path", dest="path",
                    choices=["mpignite", "gspmd"], default="mpignite")
    ap.add_argument("--backend", default="native",
                    choices=["native", "ring", "linear"])
    ap.add_argument("--remat", default="full",
                    choices=["none", "block", "full"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--out", default="artifacts")
    ap.add_argument("--all", action="store_true",
                    help="run the full cell matrix in subprocesses")
    ap.add_argument("--timeout", type=float, default=2400)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--force", action="store_true")
    # ---- perf-iteration knobs (section Perf of EXPERIMENTS.md) ----
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh dims, e.g. 256,1 (data,model)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--quant-gather", action="store_true",
                    help="ZeRO++-style int8 FSDP weight all-gathers")
    ap.add_argument("--no-fsdp", dest="fsdp", action="store_false",
                    help="resident weights (serving layout)")
    ap.add_argument("--lean-opt", action="store_true",
                    help="master-less Adafactor + bf16 grad accumulation")
    args = ap.parse_args(argv)

    if args.all:
        return _run_all(args)
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    extra = []
    if args.mesh_shape:
        extra.append("mesh" + args.mesh_shape.replace(",", "x"))
    if args.microbatches > 1:
        extra.append(f"mb{args.microbatches}")
    if args.quant_gather:
        extra.append("wgq8")
    if not args.fsdp:
        extra.append("nofsdp")
    if args.lean_opt:
        extra.append("lean")
    for mesh_name in meshes:
        out_path = os.path.join(args.out, artifact_name(
            args.arch, args.shape, mesh_name, args.path, args.backend,
            args.remat, args.compression, "-".join(extra)))
        art = run_cell(args.arch, args.shape, mesh_name == "multi",
                       args.path, args.backend, out_path, args.remat,
                       args.save_hlo, args.compression, args.mesh_shape,
                       args.microbatches, args.quant_gather, args.fsdp,
                       args.lean_opt)
        status = f"SKIP({art['skip']})" if art.get("skip") else \
            f"ok compile={art['compile_s']}s " \
            f"mem={art['memory']['peak_bytes_est']/2**30:.2f}GiB"
        print(f"[dryrun] {args.arch} x {args.shape} x {mesh_name} "
              f"x {args.path}/{args.backend}: {status}", flush=True)
    return 0


def _run_all(args) -> int:
    cells = cell_plan()
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    failures = []
    for cell in cells:
        for mesh_name in meshes:
            out_path = os.path.join(args.out, artifact_name(
                cell["arch"], cell["shape"], mesh_name, args.path,
                args.backend, args.remat, args.compression))
            if os.path.exists(out_path) and not args.force:
                print(f"[dryrun] resume-skip {out_path}", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", cell["arch"], "--shape", cell["shape"],
                   "--mesh", mesh_name, "--parallel-path", args.path,
                   "--backend", args.backend, "--remat", args.remat,
                   "--compression", args.compression, "--out", args.out]
            t0 = time.time()
            try:
                r = subprocess.run(cmd, timeout=args.timeout,
                                   capture_output=True, text=True)
                ok = r.returncode == 0
                if not ok:
                    failures.append((cell, mesh_name,
                                     r.stderr.strip()[-2000:]))
                print(f"[all] {cell['arch']} x {cell['shape']} x "
                      f"{mesh_name}: {'OK' if ok else 'FAIL'} "
                      f"({time.time()-t0:.0f}s)", flush=True)
            except subprocess.TimeoutExpired:
                failures.append((cell, mesh_name, "timeout"))
                print(f"[all] {cell['arch']} x {cell['shape']} x "
                      f"{mesh_name}: TIMEOUT", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for cell, mesh_name, err in failures:
            print(f"--- {cell['arch']} x {cell['shape']} x {mesh_name}\n"
                  f"{err}\n")
        return 1
    print("all cells OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
