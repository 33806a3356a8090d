"""What a run is asked to do: the cell, its configuration, its traffic
and its metrics, all found by name under the benchmark's directory.

Nothing here imports JAX or the program, so tests and the command line
can load a cell without touching a device.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
REPO_ROOT = CHIP_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, plus its "name"
    traffic: dict           # the traffic file, plus its "name"
    end_to_end: list[dict]  # this cell's end-to-end metric entries
    per_layer: list[dict]   # this cell's per-layer metric entries
    dirs: list[Path]        # where traffic/, metrics/, drivers/ are looked up


def _reports(entry: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in e2e_names


def find(dirs: list[Path], sub: str, name: str) -> Path:
    """``<dir>/<sub>/<name>`` in the first of ``dirs`` that has it."""
    for d in dirs:
        if (d / sub / name).is_file():
            return d / sub / name
    raise FileNotFoundError(f"{sub}/{name} in none of {[str(d) for d in dirs]}")


def load_cell(workload: str, root: Path = REPO_ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``. Its traffic,
    metric readers and driver are looked up under the benchmark's
    ``paths`` in ``root``, then beside this file."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    dirs = [root / p for p in bench["paths"]] + [CHIP_DIR]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    config["name"] = conf_entry["name"]
    traffic = json.loads(find(dirs, "traffic", f"{w['traffic']}.json")
                         .read_text())
    traffic["name"] = w["traffic"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                dirs)


def _load(path: Path, mod_name: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(dirs: list[Path], metric: str):
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    name = "chip_metric_" + metric.replace(".", "_").replace("-", "_")
    return _load(find(dirs, "metrics", f"{metric}.py"), name).read


def load_driver(dirs: list[Path], job: str):
    """The ``run(cell, seed, seconds, ctx)`` of ``drivers/<job>.py``."""
    return _load(find(dirs, "drivers", f"{job}.py"), f"chip_driver_{job}").run


def peaks_for(device_kind: str, chip_dir: Path = CHIP_DIR) -> dict:
    """The published peaks of one chip of ``device_kind``. A kind that is
    not in ``peaks.json`` is an error, never a default."""
    table = json.loads((chip_dir / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]
