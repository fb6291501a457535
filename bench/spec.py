"""Find a cell's files by name and build its inputs.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files themselves are ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/limits/<workload>.json`` (falling back to ``default.json``).  A new
cell is new files and one ``workloads`` entry: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits_dir = root / "bench" / "limits"
    own = limits_dir / f"{name}.json"
    limits = json.loads((own if own.exists()
                         else limits_dir / "default.json").read_text())

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), config, traffic, limits,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def load_reader(metric: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- building the system's inputs from the configuration file -----------------


def program_graph(config: dict):
    """The configuration's graph as the program's ``DnnGraph``."""
    from repro.core.ir import DnnGraph, Layer
    g = DnnGraph(config["graph"]["name"])
    for row in config["graph"]["layers"]:
        kw = {k: row[k] for k in ("B", "C", "H", "W", "K", "HK", "WK",
                                  "stride", "pad")}
        g.add(Layer(row["name"], row["kind"], **kw), row["preds"])
    return g


def program_constraints(config: dict):
    """``PimConstraints`` from the file; the design space must match it."""
    from repro.core.hardware import PimConstraints, sample_space
    cons = PimConstraints(**config["constants"])
    space = {k: list(v) for k, v in sample_space(cons).items()}
    if space != config["design_space"]:
        raise SystemExit("bench: the program's Table-II design space differs "
                         "from the configuration file's")
    return cons


def mapper_kwargs(config: dict) -> dict:
    return {k: config[k] for k in ("max_optim_iter", "lm_cap", "n_wr",
                                   "cap_units", "dl_max_group")}
