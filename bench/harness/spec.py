"""Find a cell's configuration, traffic mix, limits and metrics by name.

Everything that belongs to one configuration, mix or per-layer metric sits
in a file of its own and is found from `BENCHMARK.json` alone:

    bench/configs/<config>.json     one deployment (sizes, corpus, guarantees)
    bench/mixes/<traffic>.json      parameters for the one traffic generator
    bench/limits/<workload>.json    limits of the numbers `correct` compares
    bench/metrics/<metric>.py       one reducer per per-layer metric

Adding a cell therefore adds files and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable

__all__ = ["Cell", "load_cell", "bench_dir", "repo_root"]


def bench_dir() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def repo_root() -> str:
    return os.path.dirname(bench_dir())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: str
    mix: dict
    limits: dict
    end_to_end: list[dict]                     # metric entries of this cell
    per_layer: list[tuple[dict, Callable]]     # (entry, reader)


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _load_reader(path: str, name: str) -> Callable:
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, root: str | None = None,
              benchmark: dict | None = None) -> Cell:
    """The cell `workload` of `<root>/BENCHMARK.json` with all its files.

    Raises FileNotFoundError or KeyError when the cell or a file it names
    is missing."""
    root = root or repo_root()
    bench = benchmark or _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    here = os.path.join(root, "bench")
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    mix = _read_json(os.path.join(here, "mixes", w["traffic"] + ".json"))
    limits = _read_json(os.path.join(here, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    layers = [(m, _load_reader(os.path.join(here, "metrics",
                                            m["name"] + ".py"), m["name"]))
              for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic=w["traffic"], mix=mix, limits=limits,
                end_to_end=e2e, per_layer=layers)
