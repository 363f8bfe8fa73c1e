"""The harness finds everything by name, and BENCHMARK.json keeps to its
format (CPU, no JAX work)."""
from __future__ import annotations

import json
import os
import re

import pytest

from harness.spec import load_cell
from tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _write(path: str, text: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_cell_found_from_files_alone(tmp_path):
    root = str(tmp_path)
    bench = {
        "configs": [{"name": "toy_cfg", "file": "bench/configs/toy_cfg.json",
                     "source": "x", "reduced": [], "why": "x"}],
        "workloads": [{"name": "toy.cell", "config": "toy_cfg",
                       "traffic": "toy_mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "docs_per_s", "unit": "docs/s"},
                       {"name": "other", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "toy_metric.x", "unit": "ms",
                       "workloads": ["toy.cell"]}],
    }
    _write(f"{root}/BENCHMARK.json", json.dumps(bench))
    _write(f"{root}/bench/configs/toy_cfg.json", '{"prefill_docs": 7}')
    _write(f"{root}/bench/mixes/toy_mix.json", '{"arrival": "closed"}')
    _write(f"{root}/bench/limits/toy.cell.json", '{"sim_gap": {"max": 1}}')
    _write(f"{root}/bench/metrics/toy_metric.x.py",
           "def read(ctx):\n    return ctx * 2\n")
    cell = load_cell("toy.cell", root)
    assert cell.config == {"prefill_docs": 7}
    assert cell.mix == {"arrival": "closed"}
    assert cell.limits == {"sim_gap": {"max": 1}}
    assert [m["name"] for m in cell.end_to_end] == ["docs_per_s"]
    (entry, reader), = cell.per_layer
    assert entry["name"] == "toy_metric.x" and reader(21) == 42
    with pytest.raises(KeyError):
        load_cell("no_such_cell", root)


def test_benchmark_json_keeps_its_format():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"] for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    used = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["traffic"])
        used.add(w["config"])
        cell = load_cell(w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m, _ in cell.per_layer:
            assert m["moves"] in names
    assert used == cfgs
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in b["workloads"]}
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
