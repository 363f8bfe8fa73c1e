"""CPU rehearsal of each traffic mix end to end at a tiny size, the
generator's data keys, and the entry point's refusals."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from harness import traffic
from tiny import ROOT, tiny_run

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench", "mixes"))
               if f.endswith(".json"))


@pytest.mark.parametrize("mix", MIXES)
def test_mix_end_to_end(mix):
    run = tiny_run("cc_ingest_max", traffic=mix)
    res = run.result
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert run.numbers["unanswered"] == 0
    json.dumps(res)
    assert res["metrics"]["docs_per_s"]["value"] > 0
    assert run.window["verdict_p99_ms"] > 0
    # every document of the stream that was submitted got a verdict, in
    # batches no larger than the service's menu
    assert all(b["B"] <= 8 and len(b["doc_ids"]) <= b["B"]
               for b in run.batches)


def test_open_loop_phases_fix_the_work():
    """Bursts are phases of the one open-loop generator: each phase offers
    its own fixed number of requests inside its own part of the window,
    and the seed only orders the gaps."""
    mix = {"arrival": "open", "request_docs": 4,
           "phases": [{"share": 0.25, "rate_docs_per_s": 400},
                      {"share": 0.75, "rate_docs_per_s": 80}]}
    assert traffic.window_docs(mix, 10.0) == (250 + 150) * 4
    a = traffic._arrivals(mix, 1, 0.0, 10.0)
    b = traffic._arrivals(mix, 2**31 + 5, 0.0, 10.0)
    assert len(a) == len(b) == 400
    for x in (a, b):
        assert (np.diff(x) > 0).all() and x[0] == 0.0 and x[-1] < 10.0
        assert (x < 2.5).sum() == 250
    assert not np.array_equal(a, b)
    # the same gaps, but for the one each seed leaves at the phase's end
    ga, gb = (np.round(np.diff(x[:250]), 12) for x in (a, b))
    assert np.intersect1d(ga, gb).size >= 247


def test_refetch_copies_earlier_documents():
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 1000, (200, 16)).astype(np.uint32)
    lengths = rng.integers(4, 17, 200).astype(np.int32)
    stream = traffic.Stream(tokens.copy(), lengths.copy())
    k = traffic.refetch(stream, {"refetch_share": 0.1}, 2**31 + 3, 100)
    assert k == 10
    changed = np.flatnonzero((stream.tokens != tokens).any(axis=1))
    assert len(changed) <= k and (changed >= 100).all()
    for i in changed:
        assert any((stream.tokens[i] == stream.tokens[j]).all()
                   and stream.lengths[i] == stream.lengths[j]
                   for j in range(i))
    assert traffic.refetch(stream, {}, 1, 100) == 0


def _run_py(root: str, extra_env: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "cc_ingest_max", "--seed", str(2**31 + 1),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=root)


def test_refuses_without_a_tpu():
    p = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")


def test_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
