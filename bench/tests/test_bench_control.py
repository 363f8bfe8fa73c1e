"""The control (the reference in the program's place, in bfloat16) reads
not correct where the program reads correct (CPU, tiny size)."""
from __future__ import annotations

from reference import compare
from reference.control import control_outcomes
from tiny import tiny_run


def test_control_is_not_correct():
    run = tiny_run("cc_ingest_max")
    assert run.result["correct"], run.result["checks"]
    order = [b["doc_ids"] for b in run.batches]
    outs = control_outcomes(run.bitmaps, order, run.tau, k=4)
    nums = compare.compare(outs, len(run.bitmaps), run.bitmaps,
                           run.ref_admitted, run.tau)
    limits = {k: v for k, v in run.result["checks"].items()}
    ok, checks = compare.judge(nums, {
        k: ({"min": c["limit"]} if c["need"] == ">=" else
            {"max": c["limit"]}) for k, c in limits.items()})
    assert not ok, checks
    assert nums["sim_gap"] > checks["sim_gap"]["limit"]
