"""A run with the timed path broken underneath reads not correct (CPU,
tiny size), once for each fault of `harness.faults`."""
from __future__ import annotations

import pytest

from harness.faults import FAULTS
from tiny import tiny_run


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_not_correct(fault):
    run = tiny_run("cc_ingest_max", prepare=FAULTS[fault])
    assert not run.result["correct"], run.result["checks"]
