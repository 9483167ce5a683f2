"""`correct` has been shown to fail: the whole of a run, minus the look for
a chip, with the timed path broken underneath (harness/controls.py), at a size a test
run can hold.  On the chip the control ran at the cells' own sizes (PERF.md
section 2)."""
import argparse
import time

import pytest

from harness import cell
from harness import controls


def run(workload, tamper, seed=2**31 + 5):
    args = argparse.Namespace(
        workload=workload, seed=seed, seconds=0.3, trace=0, scale=0.01,
        rehearse_cpu=True, keep_trace=None, trace_queries=None)
    return cell.run_cell(args, time.perf_counter(), tamper,
                         say=lambda msg: None)


@pytest.mark.parametrize("workload", ["tpch-sf10.q6", "tpch-sf1.joins"])
def test_sound_run_is_correct_and_complete(workload):
    line = run(workload, None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == line["checks"]["compared"] > 0
    assert set(line["metrics"]) >= {"query_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line


@pytest.mark.parametrize("workload", ["tpch-sf10.q6", "tpch-sf1.joins"])
@pytest.mark.parametrize("tamper", sorted(controls.BY_NAME))
def test_control_and_planted_faults_come_out_not_correct(workload, tamper):
    line = run(workload, controls.BY_NAME[tamper]())
    assert line["correct"] is False
    assert line["checks"]["wrong_values"]["value"] > 0
