"""CPU rehearsal of every driver through the harness: a copy of the
benchmark gains tiny cells by data files alone (a throwaway configuration,
traffic mix and limits file each), and the harness finds them by name and
prints the result line the contract asks for. The system runs on its TPU
branch with the Pallas kernels in interpret mode."""

from __future__ import annotations

import pytest

from bench.tests import rehearsal
from bench.tests.rehearsal import DOCS, SERVE


def _keys(line):
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]


def test_serving_cell_end_to_end(tpu_branch, root):
    rc, line, err = rehearsal.run(root, SERVE, seconds=6.0)
    assert rc == 0 and line["correct"], (line, err)
    _keys(line)
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p99_ms", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert err.strip().splitlines()[-1].startswith("check max_gap:")


def test_serving_cell_traced_reports_host_per_layer_metrics(tpu_branch, root):
    rc, line, _ = rehearsal.run(root, SERVE, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"]
    _keys(line)
    # the device numbers need a TPU trace; the host numbers are all here
    assert {"decode_step_ms.gen", "admit_share.gen", "compiles.gen",
            "decode_mfu.gen"} <= set(line["metrics"])


def test_docs_cell_end_to_end(tpu_branch, root):
    rc, line, err = rehearsal.run(root, DOCS, seconds=4.0)
    assert rc == 0 and line["correct"], (line, err)
    _keys(line)
    assert set(line["metrics"]) == {"prompt_tokens_per_s", "setup_s"}
    rc, line, _ = rehearsal.run(root, DOCS, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"]
    _keys(line)
    assert {"prefill_ms.docs", "compiles.docs", "prefill_mfu.docs"} <= set(line["metrics"])


def test_drivers_read_their_controls(tpu_branch, root):
    """The readings a limit is set from: with its control on, the driver
    reports the control's number beside the system's, and judges the
    control. (How far apart the two lie is for test_bench_reference.py, at
    sizes where they separate.)"""
    res = rehearsal.run_calibration(root, SERVE, seconds=6.0)
    got = res.records["readings"]
    assert got["control"] >= 0 and got["program"] >= 0
    assert got["control"] == res.checks["max_gap"][0]


def test_control_in_the_systems_place_is_not_correct(tpu_branch, root):
    """The control goes through the harness's own comparison, limit and
    result line, and comes out as not correct."""
    rc, line, _ = rehearsal.run(root, SERVE, seconds=10.0, control=True)
    assert rc == 0 and line["correct"] is False, line
    assert line["checks"]["max_gap"]["value"] > line["checks"]["max_gap"]["limit"]
