"""The correctness check fails what it must: the control (the reference
one precision below the configuration's, in the program's place) and each
fault a cell can have, planted under an otherwise whole run on the CPU."""
import pytest

import bench_testlib as L
from bench import control


def _run(tmp_path, cell, fault=None):
    root = L.small_checkout(tmp_path)
    argv = ["--workload", cell, "--seed", "4294967323", "--seconds", "0.5"]
    if fault:
        argv += ["--fault", fault]
    rc, line, err = L.run_cell(control.main, root, argv)
    assert rc == 0, err
    return line


@pytest.mark.parametrize("cell,check", [("qrc28.planar", "state_err"),
                                        ("qaoa20.clients", "zz_gap")])
def test_control_is_not_correct(tmp_path, cell, check):
    line = _run(tmp_path, cell)
    c = line["checks"][check]
    assert line["correct"] is False
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell,fault", [
    ("qrc28.planar", "unchanged"), ("qrc28.planar", "altered"),
    ("qaoa20.clients", "unchanged"), ("qaoa20.clients", "half_batch"),
    ("qaoa20.clients", "altered")])
def test_fault_is_not_correct(tmp_path, cell, fault):
    line = _run(tmp_path, cell, fault)
    assert line["correct"] is False


@pytest.mark.parametrize("cell,check", [("qrc28.planar", "state_err"),
                                        ("qaoa20.clients", "zz_gap")])
def test_nan_output_is_not_correct(tmp_path, cell, check):
    # one NaN amplitude, or one NaN term of each row, where it is produced
    line = _run(tmp_path, cell, "nan")
    assert line["correct"] is False
    assert line["checks"][check]["value"] == "nan"


def test_unpatched_program_is_correct(tmp_path):
    from bench import run
    root = L.small_checkout(tmp_path)
    rc, line, err = L.run_cell(run.main, root, L.argv("qrc28.planar"))
    assert rc == 0, err
    assert line["correct"] is True
