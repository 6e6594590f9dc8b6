"""Each traffic loop, driven through the harness on the CPU at test size:
exact attempted and failed counts, every check passing, and the metrics
BENCHMARK.json names for the cell."""
import pytest

import bench_testlib as L
from bench import run


@pytest.mark.parametrize("cell", ["qrc28.planar", "qrc28.pallas"])
def test_serial_loop(tmp_path, cell):
    root = L.small_checkout(tmp_path)
    rc, line, err = L.run_cell(run.main, root, L.argv(cell, seconds=0.5))
    assert rc == 0, err
    assert line["correct"] is True
    assert line["failed"] == 0
    m = line["metrics"]
    assert set(m) == {"circuit_s", "setup_s"}
    # circuits run whole: the window closes at the first completion after
    # --seconds, and circuit_s is the window over the circuits in it
    assert line["attempted"] >= 1
    assert m["circuit_s"]["value"] * line["attempted"] >= 0.5
    assert list(line["checks"]) == ["state_err"]
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check state_err")
    # set-up and window each print their compile counts on an earlier line
    window = next(ln for ln in err.splitlines() if ln.startswith("window:"))
    assert "compiles=0 " in window


def test_clients_loop(tmp_path):
    root = L.small_checkout(tmp_path)
    rc, line, err = L.run_cell(run.main, root,
                               L.argv("qaoa20.clients", seconds=1.0))
    assert rc == 0, err
    assert line["correct"] is True
    assert line["failed"] == 0
    assert line["checks"]["failed"]["value"] == 0
    assert set(line["metrics"]) == {"evals_per_s", "p95_ms", "setup_s"}
    # every request sent in the window came back; all 16 clients are busy
    assert line["attempted"] >= 16
    evals = line["metrics"]["evals_per_s"]["value"]
    assert evals == pytest.approx(line["attempted"], rel=0.5)
    # the window runs from one batch completion to another: it holds
    # whole batches of 4, and lasts at least --seconds
    window = next(ln for ln in err.splitlines() if ln.startswith("window:"))
    fields = dict(f.split("=", 1) for f in window.split()[1:])
    completed, seconds = int(fields["completed"]), float(fields["seconds"])
    assert completed > 0 and completed % 4 == 0
    assert seconds >= 1.0
    assert evals == pytest.approx(completed / seconds)


def test_traced_run_reports_per_layer_metrics(tmp_path):
    root = L.small_checkout(tmp_path)
    rc, line, err = L.run_cell(run.main, root,
                               L.argv("qaoa20.clients", seconds=1.0, trace=1))
    assert rc == 0, err
    assert line["correct"] is True
    # the CPU has no device plane: only the program's own counters read
    assert set(line["metrics"]) == {"batch_fill_pct.serve",
                                    "queue_wait_ms.serve"}
    assert line["metrics"]["batch_fill_pct.serve"]["value"] == 100.0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
