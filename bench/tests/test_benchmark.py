"""BENCHMARK.json: every cell resolves its configuration, traffic and
metric files by name, and the file keeps the contract's shape."""
import json
import os
import re

import pytest

from harness import main

ROOT = main.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = main.load_benchmark()


def test_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_resolves_and_reports(cell):
    got = main.resolve(BENCH, cell)
    assert got["cell"]["chips"] == 1
    e2e = [m["name"] for m in got["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert got["per_layer"]
    for m in got["end_to_end"] + got["per_layer"]:
        assert callable(main.metric_module(m["name"]).read)
    fam = main.family_module(got["spec"]["family"])
    assert hasattr(fam, "System") and hasattr(fam, "check")
    assert got["traffic"]["loop"] in ("closed", "open")
    assert got["spec"]["check"]
    assert set(got["spec"]["check"]) <= {"logp_err_geomean", "logp_err"}


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            e2e = [x["name"] for x in main.resolve(BENCH, cell)["end_to_end"]]
            assert m["moves"] in e2e, (m["name"], cell)


def test_config_files_lie_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("bench/") and os.path.exists(os.path.join(ROOT, f))
        with open(os.path.join(ROOT, f)) as fh:
            spec = json.load(fh)
        assert spec["name"] in {c["name"] for c in BENCH["configs"]}
