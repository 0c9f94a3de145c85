"""Whole runs of a CPU-sized cell with the harness's look for a chip
skipped: a sound run is correct, one whose answers are altered where they
are produced is not; and the command refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from harness import main

ROOT = main.ROOT
TINY = "m3vit-test"


@pytest.fixture
def tiny_cell(monkeypatch):
    bench = main.load_benchmark()
    bench["configs"].append({"name": TINY, "source": "tests",
                             "file": "bench/tests/tiny.json", "reduced": [],
                             "why": "CPU size"})
    bench["workloads"].append({"name": TINY + ".saturated", "config": TINY,
                               "traffic": "saturated", "chips": 1,
                               "why": "CPU size"})
    monkeypatch.setattr(main, "load_benchmark", lambda root=ROOT: bench)
    monkeypatch.setattr(main, "devices", lambda chips: jax.devices())
    monkeypatch.setattr(main, "enable_cache", lambda: "off")

    def run(seed=11):
        args = type("A", (), dict(workload=TINY + ".saturated", seed=seed,
                                  seconds=1.0, trace=0))
        return main.run(args, 0.0)
    return run


def test_sound_run_is_correct(tiny_cell):
    result, lines = tiny_cell()
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "check"
    assert set(result["check"]) == {"logp_err_geomean", "logp_err"}
    assert all("limit" in line for line in lines)


def test_altered_answers_are_not_correct(tiny_cell, monkeypatch):
    import repro.models as models

    real = models.classify

    def altered(params, cfg, patches, **kw):
        out = dict(real(params, cfg, patches, **kw))
        out["classes"] = (out["classes"] + 1) % cfg.num_classes
        return out

    monkeypatch.setattr(models, "classify", altered)
    result, _ = tiny_cell()
    assert result["failed"] == 0
    assert result["correct"] is False


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "m3vit-small.saturated", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)
    return p


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_cpu_backend_exits_nonzero_without_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
