"""The control, at a size a test run can hold: the reference in the
nearest precision below the configuration's (bfloat16 operands for its
float32 dots) takes the program's place and must come out not correct
under the limits the configurations hold, while the program, served on the
same images, comes out correct."""
import json
import os

import pytest

import control
from harness import main

ROOT = main.ROOT


@pytest.fixture
def tiny(monkeypatch):
    bench = main.load_benchmark()
    bench["configs"].append({"name": "m3vit-test", "source": "tests",
                             "file": "bench/tests/tiny.json", "reduced": [],
                             "why": "CPU size"})
    bench["workloads"].append({"name": "m3vit-test.saturated",
                               "config": "m3vit-test", "traffic": "saturated",
                               "chips": 1, "why": "CPU size"})
    monkeypatch.setattr(main, "enable_cache", lambda: "off")
    return main.resolve(bench, "m3vit-test.saturated")


def test_tiny_limits_are_m3vit_smalls():
    def check(path):
        with open(os.path.join(ROOT, path)) as f:
            return json.load(f)["check"]

    assert check("bench/tests/tiny.json") == check(
        "bench/configs/m3vit-small.json")


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_control_fails_and_program_passes(tiny, seed):
    fam = main.family_module("m3vit")
    system = fam.System(tiny["spec"], seed)
    program, ctl = control.readings(system, fam, tiny, seed, 0.5)
    limits = tiny["spec"]["check"]
    assert all(program[k] <= v for k, v in limits.items()), program
    assert any(ctl[k] > v for k, v in limits.items()), ctl
