"""A whole run of each cell, driven on the CPU at a reduced size (the
harness's look for a chip skipped): correct when the program is sound,
not correct with the fp8 control in the program's place, nor when a
served token is altered where it is produced; and the command itself
refuses a CPU backend."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import harness, spec
from conftest import ROOT, tiny_cell, tiny_model


def test_command_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "phi3-mini.longdoc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _run(name, devices, control=False):
    cell = tiny_cell(name)
    return harness.run(cell, 2 ** 33 + 5, 2.0, False, devices,
                       time.perf_counter(),
                       model=tiny_model(cell["model"]), control=control)


@pytest.mark.parametrize("name", spec.cell_names())
def test_sound_run_is_correct(name, cpu_devices):
    out = _run(name, cpu_devices)
    res = out["result"]
    assert res["correct"], out["check_lines"]
    assert list(res)[-1] == "check"
    cell = spec.cell(name)
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert any("compiles inside the window: 0 programs" in line
               for line in out["lines"]), out["lines"]
    json.dumps(res)


@pytest.mark.parametrize("name", spec.cell_names())
def test_control_is_not_correct(name, cpu_devices):
    res = _run(name, cpu_devices, control=True)["result"]
    assert not res["correct"]
    assert res["check"]["widest_gap"]["value"] > \
        res["check"]["widest_gap"]["limit"]


@pytest.mark.parametrize("name", spec.cell_names())
def test_altered_token_is_not_correct(name, cpu_devices, monkeypatch):
    from repro.serving import engine as E
    sample = E._sample
    vocab = tiny_model(spec.cell(name)["model"])["vocab_size"]

    def altered(logits, temperature, rng):
        # every sampled token moved one id up where it is produced
        return (sample(logits, temperature, rng) + 1) % vocab

    monkeypatch.setattr(E, "_sample", altered)
    res = _run(name, cpu_devices)["result"]
    assert not res["correct"]
    assert res["check"]["widest_gap"]["value"] > \
        res["check"]["widest_gap"]["limit"]
