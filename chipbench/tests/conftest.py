"""CPU tests of the benchmark harness: run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests`` from the root of
the repository (they sit outside the repository's own test paths)."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_model(model):
    """The configuration at its family's CPU test size."""
    from chipbench import spec
    return spec.family(model).tiny(model)


def tiny_cell(name):
    """The cell with its engine and traffic scaled to the tiny model."""
    from chipbench import spec
    cell = copy.deepcopy(spec.cell(name))
    cell["engine"].update(slots=min(4, cell["engine"]["slots"]),
                          max_seq=256, page_tokens=16, chunk_budget=64)
    for c in cell["mix"]["classes"]:
        c["prompt"].update(min=64, max=192, round_up=64)
        c["output"].update(min=2, max=16)
    for k in cell["mix"]["knees"].values():
        k["rate_per_s"] = 10.0
    return cell


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices("cpu")
