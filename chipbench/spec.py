"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
a per-layer metric is a reader in ``metrics/<metric>.py``; a model
family is a module in ``families/<architecture>.py``, named by the
configuration's own ``architectures[0]``.  Nothing here knows a cell, a
model, a family or a metric by name: a later change adds files, never
edits these.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FAMILIES = os.path.join(HERE, "families")
# what every family module gives (``families/__init__.py``)
FAMILY_API = ("program_config", "tiny", "canonical", "for_program",
              "fingerprint_program", "logits", "params", "weight_bytes",
              "prefill_flops", "decode_flops", "decode_step_bytes",
              "chunk_kernel")


def _load(kind: str, name: str) -> Dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> Dict:
    """The workload file of ``name`` with its configuration and traffic
    loaded beside it, and the metrics ``BENCHMARK.json`` asks of it."""
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    wl = _load("workloads", name)
    for key in ("config", "traffic", "chips"):
        if wl[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {wl[key]!r} in its file "
                             f"but {entry[key]!r} in BENCHMARK.json")
    wl["name"] = name
    wl["model"] = _load("configs", wl["config"])
    wl["mix"] = _load("traffic", wl["traffic"])

    def applies(m):
        return name in m.get("workloads", [name])

    wl["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    wl["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return wl


def reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(model: Dict) -> ModuleType:
    """The module of ``model``'s family, ``families/<architectures[0]>.py``.
    A family that has no module is an error naming the file looked for,
    never a default."""
    return _family(os.path.join(FAMILIES,
                                f"{model['architectures'][0]}.py"))


@functools.lru_cache(maxsize=None)
def _family(path: str) -> ModuleType:
    # loaded once per path, so its jitted functions keep their programs
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no model family module {path}")
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"{path} lacks {missing}")
    return mod


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; a kind that is
    not in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def cell_names() -> List[str]:
    return [w["name"] for w in benchmark()["workloads"]]
