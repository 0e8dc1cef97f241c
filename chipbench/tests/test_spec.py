"""Every cell, configuration, traffic mix and metric of BENCHMARK.json is
found from its own file by name."""
import json
import os

from chipbench import e2e, spec, traffic

HERE = os.path.dirname(spec.__file__)


def test_benchmark_keys_and_paths():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"]
    assert b["command"] == ["python3", "chipbench/run.py"]
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        model = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert model["name"] == c["name"]
        assert model["source"] == c["source"]
        assert model["reduced"] == c["reduced"]


def test_every_cell_loads():
    for name in spec.cell_names():
        cell = spec.cell(name)
        assert cell["model"]["name"] == cell["config"]
        assert cell["end_to_end"] and cell["per_layer"]
        assert "setup_s" in [m["name"] for m in cell["end_to_end"]]
        arr = traffic.schedule(cell["mix"], 30, 2 ** 31 + 5)
        assert arr and all(0 <= a.due_s < 30 for a in arr)
        grid = set(traffic.prompt_grid(cell["mix"]))
        assert {a.prompt_len for a in arr} <= grid
        assert all(a.prompt_len + a.output_len <= cell["engine"]["max_seq"]
                   for a in arr)


def test_every_metric_has_its_reduction():
    b = spec.benchmark()
    for m in b["end_to_end"]:
        assert m["name"] in e2e.METRICS
    for m in b["per_layer"]:
        assert callable(spec.reader(m["name"]))
        assert m["moves"] in [e["name"] for e in b["end_to_end"]]
        for w in m.get("workloads", []):
            assert w in spec.cell_names()


def test_seed_orders_the_same_requests():
    """Each seed gets the same set of gaps and lengths in its own order;
    one seed gives one schedule and one set of token ids."""
    for name in spec.cell_names():
        mix = spec.cell(name)["mix"]
        a, b = (traffic.schedule(mix, 51, s) for s in (3, 2 ** 31 + 9))
        assert a == traffic.schedule(mix, 51, 3)
        assert a != b and a[0].due_s == b[0].due_s == 0
        assert sorted((x.prompt_len, x.output_len) for x in a) != \
            sorted((x.prompt_len, x.output_len) for x in b)
        for key in ("prompt_len", "output_len"):
            assert sorted(getattr(x, key) for x in a) == \
                sorted(getattr(x, key) for x in b)
        # the gaps between arrivals, and from the last to the close
        gaps = [sorted([y.due_s - x.due_s for x, y in zip(s, s[1:])]
                       + [51 - s[-1].due_s]) for s in (a, b)]
        assert max(abs(x - y) for x, y in zip(*gaps)) < 1e-9
        ids = [traffic.prompt_ids(a, 509, s) for s in (3, 2 ** 31 + 9)]
        assert [len(x) for x in ids[0]] == [x.prompt_len for x in a]
        assert any((x != y).any() for x, y in zip(*ids))


def test_stratified_order():
    """Every run of ``strata`` places takes one index from each of the
    ``strata`` ranges that split ``range(n)``."""
    import numpy as np
    for n, strata, seed in ((38, 4, 1), (40, 4, 2 ** 31 + 3), (7, 3, 5)):
        perm = traffic.stratified(n, strata, np.random.default_rng(seed))
        assert sorted(perm.tolist()) == list(range(n))
        full = n // strata * strata
        for k in range(0, full, strata):
            ranges = {int(i) * strata // n for i in perm[k:k + strata]}
            assert len(ranges) == strata


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
