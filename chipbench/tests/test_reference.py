"""The plain reference against the program's engine at a reduced size:
in float32 every token the engine serves greedily is the reference's
first choice to rounding, through whole-prompt prefill, chunked prefill
and paged decode; a reference with one equation changed is not."""
import dataclasses

import numpy as np
import pytest

from chipbench import harness, spec, weights
from conftest import tiny_cell, tiny_model


def _serve(cell, model, seed, prompts, new_tokens, devices):
    from repro.serving.request import ServeRequest
    clk = harness.CompileClock()
    cluster, fp, _ = harness.build(cell, model, seed, devices, clk)
    reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    cluster.run(reqs, max_steps=5_000)
    return reqs, fp


def _widest(w, model, reqs):
    out = 0.0
    for q in reqs:
        toks = q.prompt + q.generated[:-1]
        rows = list(range(len(q.prompt) - 1, len(toks)))
        ref = spec.family(model).logits(w, model, toks, rows)
        out = max(out, float(np.max(ref.max(1) - ref[np.arange(len(rows)),
                                                     q.generated])))
    return out


@pytest.mark.parametrize("name", spec.cell_names())
def test_reference_matches_engine(name, cpu_devices):
    cell = tiny_cell(name)
    model = dict(tiny_model(cell["model"]), torch_dtype="float32")
    seed = 2 ** 32 + 77
    rng = np.random.default_rng(0)
    # 48 tokens: whole-prompt prefill; 200: chunked (64-token budget)
    prompts = [rng.integers(0, model["vocab_size"], size=n).tolist()
               for n in (48, 200)]
    reqs, fp = _serve(cell, model, seed, prompts, 24, cpu_devices[:1])
    w = spec.family(model).canonical(seed, model)
    assert weights.same_fingerprint(weights.fingerprint(w), fp)
    assert _widest(w, model, reqs) < 1e-4
    wrong = dict(model, rope_theta=500.0)
    assert _widest(w, wrong, reqs) > 1e-2
