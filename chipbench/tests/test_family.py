"""Model families (``chipbench/families/``): phi3-mini's weights,
reference logits and counts pinned to the values they had before the
llama code moved out of ``weights.py``, ``reference.py``, ``flops.py``
and ``harness.py``; a family found by its file alone; a missing family
an error that names the file looked for."""
import os
import shutil

import numpy as np
import pytest

from chipbench import harness, spec, weights
from conftest import tiny_cell, tiny_model

CELL = "phi3-mini.longdoc"
SEED = 2 ** 32 + 77
LOGITS = os.path.join(os.path.dirname(__file__), "data",
                      "phi3_mini_tiny_logits.npy")

# weights.fingerprint of tiny(phi3-mini)'s canonical weights at SEED
FINGERPRINT = {
    "embed": (7.564778804779053, 1046.9659423828125),
    "lm_head": (-1.459388256072998, 4605.07763671875),
    "ln_attn": (1.0801506042480469, 18.297557830810547),
    "ln_final": (-0.7234411239624023, 10.479208946228027),
    "ln_mlp": (-0.28458118438720703, 20.558719635009766),
    "w_down": (30.72517204284668, 3650.931396484375),
    "w_gate": (-3.68502140045166, 5774.0078125),
    "w_up": (-8.300233840942383, 5765.62548828125),
    "wk": (47.347869873046875, 2321.014404296875),
    "wo": (-18.085905075073242, 2316.78076171875),
    "wq": (4.036998271942139, 2295.6240234375),
    "wv": (-12.5647554397583, 2309.28271484375),
}
# counts at phi3-mini's published widths
PARAMS = 3_821_079_552
WEIGHT_BYTES = 7_445_157_888
PREFILL = {(0, 512): 3_762_689_015_808, (1024, 512): 3_968_847_446_016,
           (3072, 512): 4_381_164_306_432, (3584, 37): 320_784_039_936}
DECODE = {1: 7_445_151_744, 3000: 8_624_406_528, 4096: 9_055_371_264}
STEP_BYTES = {(3000,): 8_624_805_888, (100, 200): 7_563_122_688,
              (): 7_445_157_888}
CHUNK = {(0, 512): {"flops": 51_640_270_848, "bytes": 603_979_776},
         (1024, 512): {"flops": 257_798_701_056, "bytes": 1_006_632_960},
         (3072, 512): {"flops": 670_115_561_472, "bytes": 1_811_939_328}}


def phi3():
    return spec.cell(CELL)["model"]


def test_phi3_weights_and_reference_pinned():
    model = tiny_model(phi3())
    fam = spec.family(model)
    w = fam.canonical(SEED, model)
    assert weights.fingerprint(w) == FINGERPRINT
    prompt = np.random.default_rng(0).integers(0, 509, size=48).tolist()
    got = fam.logits(w, model, prompt, [0, 23, 47])
    assert got.dtype == np.float32 and got.shape == (3, 509)
    np.testing.assert_array_equal(got, np.load(LOGITS))


def test_phi3_program_weights_match_the_pins():
    from repro.core.padding import make_plan
    model = tiny_model(phi3())
    fam = spec.family(model)
    cfg = fam.program_config(model)
    params = fam.for_program(SEED, model, cfg, make_plan(cfg, 1,
                                                         mode="page"))
    assert weights.same_fingerprint(fam.fingerprint_program(params, model),
                                    FINGERPRINT)


def test_phi3_counts_pinned():
    m = phi3()
    fam = spec.family(m)
    assert fam.params(m) == PARAMS
    assert fam.weight_bytes(m) == WEIGHT_BYTES
    assert {k: fam.prefill_flops(m, *k) for k in PREFILL} == PREFILL
    assert {k: fam.decode_flops(m, k) for k in DECODE} == DECODE
    assert {k: fam.decode_step_bytes(m, list(k))
            for k in STEP_BYTES} == STEP_BYTES
    assert {k: fam.chunk_kernel(m, *k) for k in CHUNK} == CHUNK


def test_family_found_by_its_file_alone(tmp_path, monkeypatch, cpu_devices):
    """A new architecture that shares the llama code: one file in a copy
    of the families directory, and a configuration naming it."""
    real = spec.FAMILIES
    before = sorted(os.listdir(real))
    fams = tmp_path / "families"
    shutil.copytree(real, fams,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (fams / "TinyStackForCausalLM.py").write_text(
        "from chipbench.families.LlamaForCausalLM import *  # noqa\n")
    monkeypatch.setattr(spec, "FAMILIES", str(fams))
    model = dict(phi3(), name="tiny-stack",
                 architectures=["TinyStackForCausalLM"])
    fam = spec.family(model)
    assert fam.__file__ == str(fams / "TinyStackForCausalLM.py")
    small = tiny_model(model)
    assert spec.family(small) is fam
    cell = tiny_cell(CELL)
    cluster, fp, _ = harness.build(cell, small, SEED, cpu_devices[:1],
                                   harness.CompileClock())
    assert cluster.engines[0].cfg.name == "tiny-stack"
    assert weights.same_fingerprint(fp, FINGERPRINT)
    assert sorted(os.listdir(real)) == before


def test_missing_family_names_its_file(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "FAMILIES", str(tmp_path))
    with pytest.raises(FileNotFoundError) as err:
        spec.family(phi3())
    assert str(tmp_path / "Phi3ForCausalLM.py") in str(err.value)


def test_family_without_its_functions_is_an_error(tmp_path, monkeypatch):
    (tmp_path / "HalfForCausalLM.py").write_text(
        "def tiny(model):\n    return model\n")
    monkeypatch.setattr(spec, "FAMILIES", str(tmp_path))
    with pytest.raises(AttributeError) as err:
        spec.family({"architectures": ["HalfForCausalLM"]})
    assert "program_config" in str(err.value)
