"""Small-mesh dry-run CI: the same lower+compile path as the production
dry-run, on an 8-device (2x4) mesh via subprocess, one arch per family.
(The full 16x16 / 2x16x16 sweep is run by `python -m repro.launch.dryrun
--all`; its results live in EXPERIMENTS.md §Dry-run.)"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BODY = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_config, SHAPES
    from repro.configs.base import ShapeConfig
    from repro.launch import dryrun as DR
    import repro.launch.mesh as mesh_mod

    # shrink the production mesh for CI
    def small_mesh(*, multi_pod=False):
        if multi_pod:
            return mesh_mod.make_mesh((2, 2, 2), ("pod", "data", "model"))
        return mesh_mod.make_mesh((2, 4), ("data", "model"))
    DR.make_production_mesh = small_mesh

    shape = dataclasses.replace(SHAPES["{shape}"],
                                seq_len={seq}, global_batch={batch})
    import repro.launch.dryrun as dr
    dr.SHAPES = dict(SHAPES)
    dr.SHAPES["{shape}"] = shape
    rec = dr.run_one("{arch}", "{shape}", {multi}, save=False)
    assert rec.get("flops_total", 0) > 0 or rec.get("skipped")
    print("DRYRUN_OK", rec["arch"], rec.get("flops_total"))
"""


def run_case(arch, shape, seq, batch, multi=False):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    body = textwrap.dedent(BODY).format(arch=arch, shape=shape, seq=seq,
                                        batch=batch, multi=multi)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"OUT:\n{out.stdout}\nERR:\n{out.stderr}"
    assert "DRYRUN_OK" in out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape,seq,batch", [
    ("llama3-8b", "train_4k", 256, 8),
    ("granite-moe-3b-a800m", "decode_32k", 512, 8),
    ("xlstm-1.3b", "decode_32k", 512, 8),
    ("recurrentgemma-9b", "prefill_32k", 512, 8),
    ("whisper-tiny", "train_4k", 256, 8),
])
def test_small_mesh_dryrun(arch, shape, seq, batch):
    run_case(arch, shape, seq, batch)


@pytest.mark.slow
def test_small_mesh_multipod():
    run_case("llama3-8b", "decode_32k", 512, 8, multi=True)


@pytest.mark.slow
def test_transform_dryrun_small_mesh():
    """The Gyges transformation itself lowers: weights replicated->TP
    sharded with zero collectives; pool reshard is one all-to-all."""
    body = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.hlo_analysis import collective_bytes
    from repro.launch.mesh import make_mesh
    mesh1 = make_mesh((2, 4, 1), ("host", "rep", "tp"))
    mesh4 = make_mesh((2, 1, 4), ("host", "rep", "tp"))
    # weights: replicated -> col-sharded over tp: no comm (slice only)
    w = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)
    wi = NamedSharding(mesh1, P(None, "tp"))
    wo = NamedSharding(mesh4, P(None, "tp"))
    txt = jax.jit(lambda x: jax.lax.with_sharding_constraint(x, wo),
                  in_shardings=(wi,), out_shardings=wo).lower(
                      w).compile().as_text()
    d = collective_bytes(txt)
    assert sum(v for k, v in d.items() if k != "count") == 0, d
    # pool: pages-per-rep -> heads-per-tp: one all-to-all, bytes > 0
    pool = jax.ShapeDtypeStruct((2, 64, 8, 2, 16, 32), jnp.bfloat16)
    pi = NamedSharding(mesh1, P(None, ("host", "rep"), "tp"))
    po = NamedSharding(mesh4, P(None, ("host", "rep"), "tp"))
    txt = jax.jit(lambda x: jax.lax.with_sharding_constraint(x, po),
                  in_shardings=(pi,), out_shardings=po).lower(
                      pool).compile().as_text()
    d = collective_bytes(txt)
    assert sum(v for k, v in d.items() if k != "count") > 0, d
    print("TRANSFORM_DRYRUN_OK")
    """
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"OUT:\n{out.stdout}\nERR:\n{out.stderr}"
    assert "TRANSFORM_DRYRUN_OK" in out.stdout
