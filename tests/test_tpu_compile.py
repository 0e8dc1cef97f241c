"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see the TPU's tiling
rules or its scoped-VMEM budget; the TPU compiler, which is installed
here, compiles for a described chip without one attached.  Each case
compiles one kernel at gemma-2b widths (8 query heads, 1 kv head,
head dim 256, 64-token pages, 512-token chunks), ~2-8 s apiece, and
checks that the program calls the Mosaic kernel (``tpu_custom_call``).
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, so a
module that did so on import would give pytest-xdist workers different
test sets.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import kv_transform as KT
from repro.kernels import chunk_prefill as CP
from repro.kernels import page_migrate as PM

HQ, KV, DH, PAGE, CHUNK = 8, 1, 256, 64, 512   # gemma-2b attention
SLOT_TOKENS = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def instance_mesh(topo):
    """A TP4 instance mesh over the four described chips."""
    import numpy as np
    return Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("rep", "sp", "tp"))


@pytest.fixture(autouse=True, scope="module")
def no_compile_cache():
    """Programs compiled for a described chip cannot be read back
    without one; keep them out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _chunk_args(B, kvs, sharding_of):
    mps = SLOT_TOKENS // PAGE
    shapes = [((B, CHUNK, HQ, DH), jnp.bfloat16),
              ((B, CHUNK, kvs, DH), jnp.bfloat16),
              ((B, CHUNK, kvs, DH), jnp.bfloat16),
              ((B * mps, kvs, 2, PAGE, DH), jnp.bfloat16),
              ((B, mps), jnp.int32),
              ((B, SLOT_TOKENS), jnp.int32),
              ((B, CHUNK), jnp.int32)]
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding_of(i))
            for i, (s, d) in enumerate(shapes)]


@pytest.mark.parametrize("attend_prefix", [False, True],
                         ids=["first_chunk", "with_prefix"])
def test_chunk_prefill_compiles(one_chip, attend_prefix):
    """Two batch rows, so every position block is a proper sub-block of
    its array (the tiling rule a (1, Sp) block broke once B > 1)."""
    args = _chunk_args(2, KV, lambda i: one_chip)
    txt = _compiled_text(
        lambda *a: CP.chunk_prefill_attention(
            *a, attend_prefix=attend_prefix, interpret=False), *args)
    assert "tpu_custom_call" in txt


def test_chunk_prefill_sharded_compiles_on_four_chips(instance_mesh):
    """The TP4 engine's chunk: kv heads (padded to 4 slots) split over
    ``tp`` under shard_map, since GSPMD cannot partition the kernel."""
    heads = NamedSharding(instance_mesh, P(None, None, "tp", None))
    specs = [heads, heads, heads,
             NamedSharding(instance_mesh, P(None, "tp"))] \
        + [NamedSharding(instance_mesh, P())] * 3
    args = _chunk_args(1, 4, lambda i: specs[i])
    txt = _compiled_text(
        lambda *a: CP.chunk_prefill_sharded(instance_mesh, *a,
                                            interpret=False), *args)
    assert "tpu_custom_call" in txt


def test_page_copy_and_gather_compile(one_chip):
    n, pages = 64, 8 * (SLOT_TOKENS // PAGE)
    pool = jax.ShapeDtypeStruct((pages, 4, 2, PAGE, DH), jnp.bfloat16,
                                sharding=one_chip)
    idx = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    def copy(src, dst, i):
        return PM.copy_page_slices(src, dst, i, i, i, i, heads_per_slice=4,
                                   interpret=False)

    def gather(src, i):
        return PM.gather_page_slices(src, i, i // n, heads_per_slice=1,
                                     interpret=False)

    assert "tpu_custom_call" in _compiled_text(copy, pool, pool, idx)
    assert "tpu_custom_call" in _compiled_text(gather, pool, idx)


@pytest.mark.parametrize("direction", ["up", "down"])
def test_sharded_migration_compiles_on_four_chips(topo, direction):
    """TP1x4 <-> TP4 KV migration: gather kernel, all_to_all, scatter
    kernel per device under shard_map, over the described 2x2 chips."""
    import numpy as np
    flat = Mesh(np.asarray(topo.devices).reshape(-1), ("x",))
    spec = P("x") if direction == "up" else P(None, "x")
    pool = jax.ShapeDtypeStruct((4 * SLOT_TOKENS // PAGE, 4, 2, PAGE, DH),
                                jnp.bfloat16,
                                sharding=NamedSharding(flat, spec))
    migrate = (KT.migrate_scale_up_sharded if direction == "up"
               else KT.migrate_scale_down_sharded)
    txt = _compiled_text(
        lambda p: migrate(p, flat, "x", interpret=False), pool)
    assert "tpu_custom_call" in txt
    assert "all-to-all" in txt
