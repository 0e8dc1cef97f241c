"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see the TPU's tiling
rules or its scoped-VMEM budget; the TPU compiler, which is installed
here, compiles for a described chip without one attached.  Each case
compiles one kernel at gemma-2b widths (8 query heads, 1 kv head,
head dim 256, 64-token pages, 512-token chunks) — the decode kernel at
phi3-mini widths too (32 query and kv heads, head dim 96) — ~1-8 s
apiece, and checks that the program calls the Mosaic kernel
(``tpu_custom_call``).  Nothing runs, so nothing here says anything
about results or speed.

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
from repro.kernels import paged_attention as PA

HQ, KV, DH, PAGE, CHUNK = 8, 1, 256, 64, 512   # gemma-2b attention
SLOT_TOKENS = 4096
DECODE_WIDTHS = {"gemma": (HQ, KV, DH), "phi3": (32, 32, 96)}


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


def _decode_args(B, Hq, kvs, dh, sharding_of):
    mps = SLOT_TOKENS // PAGE
    shapes = [((B, Hq, dh), jnp.bfloat16),
              ((B * mps, kvs, 2, PAGE, dh), jnp.bfloat16),
              ((B, mps), jnp.int32), ((B,), jnp.int32),
              ((B, SLOT_TOKENS), jnp.int32), ((B,), jnp.int32)]
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding_of(i))
            for i, (s, d) in enumerate(shapes)]


@pytest.mark.parametrize("width", sorted(DECODE_WIDTHS))
def test_paged_attention_compiles(one_chip, width):
    """The decode kernel with its live-page clamp and positions mask,
    windowed, on two rows of 4096-token slots."""
    args = _decode_args(2, *DECODE_WIDTHS[width], lambda i: one_chip)
    txt = _compiled_text(
        lambda *a: PA.paged_attention(*a, window=1024, interpret=False),
        *args)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tp", [4, 1], ids=["tp4", "rep4"])
def test_paged_attention_sharded_compiles_on_four_chips(topo, tp):
    """A merged TP4 instance (kv heads padded to four slots over ``tp``)
    and a TP1 engine over four chips (rows and their pages over
    ``rep``), under shard_map."""
    import numpy as np
    mesh = Mesh(np.asarray(topo.devices).reshape(4 // tp, 1, tp),
                ("rep", "sp", "tp"))
    rows = "rep" if tp == 1 else None
    specs = [P(rows, "tp"), P(("rep", "sp"), "tp"), P(rows), P(rows),
             P(rows), P(rows)]
    Hq, kvs, dh = 8, 4, DH
    args = _decode_args(4 // tp, Hq, kvs, dh,
                        lambda i: NamedSharding(mesh, specs[i]))
    txt = _compiled_text(
        lambda *a: PA.paged_attention_sharded(mesh, *a, interpret=False),
        *args)
    assert "tpu_custom_call" in txt


def test_decode_step_reads_the_pool_in_place(one_chip, monkeypatch):
    """``decode_step`` at phi3-mini widths (two layers of the 32) on one
    4096-token slot: with the kernel the program calls it and holds no
    token-major copy of a layer's reservation, which the jnp path
    without it does (``gather_kv``'s transpose,
    ``[1, 64 pages, 64 tokens, 2, 32 heads, 96]``)."""
    from repro.configs.base import ModelConfig
    from repro.core.padding import make_plan
    from repro.models import model as M

    # the program takes its CPU branch here; the test steers it to the
    # chip's
    monkeypatch.setattr(PA, "_auto_interpret", lambda interpret: False)
    cfg = ModelConfig(name="phi3-mini", arch_type="dense", num_layers=2,
                      d_model=3072, num_heads=32, num_kv_heads=32,
                      d_ff=8192, vocab_size=32064, head_dim=96,
                      activation="swiglu", tie_embeddings=False,
                      rope_theta=10000.0, norm_eps=1e-5, dtype="bfloat16")
    plan = make_plan(cfg, 1, mode="page")

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, plan)))
    caches = on_chip(jax.eval_shape(
        lambda: M.init_decode_caches(cfg, plan, 1, SLOT_TOKENS, PAGE)))
    tok = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    copy = "bf16[1,64,64,2,32,96]"
    for use_kernel in (True, False):
        txt = _compiled_text(
            lambda p, c, t, q: M.decode_step(p, cfg, plan, c, t, q,
                                             use_kernel=use_kernel),
            params, caches, tok, tok)
        assert ("tpu_custom_call" in txt) == use_kernel
        assert (copy in txt) != use_kernel
