"""Fused paged chunk-prefill attention kernel (kernels/chunk_prefill).

Fast parity sweep (interpret mode): the kernel matches the dense oracle
(`ref.chunk_prefill_ref`) and the page-granular jnp mirror
(`chunk_prefill_jnp`) on GQA, partial trailing pages, ring wraps,
scattered page tables, and mid-transform widened pools; the in-place
pool scatter is BIT-identical to ``pool.write_chunk`` in every case
(attention outputs carry a ~1-ulp tolerance: multi-step online-softmax
accumulation through VMEM scratch rounds differently from the eager
mirror).  Storage layouts (header_centric + page_friendly) round-trip
through the canonical boundary bit-exactly.

A GSPMD locality guard (8 fake devices, subprocess) lowers the engine's
identity-pages chunk path and asserts its HLO moves no full-pool
all-gather bytes, while the page-table gather path does — the copy the
fusion deletes.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def _case(B, Hq, kvs, P, mps, dh, S, done, window=0, attend_prefix=True,
          dtype="float32", scattered_pt=False, extra_pages=0, seed=0):
    """Build one chunk-prefill problem.  ``done`` tokens already sit in
    the pool (ring-wrapped when done > capacity); the chunk starts at
    position ``done`` (page-aligned by construction)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    cap = mps * P
    NP = B * mps + extra_pages
    assert done % P == 0, "chunking invariant: page-aligned chunk start"
    pool = jnp.asarray(rng.normal(size=(NP, kvs, 2, P, dh)), dt)
    if scattered_pt or extra_pages:
        pt = rng.permutation(NP)[:B * mps].reshape(B, mps)
    else:
        pt = np.arange(B * mps).reshape(B, mps)
    pt = jnp.asarray(pt, jnp.int32)
    kvpos = np.full((B, cap), -1, np.int32)
    for p in range(max(0, done - cap), done):
        kvpos[:, p % cap] = p
    kvpos = jnp.asarray(kvpos)
    qpos = jnp.asarray(
        np.broadcast_to(done + np.arange(S), (B, S)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, dh)), dt)
    k = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), dt)
    v = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), dt)
    return dict(q=q, k_new=k, v_new=v, pool=pool, page_table=pt,
                kv_positions=kvpos, q_positions=qpos, window=window,
                attend_prefix=attend_prefix)


# name, B, Hq, kvs, P, mps, dh, S, done, window, attend_prefix, kwargs
SWEEP = [
    ("gqa_partial_page", 2, 8, 4, 8, 4, 16, 12, 16, 0, True, {}),
    ("mha_full_pages", 2, 4, 4, 8, 4, 16, 16, 8, 0, True, {}),
    ("first_chunk", 2, 8, 4, 8, 4, 16, 12, 0, 0, False, {}),
    ("window_mask", 2, 8, 4, 8, 4, 16, 12, 16, 12, True, {}),
    ("ring_wrap", 1, 8, 4, 8, 2, 16, 8, 24, 16, True, {}),
    ("scattered_pages", 2, 8, 4, 8, 4, 16, 12, 16, 0, True,
     {"scattered_pt": True}),
    ("widened_pool", 2, 8, 4, 8, 4, 16, 12, 16, 0, True,
     {"extra_pages": 6}),
    ("bf16", 2, 8, 4, 8, 4, 16, 12, 16, 0, True, {"dtype": "bfloat16"}),
]


@pytest.mark.parametrize(
    "name,B,Hq,kvs,P,mps,dh,S,done,window,ap,kw",
    SWEEP, ids=[c[0] for c in SWEEP])
def test_kernel_parity_sweep(name, B, Hq, kvs, P, mps, dh, S, done,
                             window, ap, kw):
    import jax.numpy as jnp
    from repro.kernels import chunk_prefill as CP
    from repro.kernels.ref import chunk_prefill_ref

    c = _case(B, Hq, kvs, P, mps, dh, S, done, window, ap, **kw)
    out, pool = CP.chunk_prefill_attention(interpret=True, **c)
    ref_out, ref_pool = chunk_prefill_ref(**c)
    jnp_out, jnp_pool = CP.chunk_prefill_jnp(**c)
    tol = 2e-2 if c["q"].dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=tol, rtol=tol)
    # the page-granular mirror shares the kernel's op order; only
    # multi-step scratch round-trips separate them (~1 ulp)
    mtol = 2e-2 if c["q"].dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jnp_out, np.float32),
                               atol=mtol, rtol=mtol)
    # the in-place scatter is exact data movement: bitwise equal to the
    # write_chunk semantics the oracle and mirror implement
    np.testing.assert_array_equal(np.asarray(pool), np.asarray(ref_pool))
    np.testing.assert_array_equal(np.asarray(pool), np.asarray(jnp_pool))


@pytest.mark.parametrize("storage_layout",
                         ["header_centric", "page_friendly"])
def test_kernel_scatter_matches_write_chunk_layouts(storage_layout):
    """Driving the kernel through the canonical boundary
    (``pool.canonical`` -> kernel -> ``pool.adopt_chunk_pool``) lands
    the bit-identical PagedState that ``pool.write_chunk`` produces, on
    either storage layout."""
    import jax.numpy as jnp
    from repro.kernels import chunk_prefill as CP
    from repro.paged import pool as pp

    B, mps, kvs, P, dh, S, done = 2, 4, 4, 8, 16, 12, 16
    rng = np.random.default_rng(1)
    st = pp.make_state(B * mps, kvs, P, dh, B, mps, dtype=jnp.float32,
                       storage_layout=storage_layout)
    kpre = jnp.asarray(rng.normal(size=(B, done, kvs, dh)), jnp.float32)
    vpre = jnp.asarray(rng.normal(size=(B, done, kvs, dh)), jnp.float32)
    st = pp.write_prefill(st, kpre, vpre, storage_layout)

    q = jnp.asarray(rng.normal(size=(B, S, 8, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), jnp.float32)
    pos = jnp.broadcast_to(done + jnp.arange(S, dtype=jnp.int32), (B, S))

    want = pp.write_chunk(st, k, v, pos, storage_layout)

    _, pool_c = CP.chunk_prefill_attention(
        q, k, v, pp.canonical(st.pool, storage_layout), st.page_table,
        st.positions, pos, interpret=True)
    got = pp.adopt_chunk_pool(st, pool_c, pos, storage_layout)

    np.testing.assert_array_equal(np.asarray(got.pool),
                                  np.asarray(want.pool))
    np.testing.assert_array_equal(np.asarray(got.positions),
                                  np.asarray(want.positions))
    np.testing.assert_array_equal(np.asarray(got.seq_lens),
                                  np.asarray(want.seq_lens))


def test_attention_chunk_kernel_vs_jnp_paths():
    """blocks.attention_chunk with use_kernel=True matches the jnp path
    on the same cache (attention allclose, pool bytes + metadata
    bitwise), first and continuation chunks."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.padding import make_plan
    from repro.models import blocks as B_
    from repro.paged import pool as pp

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32")
    plan = make_plan(cfg, 1)
    B, S, done, P = 2, 8, 8, 8
    mps = 4
    rng = jax.random.PRNGKey(0)
    p = B_.init_attention(rng, cfg, plan)
    x = jax.random.normal(jax.random.fold_in(rng, 1),
                          (B, S, cfg.d_model), jnp.float32)
    for first, start in ((True, 0), (False, done)):
        cache = pp.make_state(B * mps, plan.kv_slots, P,
                              cfg.resolved_head_dim, B, mps,
                              dtype=jnp.float32)
        if not first:
            kpre = jax.random.normal(
                jax.random.fold_in(rng, 2),
                (B, done, plan.kv_slots, cfg.resolved_head_dim),
                jnp.float32)
            cache = pp.write_prefill(cache, kpre, kpre)
        pos = jnp.broadcast_to(start + jnp.arange(S, dtype=jnp.int32),
                               (B, S))
        out_j, cache_j = B_.attention_chunk(p, x, cfg, plan, pos, cache,
                                            first_chunk=first)
        out_k, cache_k = B_.attention_chunk(p, x, cfg, plan, pos, cache,
                                            first_chunk=first,
                                            use_kernel=True)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(cache_k.pool),
                                      np.asarray(cache_j.pool))
        np.testing.assert_array_equal(np.asarray(cache_k.positions),
                                      np.asarray(cache_j.positions))
        np.testing.assert_array_equal(np.asarray(cache_k.seq_lens),
                                      np.asarray(cache_j.seq_lens))


def test_first_chunk_skip_is_bit_exact():
    """Satellite: skipping the all-invalid prefix gather on the first
    chunk leaves the attention output BIT-identical (masked prefix terms
    are exact zeros) — the engine's static first_chunk=True variant
    cannot change streams."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.padding import make_plan
    from repro.models import blocks as B_
    from repro.paged import pool as pp

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32")
    plan = make_plan(cfg, 1)
    B, S, P, mps = 2, 8, 8, 4
    rng = jax.random.PRNGKey(3)
    p = B_.init_attention(rng, cfg, plan)
    x = jax.random.normal(jax.random.fold_in(rng, 1),
                          (B, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    mk = lambda: pp.make_state(B * mps, plan.kv_slots, P,
                               cfg.resolved_head_dim, B, mps,
                               dtype=jnp.float32)
    out_skip, c_skip = B_.attention_chunk(p, x, cfg, plan, pos, mk(),
                                          first_chunk=True)
    out_full, c_full = B_.attention_chunk(p, x, cfg, plan, pos, mk(),
                                          first_chunk=False)
    np.testing.assert_array_equal(np.asarray(out_skip),
                                  np.asarray(out_full))
    np.testing.assert_array_equal(np.asarray(c_skip.pool),
                                  np.asarray(c_full.pool))


def test_fused_path_hlo_has_no_pool_all_gather():
    """GSPMD locality guard: on an 8-device mesh with the pool sharded
    over kv heads (the engine's TP axis), the identity-pages chunk path
    (gather + in-place write, the exact data movement the kernel fuses)
    compiles with ZERO collective bytes — every page stays resident on
    its shard.  As a control that the counter can see a violation, the
    page-table-indexed gather with the pool sharded over the PAGE axis
    does move bytes (dynamic indexing across shards)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.paged import pool as pp
        from repro.launch.hlo_analysis import collective_bytes

        B, mps, kvs, Pt, dh, S, done = 2, 4, 8, 8, 32, 16, 16
        mesh = Mesh(np.asarray(jax.devices()), ("tp",))
        k = jnp.zeros((B, S, kvs, dh), jnp.float32)
        pos = jnp.broadcast_to(done + jnp.arange(S, dtype=jnp.int32),
                               (B, S))

        def chunk_io(identity):
            def f(st, k, pos):
                kk, vv, kv_pos, valid = pp.gather_kv(
                    st, identity_pages=identity)
                st = pp.write_chunk(st, k, k, pos,
                                    identity_pages=identity)
                return kk, vv, st
            return f

        def lower(pool_spec, identity):
            st = pp.make_state(B * mps, kvs, Pt, dh, B, mps,
                               dtype=jnp.float32)
            st = jax.device_put(st, pp.PagedState(
                NamedSharding(mesh, pool_spec),
                NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                NamedSharding(mesh, P())))
            f = jax.jit(chunk_io(identity))
            return f.lower(st, k, pos).compile().as_text()

        local = collective_bytes(lower(P(None, "tp"), True))
        paged = collective_bytes(lower(P("tp"), False))
        print("local_bytes", sum(local.values()))
        print("paged_bytes", sum(paged.values()))
        assert sum(local.values()) == 0, local
        assert sum(paged.values()) > 0, paged
    """)
    assert "local_bytes 0" in out


def test_sharded_kernel_matches_single_device():
    """Over several devices the kernel runs under shard_map (kv heads
    over ``tp``, replicated over ``rep``): same attention and the same
    pool bytes as one un-sharded call, on 4 fake devices as rep2 x tp2."""
    out = run_py("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh
        from repro.kernels import chunk_prefill as CP

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 1, 2),
                    ("rep", "sp", "tp"))
        rng = np.random.default_rng(0)
        B, Hq, kvs, P, mps, dh, S, done = 1, 8, 4, 8, 4, 16, 12, 16
        cap = mps * P
        pool = jnp.asarray(rng.normal(size=(B * mps, kvs, 2, P, dh)),
                           jnp.float32)
        pt = jnp.arange(B * mps, dtype=jnp.int32).reshape(B, mps)
        kvpos = np.full((B, cap), -1, np.int32)
        kvpos[:, :done] = np.arange(done)
        qpos = jnp.asarray(done + np.arange(S)[None], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, S, Hq, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), jnp.float32)
        args = (q, k, v, pool, pt, jnp.asarray(kvpos), qpos)
        want_o, want_p = CP.chunk_prefill_attention(*args, interpret=True)
        got_o, got_p = jax.jit(lambda *a: CP.chunk_prefill_sharded(
            mesh, *a, interpret=True))(*args)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(got_p),
                                      np.asarray(want_p))
        print("SHARDED_KERNEL_OK")
    """)
    assert "SHARDED_KERNEL_OK" in out


def test_kernel_refusal_is_an_error():
    """With the kernel on, a chunk it cannot take (longer than the slot
    capacity) raises instead of silently switching to the jnp path."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.padding import make_plan
    from repro.models import blocks as B_
    from repro.paged import pool as pp

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32")
    plan = make_plan(cfg, 1)
    B, S, P, mps = 1, 40, 8, 4                # capacity 32 < 40
    p = B_.init_attention(jax.random.PRNGKey(0), cfg, plan)
    x = jnp.zeros((B, S, cfg.d_model), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    cache = pp.make_state(B * mps, plan.kv_slots, P, cfg.resolved_head_dim,
                          B, mps, dtype=jnp.float32)
    with pytest.raises(ValueError, match="cannot take this chunk"):
        B_.attention_chunk(p, x, cfg, plan, pos, cache, first_chunk=True,
                           use_kernel=True)


def test_kernel_eligibility_gate():
    from repro.kernels.chunk_prefill import chunk_prefill_eligible

    class Shape:
        def __init__(self, ndim):
            self.ndim = ndim

    assert chunk_prefill_eligible(Shape(5), 16, 64)
    assert not chunk_prefill_eligible(Shape(5), 0, 64)       # empty chunk
    assert not chunk_prefill_eligible(Shape(5), 65, 64)      # > capacity
    assert not chunk_prefill_eligible(Shape(6), 16, 64)      # stacked pool
