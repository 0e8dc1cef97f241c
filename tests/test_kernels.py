"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs the
pure-jnp oracle (ref.py), as required per kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.padded_ffn import padded_ffn as ffn_pallas
from repro.kernels.paged_attention import paged_attention as pa_pallas
from repro.core.weight_transform import (ffn_reference, pad_columns_for_tp,
                                         pad_rows_for_tp)


# ---------------------------------------------------------------------------
# paged_attention: sweep (B, Hq, kvs, P, pages, dh) x dtype
# ---------------------------------------------------------------------------
SWEEP = [
    # B, Hq, kvs, P, n_pages, dh
    (1, 4, 4, 8, 2, 32),
    (2, 8, 4, 16, 4, 64),
    (3, 8, 8, 8, 3, 64),
    (2, 16, 2, 32, 2, 128),
    (1, 2, 1, 16, 5, 128),   # MQA replicated to 2 slots -> kvs=1,rep=2
]


@pytest.mark.parametrize("B,Hq,kvs,P,n_pages,dh", SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_vs_oracle(B, Hq, kvs, P, n_pages, dh, dtype):
    rng = np.random.default_rng(hash((B, Hq, kvs, P, n_pages, dh)) % 2**32)
    NP = B * n_pages
    q = jnp.asarray(rng.normal(size=(B, Hq, dh)), dtype)
    pool = jnp.asarray(rng.normal(size=(NP, kvs, 2, P, dh)), dtype)
    pt = jnp.asarray(
        rng.permutation(NP).reshape(B, n_pages), jnp.int32)
    max_t = n_pages * P
    sl = jnp.asarray(rng.integers(1, max_t + 1, size=(B,)), jnp.int32)
    out = pa_pallas(q, pool, pt, sl, interpret=True)
    want = ref.paged_attention_ref(q, pool, pt, sl)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_scattered_page_table():
    """Non-identity page tables (the paged property!) must work."""
    rng = np.random.default_rng(7)
    B, Hq, kvs, P, n_pages, dh = 2, 4, 2, 8, 3, 32
    NP = 16  # more physical pages than used
    q = jnp.asarray(rng.normal(size=(B, Hq, dh)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(NP, kvs, 2, P, dh)), jnp.float32)
    pt = jnp.asarray([[5, 0, 9], [14, 2, 7]], jnp.int32)
    sl = jnp.asarray([17, 24], jnp.int32)
    out = pa_pallas(q, pool, pt, sl, interpret=True)
    want = ref.paged_attention_ref(q, pool, pt, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# paged_attention on the serving path's caches: positions mask, live-page
# bound, rings — against the dense oracle and the jnp page walk
# ---------------------------------------------------------------------------
WIDTHS = {"phi3": (32, 32, 96), "gemma": (8, 1, 256)}   # Hq, kvs, dh


def _decode_cache(case, Hq, kvs, dh, P=16, n=4):
    """A decode-time cache: (q, pool, page_table, seq_lens, positions,
    q_positions, window, empty rows).  Pages past a row's live length
    hold NaN, which the kernel must neither read nor mix in."""
    rng = np.random.default_rng(sum(map(ord, case)) + Hq + dh)
    cap, window = n * P, 0
    if case == "partial":            # partial last page, empty row, full
        sl = np.array([2 * P + 5, 0, cap])
        pt = np.arange(3 * n).reshape(3, n)
        NP = 3 * n
    elif case == "scattered":        # pages anywhere in a larger pool
        sl = np.array([P + 1, cap - 3])
        NP = 3 * 2 * n
        pt = rng.permutation(NP)[:2 * n].reshape(2, n)
    else:                            # wrapped rings, window inside them
        sl = np.array([cap + 7, 3 * cap + 20])
        pt = np.arange(2 * n).reshape(2, n)
        NP, window = 2 * n, cap - P // 2
    B = len(sl)
    t = np.arange(cap)[None]
    # slot t holds the newest position below seq_len congruent to it
    pos = t + ((sl[:, None] - 1 - t) // cap) * cap
    pos = np.where(t < np.minimum(sl, cap)[:, None], pos, -1)
    pool = rng.normal(size=(NP, kvs, 2, P, dh)).astype(np.float32)
    for b in range(B):
        live = -(-min(sl[b], cap) // P)
        pool[pt[b, live:]] = np.nan
    q = jnp.asarray(rng.normal(size=(B, Hq, dh)), jnp.bfloat16)
    return (q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(pt, jnp.int32),
            jnp.asarray(sl, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(sl - 1, jnp.int32), window, sl == 0)


@pytest.mark.parametrize("case", ["partial", "scattered", "ring"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_attention_serving_caches(width, case):
    """The kernel against ``ref.paged_attention_ref`` and the jnp page
    walk ``layers.paged_decode_attention`` (both given the dead pages
    as zeros) at phi3 (MHA, head 96) and gemma (MQA, head 256) widths:
    a partial last page, an empty row (zeros), a scattered page table,
    wrapped rings under a window.  NaN in the dead pages must not reach
    the output."""
    from repro.models import layers as Lyr
    Hq, kvs, dh = WIDTHS[width]
    q, pool, pt, sl, pos, qp, window, empty = _decode_cache(case, Hq, kvs,
                                                            dh)
    out = pa_pallas(q, pool, pt, sl, pos, qp, window=window,
                    interpret=True)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    assert (out[empty] == 0).all()
    clean = jnp.where(jnp.isnan(pool), 0, pool)
    want = ref.paged_attention_ref(q, clean, pt, sl, pos, qp, window)
    walk = Lyr.paged_decode_attention(q, clean[pt], pos, qp,
                                      window=window)
    for other in (want, walk):
        np.testing.assert_allclose(out[~empty],
                                   np.asarray(other, np.float32)[~empty],
                                   rtol=2e-2, atol=2e-2)


def test_paged_attention_sharded_on_fake_devices():
    """``paged_attention_sharded`` over a (rep=2, sp=1, tp=2) mesh of
    four CPU devices: rows and their pages split over ``rep`` (each
    shard rebases the global page ids), kv heads over ``tp``; MHA and
    grouped heads both equal the kernel on one device."""
    import subprocess
    import sys
    import textwrap
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import paged_attention as PA
        from repro.launch.mesh import Layout, make_instance_mesh

        mesh = make_instance_mesh(jax.devices()[:4], Layout(1, 2))
        rng = np.random.default_rng(0)
        B, n, Pg, dh = 2, 4, 8, 32
        for Hq, kvs in ((4, 2), (4, 4)):
            pool = rng.normal(size=(B * n, kvs, 2, Pg, dh))
            # each row's pages scattered inside its own replica's range
            pt = np.stack([b * n + rng.permutation(n) for b in range(B)])
            sl = np.array([n * Pg - 5, Pg + 2])
            t = np.arange(n * Pg)[None]
            pos = np.where(t < sl[:, None], t, -1)
            args = [jnp.asarray(rng.normal(size=(B, Hq, dh)), jnp.float32),
                    jnp.asarray(pool, jnp.float32),
                    jnp.asarray(pt, jnp.int32), jnp.asarray(sl, jnp.int32),
                    jnp.asarray(pos, jnp.int32),
                    jnp.asarray(sl - 1, jnp.int32)]
            want = PA.paged_attention(*args, interpret=True)
            specs = [P("rep", "tp"), P(("rep", "sp"), "tp"), P("rep"),
                     P("rep"), P("rep"), P("rep")]
            placed = [jax.device_put(a, NamedSharding(mesh, s))
                      for a, s in zip(args, specs)]
            got = jax.jit(lambda *a: PA.paged_attention_sharded(
                mesh, *a, interpret=True))(*placed)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)
        print("SHARDED_OK")
    """)
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED_OK" in out.stdout


# ---------------------------------------------------------------------------
# padded_ffn: sweep shapes x tp x activation x dtype
# ---------------------------------------------------------------------------
FFN_SWEEP = [
    # T, d, ff_per_shard, pad_per_shard, tp
    (128, 128, 128, 0, 1),
    (128, 128, 128, 128, 2),
    (256, 256, 256, 128, 2),
    (128, 128, 256, 128, 4),
]


@pytest.mark.parametrize("T,d,ffs,pad,tp", FFN_SWEEP)
@pytest.mark.parametrize("act", ["swiglu", "geglu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_padded_ffn_vs_unpadded_oracle(T, d, ffs, pad, tp, act, dtype):
    rng = np.random.default_rng(hash((T, d, ffs, pad, tp, act)) % 2**32)
    ff, ffp = ffs * tp, (ffs + pad) * tp
    x = jnp.asarray(rng.normal(size=(T, d)), dtype)
    u = jnp.asarray(rng.normal(size=(d, 2 * ff)) * 0.05, dtype)
    dn = jnp.asarray(rng.normal(size=(ff, d)) * 0.05, dtype)
    gate, up = jnp.split(u, 2, axis=1)
    wi = jnp.concatenate([pad_columns_for_tp(gate, ff, ffp, tp),
                          pad_columns_for_tp(up, ff, ffp, tp)], axis=1)
    wo = pad_rows_for_tp(dn, ff, ffp, tp)
    out = ffn_pallas(x, wi, wo, tp=tp, ff=ff, activation=act,
                     interpret=True)
    want = ffn_reference(x.astype(jnp.float32), u.astype(jnp.float32),
                         dn.astype(jnp.float32), act)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_ops_wrappers_jnp_backend():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 4, 32)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(4, 2, 2, 8, 32)), jnp.float32)
    pt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    sl = jnp.asarray([9, 16], jnp.int32)
    a = ops.paged_attention(q, pool, pt, sl, backend="jnp")
    b = ops.paged_attention(q, pool, pt, sl, backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention: prefill kernel sweep
# ---------------------------------------------------------------------------
from repro.kernels.flash_attention import flash_attention

FLASH_SWEEP = [
    # B, S, Hq, Hkv, dh, window, bq, bk
    (1, 128, 4, 4, 32, 0, 64, 64),
    (2, 256, 8, 2, 64, 0, 128, 128),
    (1, 256, 4, 1, 64, 0, 64, 128),     # MQA
    (1, 256, 4, 4, 32, 64, 64, 64),     # sliding window
    (2, 128, 2, 2, 128, 0, 128, 64),
]


@pytest.mark.parametrize("B,S,Hq,Hkv,dh,win,bq,bk", FLASH_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_oracle(B, S, Hq, Hkv, dh, win, bq, bk, dtype):
    rng = np.random.default_rng(hash((B, S, Hq, Hkv, dh, win)) % 2**32)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), dtype)
    out = flash_attention(q, k, v, causal=True, window=win, block_q=bq,
                          block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=win)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_bidirectional():
    rng = np.random.default_rng(0)
    B, S, H, dh = 1, 128, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
