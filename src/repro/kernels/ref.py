"""Pure-jnp oracles for the Pallas kernels (used by tests and as the CPU
fallback backend)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q: jax.Array, pool: jax.Array,
                        page_table: jax.Array, seq_lens: jax.Array,
                        kv_positions=None, q_positions=None,
                        window: int = 0) -> jax.Array:
    """Decode attention over a header-centric paged KV pool.

    q:            (B, Hq, dh)
    pool:         (NP, kvs, 2, P, dh)   canonical header-centric layout
    page_table:   (B, max_pages) int32
    seq_lens:     (B,) int32 — without positions: the first ``seq_len``
                  slots are the keys (non-ring cache)
    kv_positions: (B, max_pages * P) slot positions (-1 = empty) and
    q_positions:  (B,) query positions: keys are ``0 <= pos <= q_pos``
                  (and ``pos > q_pos - window`` when ``window > 0``)
    returns       (B, Hq, dh)
    """
    B, Hq, dh = q.shape
    NP, kvs, _, P, _ = pool.shape
    rep = Hq // kvs
    scale = 1.0 / math.sqrt(dh)
    pages = pool[page_table]                      # (B, n, kvs, 2, P, dh)
    n = pages.shape[1]
    k = pages[:, :, :, 0].transpose(0, 2, 1, 3, 4).reshape(B, kvs, n * P, dh)
    v = pages[:, :, :, 1].transpose(0, 2, 1, 3, 4).reshape(B, kvs, n * P, dh)
    qg = q.reshape(B, kvs, rep, dh).astype(jnp.float32) * scale
    s = jnp.einsum("bhrd,bhtd->bhrt", qg, k.astype(jnp.float32))
    if kv_positions is None:
        pos = jnp.arange(n * P)[None, None, None, :]
        mask = pos < seq_lens[:, None, None, None]
    else:
        pos = kv_positions[:, None, None, :]
        qp = q_positions[:, None, None, None]
        mask = (pos >= 0) & (pos <= qp)
        if window > 0:
            mask = mask & (pos > qp - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhrt,bhtd->bhrd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, dh).astype(q.dtype)


def chunk_prefill_ref(q, k_new, v_new, pool, page_table, kv_positions,
                      q_positions, *, window: int = 0,
                      attend_prefix: bool = True):
    """Dense oracle for the fused chunk-prefill kernel: gather the whole
    prefix through the page table, concat the chunk's K/V, one softmax
    over everything, then the ``write_chunk`` scatter.

    q:            (B, S, Hq, dh);  k_new/v_new: (B, S, kvs, dh)
    pool:         (NP, kvs, 2, P, dh) canonical header-centric
    page_table:   (B, n_pages);  kv_positions: (B, cap) (-1 = empty)
    q_positions:  (B, S) chunk token positions (page-aligned start)
    returns       (attn (B, S, Hq, dh), new_pool)
    """
    B, S, Hq, dh = q.shape
    NP, kvs, _, P, _ = pool.shape
    rep = Hq // kvs
    scale = 1.0 / math.sqrt(dh)
    if attend_prefix:
        pages = pool[page_table]                  # (B, n, kvs, 2, P, dh)
        kv = pages.transpose(0, 1, 4, 3, 2, 5).reshape(B, -1, 2, kvs, dh)
        kk = jnp.concatenate([kv[:, :, 0], k_new], axis=1)
        vv = jnp.concatenate([kv[:, :, 1], v_new], axis=1)
        kpos = jnp.concatenate([kv_positions, q_positions], axis=1)
        valid = jnp.concatenate(
            [kv_positions >= 0, jnp.ones((B, S), bool)], axis=1)
    else:
        kk, vv, kpos = k_new, v_new, q_positions
        valid = jnp.ones((B, S), bool)
    qg = q.reshape(B, S, kvs, rep, dh).astype(jnp.float32) * scale
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kk.astype(jnp.float32))
    mask = (valid[:, None, None, None, :]
            & (kpos[:, None, None, None, :]
               <= q_positions[:, None, None, :, None]))
    if window > 0:
        mask = mask & (kpos[:, None, None, None, :]
                       > q_positions[:, None, None, :, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, vv.astype(jnp.float32))
    out = o.reshape(B, S, Hq, dh).astype(q.dtype)

    cap = kv_positions.shape[1]
    slot = q_positions % cap
    kvn = jnp.stack([k_new, v_new], axis=3).astype(pool.dtype)
    page_idx = jnp.take_along_axis(page_table, slot // P, axis=1)
    new_pool = pool.at[page_idx, :, :, slot % P, :].set(kvn)
    return out, new_pool


def padded_ffn_ref(x: jax.Array, wi: jax.Array, wo: jax.Array,
                   activation: str = "swiglu") -> jax.Array:
    """Padded gated FFN oracle: FFN'(x) of paper Eq. 2.

    x: (T, d); wi: (d, 2*ffp) fused [gate|up]; wo: (ffp, d).
    Zero columns/rows make it equal the unpadded FFN."""
    from repro.models.layers import _act
    gu = x @ wi
    g, u = jnp.split(gu, 2, axis=-1)
    h = _act(activation, g) * u
    return h @ wo


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0):
    """Oracle for the flash prefill kernel. q: (B,S,Hq,dh); k,v:
    (B,S,Hkv,dh)."""
    import math
    B, S, Hq, dh = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, S, Hkv, rep, dh).astype(jnp.float32) * scale
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(jnp.float32))
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window > 0:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, dh).astype(q.dtype)
