"""Pallas TPU kernel: decode attention over the header-centric paged KV
pool (paper §4.1 layout), read *in place* — no gather, no token-major
copy of the reservation.

Contract (the serving path's; ``blocks.attention_decode`` calls it on
TPU through ``paged_attention_sharded``):

* the grid walks (batch row, logical page); the scalar-prefetched page
  table maps each step to a physical page, and the BlockSpec DMAs that
  page's ``(1, kvs, 2, P, dh)`` block — all kv heads, K and V — into
  VMEM.  The block spans the pool's last four dims whole, so no head
  size needs lane padding (phi-3's 96 and gemma's 256 both compile);
* the DMA is bounded by the live length: a row's page index is clamped
  to its last live page, ``ceil(min(seq_len, q_pos + 1) / P) - 1``
  (every page once a ring has wrapped).  A repeated block index issues
  no copy, so pages past the live length are never read from HBM, and
  ``pl.when`` skips their compute.  Valid keys cannot lie past that
  page: a pool slot holds a position congruent to it modulo the
  capacity, so a key at or before ``q_pos < capacity`` sits in a slot
  at or before ``q_pos``;
* masking is ``layers._paged_partials``'s: the pool's ``positions`` row
  of each page (-1 = empty) against the query position,
  ``0 <= pos <= q_pos`` and, with ``window > 0``, ``pos > q_pos -
  window`` — ring caches included;
* online softmax in float32 VMEM scratch across the page walk; a row
  with no live page returns zeros.  Scores and the value sum are
  matmuls batched over the kv heads, MHA's one-row groups included: on
  a v5e at phi-3 widths that form ran 9% faster than a VPU multiply
  and reduce.

Validated against ``ref.paged_attention_ref`` and
``layers.paged_decode_attention`` in interpret mode
(tests/test_kernels.py), and compiled for a described v5e at phi-3 and
gemma-2b widths, alone and under ``shard_map`` on a TP4 mesh
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunk_prefill import NEG_INF, _auto_interpret


def _live_pages(seq_len, q_pos, page_tokens: int, n_pages: int):
    """Pages of a row that can hold a key its query attends: the row's
    written length, cut at the query (a fake row's stale cursor reads
    one page), every page once that passes the ring's capacity."""
    live = jnp.minimum(seq_len, q_pos + 1)
    return jnp.clip((live + page_tokens - 1) // page_tokens, 0, n_pages)


def _kernel(
    # scalar prefetch
    page_table_ref,     # (B, n_pages) int32
    seq_lens_ref,       # (B,) int32
    q_pos_ref,          # (B,) int32
    # inputs
    q_ref,              # (1, kvs, rep, dh)   one batch row's query
    kvpos_ref,          # (1, 1, 1, P) int32  positions of this page
    pool_ref,           # (1, kvs, 2, P, dh)  one page, every kv head
    # outputs
    o_ref,              # (1, kvs, rep, dh)
    # scratch
    m_ref, l_ref, acc_ref,      # (kvs, rep, 1) x2, (kvs, rep, dh)
    *, n_pages: int, page_tokens: int, window: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = q_pos_ref[b]
    live = _live_pages(seq_lens_ref[b], qp, page_tokens, n_pages)

    @pl.when(j < live)
    def _attend():
        k = pool_ref[0, :, 0].astype(jnp.float32)     # (kvs, P, dh)
        v = pool_ref[0, :, 1].astype(jnp.float32)
        pos = kvpos_ref[0, 0]                         # (1, P)
        ok = (pos >= 0) & (pos <= qp)
        if window > 0:
            ok = ok & (pos > qp - window)
        q = q_ref[0].astype(jnp.float32) * (1.0 / math.sqrt(k.shape[-1]))
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = jnp.where(ok[None], s, NEG_INF)           # (kvs, rep, P)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        # a row that attended nothing has l == 0 and acc == 0: zeros
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention(q: jax.Array, pool: jax.Array, page_table: jax.Array,
                    seq_lens: jax.Array, kv_positions=None,
                    q_positions=None, *, window: int = 0,
                    interpret=None) -> jax.Array:
    """q: (B, Hq, dh); pool: (NP, kvs, 2, P, dh) header-centric;
    page_table: (B, n_pages) physical page of each logical page;
    seq_lens: (B,) tokens written (may pass the capacity in a ring);
    kv_positions: (B, n_pages * P) each slot's global position (-1 =
    empty); q_positions: (B,) the query's position.  Without positions
    the first ``seq_len`` slots of a row are its keys and its query
    sits at ``seq_len - 1``.  Returns (B, Hq, dh)."""
    B, Hq, dh = q.shape
    NP, kvs, _, P, _ = pool.shape
    n_pages = page_table.shape[1]
    assert Hq % kvs == 0, (Hq, kvs)
    rep = Hq // kvs
    seq_lens = seq_lens.astype(jnp.int32)
    if kv_positions is None:
        t = jnp.arange(n_pages * P, dtype=jnp.int32)[None]
        kv_positions = jnp.where(t < seq_lens[:, None], t, -1)
        q_positions = seq_lens - 1
    assert kv_positions.shape == (B, n_pages * P), (kv_positions.shape,
                                                    B, n_pages, P)

    def last_page(b, j, sl, qp):
        live = _live_pages(sl[b], qp[b], P, n_pages)
        return jnp.minimum(j, jnp.maximum(live - 1, 0))

    def q_index(b, j, pt, sl, qp):
        return (b, 0, 0, 0)

    def kvpos_index(b, j, pt, sl, qp):
        return (b, last_page(b, j, sl, qp), 0, 0)

    def pool_index(b, j, pt, sl, qp):
        return (pt[b, last_page(b, j, sl, qp)], 0, 0, 0, 0)

    kernel = functools.partial(_kernel, n_pages=n_pages, page_tokens=P,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_pages),
            in_specs=[
                pl.BlockSpec((1, kvs, rep, dh), q_index),
                pl.BlockSpec((1, 1, 1, P), kvpos_index),
                pl.BlockSpec((1, kvs, 2, P, dh), pool_index),
            ],
            out_specs=pl.BlockSpec((1, kvs, rep, dh), q_index),
            scratch_shapes=[
                pltpu.VMEM((kvs, rep, 1), jnp.float32),
                pltpu.VMEM((kvs, rep, 1), jnp.float32),
                pltpu.VMEM((kvs, rep, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, kvs, rep, dh), q.dtype),
        name="paged_decode_attention",
        interpret=_auto_interpret(interpret),
    )(page_table.astype(jnp.int32), seq_lens,
      q_positions.astype(jnp.int32), q.reshape(B, kvs, rep, dh),
      kv_positions.astype(jnp.int32).reshape(B, n_pages, 1, P), pool)
    return out.reshape(B, Hq, dh)


def paged_attention_sharded(mesh, q, pool, page_table, seq_lens,
                            kv_positions, q_positions, *, window: int = 0,
                            interpret=None):
    """``paged_attention`` on an instance mesh (``(rep, sp, tp)`` axes,
    or ``None`` for a single device).  GSPMD cannot partition a Mosaic
    kernel, so over several devices the call runs under ``shard_map``:
    kv heads and their query groups split over ``tp``; batch rows and
    their pages split over ``rep`` (the engine's slot-partitioned pools
    keep each row's pages on its replica) where the rows divide, and
    are replicated otherwise.  Page ids in the table are global, so
    each ``rep`` shard subtracts its first page.  The kernel does no
    cross-shard softmax combine: a layout with ``sp > 1`` takes the jnp
    page walk (``layers.paged_decode_attention``)."""
    call = functools.partial(paged_attention, window=window,
                             interpret=interpret)
    args = (q, pool, page_table, seq_lens, kv_positions, q_positions)
    if mesh is None or mesh.size == 1:
        return call(*args)
    from jax.sharding import PartitionSpec as P
    assert mesh.shape["sp"] == 1, "sequence-parallel pools take the jnp walk"
    n_rep = mesh.shape["rep"]
    rows = ("rep" if n_rep > 1 and q.shape[0] % n_rep == 0
            and pool.shape[0] % n_rep == 0 else None)

    def shard(q, pool, page_table, *rest):
        if rows is not None:
            first = jax.lax.axis_index("rep") * pool.shape[0]
            page_table = page_table - first
        return call(q, pool, page_table, *rest)

    pages = ("rep", "sp") if rows is not None else None
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(rows, "tp", None), P(pages, "tp"), P(rows, None),
                  P(rows), P(rows, None), P(rows)),
        out_specs=P(rows, "tp", None), check_vma=False)(*args)
