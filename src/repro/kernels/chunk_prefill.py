"""Pallas TPU kernel: fused paged chunk-prefill attention (§4.1 layout).

One kernel replaces the chunked-prefill hot path's three passes
(``gather_kv`` of the whole prefix, dense attention over the gathered
copy, separate ``write_chunk`` scatter): the chunk's queries walk the
header-centric pool **page by page** through the scalar-prefetched page
table with an online softmax — no dense prefix materialization — and the
chunk's freshly-projected K/V are scattered into the pool **in the same
pass** through an aliased in-place destination (the ``copy_page_slices``
idiom).

Grid: ``(B, n_prefix_pages + n_chunk_pages)``.  For a batch row the
prefix pages are all visited *before* the chunk sub-blocks, preserving
the gather-before-write ordering ring caches rely on (the pool content a
chunk write evicts is attended first); chunk keys are attended last,
matching the jnp path's gather-then-concat key order.  Every visited
pool block is written back (unchanged on prefix steps), so the aliased
output stays coherent; untouched pages are preserved by the aliasing.

Preconditions (the engine's slot-partitioned pools satisfy all three;
``chunk_prefill_eligible`` guards what it can check statically, and the
model treats a refusal as an error):

* chunk boundaries are page boundaries: ``q_positions[:, 0]`` is a
  multiple of ``page_tokens`` (the PrefillPolicy invariant), so each
  chunk sub-block lands wholly inside one pool page;
* the chunk fits the ring capacity (``S <= cap``), so no slot is
  scattered twice within one call;
* batch rows map to disjoint physical pages (scatter steps of row b
  must not alias prefix pages of row b+1).

Validated against ``ref.chunk_prefill_ref`` (dense oracle) and the
bit-exact page-granular mirror ``chunk_prefill_jnp`` in interpret mode
(tests/test_chunk_prefill_kernel.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def chunk_prefill_eligible(pool, chunk_len: int, capacity: int) -> bool:
    """Static shape gate for the fused kernel: a 5-D paged pool (any
    storage layout — the caller canonicalizes) and a chunk no longer
    than the slot capacity (a longer chunk would scatter one slot twice
    in a single pass).  Dynamic preconditions (page-aligned chunk start,
    slot-partitioned page tables) are the engine's invariants and cannot
    be checked on traced values — callers outside the engine must hold
    them or use the jnp path."""
    return pool.ndim == 5 and 0 < chunk_len <= capacity


def _fused_kernel(
    # scalar prefetch
    pt_ref,        # (B, n_pages) int32 — the pool page table
    sp_ref,        # (B, NC) int32 — physical page of each chunk sub-block
    # inputs
    q_ref,         # (1, kvs, M, dh)    the chunk's queries, grouped per
                   #                    kv head (row m = token m // rep)
    qpos_ref,      # (1, M, 1) int32    query position of each row
    kvpos_ref,     # (1, 1, 1, P) int32 pool slot positions of page j
    cpos_ref,      # (1, 1, 1, P) int32 chunk positions of sub-block c
    ckeep_ref,     # (1, 1, P, 1) int32 the same positions, as a column
    knew_ref,      # (1, 1, kvs, P, dh) chunk K of sub-block c
    vnew_ref,      # (1, 1, kvs, P, dh) chunk V of sub-block c
    pool_ref,      # (1, kvs, 2, P, dh) one pool page (aliased input)
    # outputs
    pool_out_ref,  # (1, kvs, 2, P, dh) the same page (aliased)
    o_ref,         # (1, kvs, M, dh)
    # scratch
    m_ref, l_ref, acc_ref,
    *, n_pages: int, n_chunk: int, window: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _attend(k, v, kv_pos):
        # k, v: (kvs, P, dh) f32; kv_pos: (1, P) int32 (-1 = empty)
        q = q_ref[0].astype(jnp.float32)              # (kvs, M, dh)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q * scale, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        # s: (kvs, M, P)
        qp = qpos_ref[0]                              # (M, 1)
        ok = (kv_pos >= 0) & (kv_pos <= qp)           # (M, P)
        if window > 0:
            ok = ok & (kv_pos > qp - window)
        s = jnp.where(ok[None], s, NEG_INF)
        m_prev = m_ref[...]                           # (kvs, M, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    if n_pages > 0:
        @pl.when(j < n_pages)
        def _prefix_page():
            _attend(pool_ref[0, :, 0].astype(jnp.float32),
                    pool_ref[0, :, 1].astype(jnp.float32),
                    kvpos_ref[0, 0])
            # visited blocks must be written back explicitly — the
            # output VMEM block is not seeded from the aliased input
            pool_out_ref[...] = pool_ref[...]

    @pl.when(j >= n_pages)
    def _chunk_page():
        kc = knew_ref[0, 0]                               # (kvs, P, dh)
        vc = vnew_ref[0, 0]
        _attend(kc.astype(jnp.float32), vc.astype(jnp.float32),
                cpos_ref[0, 0])
        # in-pass scatter: chunk start is page-aligned, so sub-block
        # token t has in-page offset t; padded tokens (position < 0, the
        # trailing partial page) keep the old pool bytes
        keep = ckeep_ref[0, 0] >= 0                       # (P, 1)
        dt = pool_out_ref.dtype
        pool_out_ref[0, :, 0] = jnp.where(keep, kc.astype(dt),
                                          pool_ref[0, :, 0])
        pool_out_ref[0, :, 1] = jnp.where(keep, vc.astype(dt),
                                          pool_ref[0, :, 1])

    @pl.when(j == n_pages + n_chunk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _vmem_limit(rows: int, dh: int, P: int, q_dtype, pool_dtype) -> int:
    """Scoped-VMEM request for one grid step: the resident query and
    output blocks and the f32 accumulator (``rows`` = kv heads x padded
    chunk tokens x heads per group), the lane-padded (rows, 1) softmax
    state and query positions, the (rows, P) score temporaries, and the
    double-buffered page blocks; doubled for the compiler's own
    temporaries.  A 512-token chunk of an 8-head, dh-256 model asks for
    about 57 MiB of the 128 MiB a v5e core holds."""
    lane = 128
    qb = jnp.dtype(q_dtype).itemsize
    pb = jnp.dtype(pool_dtype).itemsize
    f32 = 4
    blocks = 2 * 2 * rows * dh * qb            # q in, attention out
    blocks += 2 * rows * lane * 4              # query positions
    blocks += 2 * 4 * 2 * P * dh * pb          # pool page in/out, k/v new
    scratch = rows * dh * f32 + 2 * rows * lane * f32
    temps = 2 * rows * max(P, lane) * f32 + rows * dh * f32
    return min(2 * (blocks + scratch + temps), 100 * 2 ** 20)


def _pad_chunk(q, k_new, v_new, q_positions, P):
    """Pad the chunk to whole pages; padded positions are -1 (invalid as
    keys, masked out of the scatter, sliced off the output)."""
    S = q.shape[1]
    NC = -(-S // P)
    pad = NC * P - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_new = jnp.pad(k_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_new = jnp.pad(v_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    return q, k_new, v_new, q_positions, NC


def chunk_prefill_attention(q, k_new, v_new, pool, page_table,
                            kv_positions, q_positions, *, window: int = 0,
                            attend_prefix: bool = True, interpret=None):
    """Fused paged chunk-prefill attention + in-place pool scatter.

    q:            (B, S, Hq, dh)   chunk queries (RoPE applied)
    k_new, v_new: (B, S, kvs, dh)  chunk K/V (replicated to kv_slots)
    pool:         (NP, kvs, 2, P, dh) canonical header-centric pool
    page_table:   (B, n_pages) int32
    kv_positions: (B, cap) int32   per-slot positions (-1 = empty)
    q_positions:  (B, S) int32     chunk token positions; row starts are
                                   page-aligned (chunking invariant)
    attend_prefix=False skips the pool walk entirely (the first chunk of
    a prompt has an empty prefix).  Returns ``(attn, new_pool)`` with
    attn (B, S, Hq, dh); new_pool holds the chunk's K/V exactly where
    ``pool.write_chunk`` would put them (bit-identical bytes).
    """
    B, S, Hq, dh = q.shape
    NP, kvs, _, P, _ = pool.shape
    assert Hq % kvs == 0
    rep = Hq // kvs
    cap = kv_positions.shape[1]
    mps = cap // P
    n_pages = page_table.shape[1] if attend_prefix else 0

    q, k_new, v_new, qpos, NC = _pad_chunk(q, k_new, v_new,
                                           q_positions, P)
    Sp = NC * P

    # physical destination page of each chunk sub-block: the sub-block
    # starting at token c*P lands at slot (start + c*P) % cap (the ring
    # wrap happens at page granularity because start and cap are both
    # page multiples)
    slot0 = (q_positions[:, :1]
             + jnp.arange(NC, dtype=jnp.int32)[None, :] * P) % cap
    scatter_pages = jnp.take_along_axis(
        page_table, slot0 // P, axis=1).astype(jnp.int32)

    M = Sp * rep
    # queries grouped per kv head, one row per (token, head-in-group):
    # each page step is then two batched 2-D matmuls over the kv heads
    q_g = q.reshape(B, Sp, kvs, rep, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, kvs, M, dh)
    qpos_col = jnp.repeat(qpos, rep, axis=1).reshape(B, M, 1)
    # positions as (1, P) rows (the mask's key axis) and, for the
    # scatter, as (P, 1) columns: every position block then spans its
    # array's last two dimensions whole, as the TPU tiling requires
    kvpos_pg = kv_positions.reshape(B, mps, 1, P)
    cpos_pg = qpos.reshape(B, NC, 1, P)
    ckeep_pg = qpos.reshape(B, NC, P, 1)
    knew_pg = k_new.reshape(B, NC, P, kvs, dh).transpose(0, 1, 3, 2, 4)
    vnew_pg = v_new.reshape(B, NC, P, kvs, dh).transpose(0, 1, 3, 2, 4)

    grid = (B, n_pages + NC)

    def q_index(b, j, pt, sp):
        return (b, 0, 0, 0)

    def qpos_index(b, j, pt, sp):
        return (b, 0, 0)

    def kvpos_index(b, j, pt, sp):
        return (b, jnp.minimum(j, mps - 1), 0, 0)

    def chunk_index(b, j, pt, sp):
        return (b, jnp.clip(j - n_pages, 0, NC - 1), 0, 0)

    def chunk_kv_index(b, j, pt, sp):
        return (b, jnp.clip(j - n_pages, 0, NC - 1), 0, 0, 0)

    if n_pages > 0:
        def pool_index(b, j, pt, sp):
            jj = jnp.minimum(j, n_pages - 1)
            cc = jnp.clip(j - n_pages, 0, NC - 1)
            return (jnp.where(j < n_pages, pt[b, jj], sp[b, cc]),
                    0, 0, 0, 0)
    else:
        def pool_index(b, j, pt, sp):
            return (sp[b, j], 0, 0, 0, 0)

    kernel = functools.partial(_fused_kernel, n_pages=n_pages,
                               n_chunk=NC, window=window)
    # inputs after the 2 prefetch args: q=0 qpos=1 kvpos=2 cpos=3
    # ckeep=4 knew=5 vnew=6 pool=7 → global index 9 aliases output 0
    new_pool, out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kvs, M, dh), q_index),
                pl.BlockSpec((1, M, 1), qpos_index),
                pl.BlockSpec((1, 1, 1, P), kvpos_index),
                pl.BlockSpec((1, 1, 1, P), chunk_index),
                pl.BlockSpec((1, 1, P, 1), chunk_index),
                pl.BlockSpec((1, 1, kvs, P, dh), chunk_kv_index),
                pl.BlockSpec((1, 1, kvs, P, dh), chunk_kv_index),
                pl.BlockSpec((1, kvs, 2, P, dh), pool_index),
            ],
            out_specs=[
                pl.BlockSpec((1, kvs, 2, P, dh), pool_index),
                pl.BlockSpec((1, kvs, M, dh), q_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((kvs, M, 1), jnp.float32),
                pltpu.VMEM((kvs, M, 1), jnp.float32),
                pltpu.VMEM((kvs, M, dh), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((B, kvs, M, dh), q.dtype),
        ],
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(kvs * M, dh, P, q.dtype,
                                         pool.dtype)),
        interpret=_auto_interpret(interpret),
    )(page_table.astype(jnp.int32), scatter_pages,
      q_g, qpos_col.astype(jnp.int32), kvpos_pg.astype(jnp.int32),
      cpos_pg.astype(jnp.int32), ckeep_pg.astype(jnp.int32),
      knew_pg, vnew_pg, pool)
    out = out.reshape(B, kvs, Sp, rep, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Sp, Hq, dh)
    return out[:, :S], new_pool


def chunk_prefill_sharded(mesh, q, k_new, v_new, pool, page_table,
                          kv_positions, q_positions, *, window: int = 0,
                          attend_prefix: bool = True, interpret=None):
    """``chunk_prefill_attention`` on an instance mesh (``(rep, sp,
    tp)`` axes, or ``None`` for a single device).  GSPMD cannot
    partition a Mosaic kernel, so over several devices the call runs
    under ``shard_map``: kv heads and their query groups split over
    ``tp`` (the padding plan makes both divisible), everything else is
    replicated, and each device walks its own heads' pages."""
    call = functools.partial(chunk_prefill_attention, window=window,
                             attend_prefix=attend_prefix,
                             interpret=interpret)
    args = (q, k_new, v_new, pool, page_table, kv_positions, q_positions)
    if mesh is None or mesh.size == 1:
        return call(*args)
    from jax.sharding import PartitionSpec as P
    heads = P(None, None, "tp", None)
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(heads, heads, heads, P(None, "tp"), P(), P(), P()),
        out_specs=(heads, P(None, "tp")), check_vma=False)(*args)


def chunk_prefill_jnp(q, k_new, v_new, pool, page_table, kv_positions,
                      q_positions, *, window: int = 0,
                      attend_prefix: bool = True):
    """Bit-exact page-granular mirror of the fused kernel: the same page
    walk, the same op order, in plain jnp (python loops — a test oracle,
    not a serving path).  Same signature and return as
    ``chunk_prefill_attention``."""
    B, S, Hq, dh = q.shape
    NP, kvs, _, P, _ = pool.shape
    rep = Hq // kvs
    cap = kv_positions.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qp_raw = q_positions
    q, k_new, v_new, qpos, NC = _pad_chunk(q, k_new, v_new,
                                           q_positions, P)
    Sp = NC * P
    n_pages = page_table.shape[1] if attend_prefix else 0

    new_pool = pool
    outs = []
    for b in range(B):
        m = jnp.full((kvs, Sp, rep), NEG_INF, jnp.float32)
        l = jnp.zeros((kvs, Sp, rep), jnp.float32)
        acc = jnp.zeros((kvs, Sp, rep, dh), jnp.float32)
        qb = q[b].astype(jnp.float32)
        qg = (qb.reshape(Sp, kvs, rep, dh) * scale).transpose(1, 0, 2, 3)
        qp = qpos[b]

        def step(k, v, kv_pos, kv_valid, m, l, acc):
            s = jax.lax.dot_general(
                qg, k, (((3,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            ok = kv_valid[None, :] & (kv_pos[None, :] <= qp[:, None])
            if window > 0:
                ok = ok & (kv_pos[None, :] > qp[:, None] - window)
            s = jnp.where(ok[None, :, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jax.lax.dot_general(
                p, v, (((3,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return m_new, l, acc

        for j in range(n_pages):
            page = pool[page_table[b, j]]
            pj = kv_positions[b].reshape(-1, P)[j]
            m, l, acc = step(page[:, 0].astype(jnp.float32),
                             page[:, 1].astype(jnp.float32),
                             pj, pj >= 0, m, l, acc)
        for c in range(NC):
            kc = k_new[b, c * P:(c + 1) * P].transpose(1, 0, 2)
            vc = v_new[b, c * P:(c + 1) * P].transpose(1, 0, 2)
            pj = qpos[b, c * P:(c + 1) * P]
            m, l, acc = step(kc.astype(jnp.float32),
                             vc.astype(jnp.float32), pj, pj >= 0,
                             m, l, acc)
        denom = jnp.maximum(l, 1e-20)[..., None]
        out = (acc / denom).transpose(1, 0, 2, 3).reshape(Sp, Hq, dh)
        outs.append(out.astype(q.dtype))

    # the scatter is write_chunk's (bit-identical bytes)
    slot = qp_raw % cap
    kv = jnp.stack([k_new[:, :S], v_new[:, :S]], axis=3)
    page_idx = jnp.take_along_axis(page_table, slot // P, axis=1)
    new_pool = new_pool.at[page_idx, :, :, slot % P, :].set(
        kv.astype(pool.dtype))
    return jnp.stack(outs)[:, :S], new_pool
