"""KV-cache transformation across TP configurations (paper §4.1.2).

Two planes:

* **Data plane** (JAX): the actual migration of page pools between
  shardings, as a jitted donate-args reshard.  ``merge_pools`` implements
  TP1 -> TPn (scale-up: page-sharded -> head-sharded) and ``split_pool``
  the reverse.  Content equality is tested in
  tests/test_kv_transform.py and on 8 fake devices in
  tests/test_transform_integration.py.

* **Accounting plane** (host): segment/byte/peak-page accounting that
  reproduces the paper's Fig. 9 comparisons between

      basic           token-first layout + migrate + trim
      header_centric  in-place migration (Gyges-)
      phased          + staged all-to-all with freed-page metadata
                      exchange (Gyges)

  The accounting uses an explicit interconnect model (bytes/bandwidth +
  per-contiguous-segment launch overhead) because segment counts — not
  bytes — are what the layout changes.  Constants are configurable; the
  defaults are NVLink-class to compare against the paper's ms numbers,
  and the TPU ICI numbers are used in the roofline.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.paged import layout as L
from repro.paged.allocator import PageAllocator

# ---------------------------------------------------------------------------
# Interconnect cost model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LinkModel:
    # effective copy bandwidth (below peak NVLink: strided copy kernels)
    bandwidth: float = 150e9      # bytes/s
    segment_overhead: float = 100e-9  # s per contiguous segment (descriptor
    # setup / gather-kernel iteration); this is what fragmentation costs
    # fraction of the transfer hideable behind compute when launched on an
    # independent stream / async DMA (paper §4.1 "Overlapping")
    overlap_fraction: float = 0.85


TPU_ICI = LinkModel(bandwidth=45e9, segment_overhead=50e-9,
                    overlap_fraction=0.9)


@dataclass
class MigrationStats:
    bytes_moved: int = 0
    segments: int = 0
    trim_bytes: int = 0           # extra local copies for compaction
    peak_extra_pages: int = 0     # transient page overhead during migration
    stages: int = 1

    def time_s(self, link: LinkModel, overlap: bool = False) -> float:
        # interconnect traffic + per-segment launch overhead can hide
        # behind decode compute (§4.1 "Overlapping") ...
        transfer = (self.bytes_moved / link.bandwidth
                    + self.segments * link.segment_overhead)
        if overlap:
            transfer *= 1.0 - link.overlap_fraction
        # ... but trims are LOCAL HBM copies serialized with the pool
        # compaction on the critical path — the async-DMA stream does not
        # hide them, so the token-first baseline pays them in full.
        return transfer + self.trim_bytes / link.bandwidth


# ---------------------------------------------------------------------------
# Accounting plane
# ---------------------------------------------------------------------------

def page_bytes(kv_slots: int, page_tokens: int, head_dim: int,
               dtype_bytes: int = 2) -> int:
    return kv_slots * 2 * page_tokens * head_dim * dtype_bytes


def account_scale_up(
    layout: str,
    n_workers: int,
    pages_per_worker: int,
    kv_slots: int,
    page_tokens: int,
    head_dim: int,
    n_stages: int = 1,
    dtype_bytes: int = 2,
) -> MigrationStats:
    """TP1 x n_workers -> TPn migration accounting (paper Fig. 5).

    Every worker keeps heads [w*H/n, (w+1)*H/n) of its local pages and
    sends the other (n-1)/n of every page to the other workers.
    """
    pb = page_bytes(kv_slots, page_tokens, head_dim, dtype_bytes)
    total_pages = n_workers * pages_per_worker
    sent_fraction = (n_workers - 1) / n_workers
    bytes_moved = int(total_pages * pb * sent_fraction)

    segs_per_block = L.contiguous_segments_per_block(
        layout, kv_slots, page_tokens, n_workers)
    # only the (n-1)/n shipped share generates send segments
    segments = int(total_pages * segs_per_block * sent_fraction)

    if layout == "header_centric":
        trim_bytes = 0  # freed space is contiguous: block reshaping, O(1)
        if n_stages <= 1:
            # arrivals land before local frees complete: peak = + incoming
            peak = int(pages_per_worker * sent_fraction) + 1
        else:
            # phased: each stage frees pages whose metadata the next stage
            # reuses (Fig. 5d) -> peak is one stage's worth
            peak = int(pages_per_worker * sent_fraction / n_stages) + 1
    else:
        # token-first: freed bytes are interleaved; trimming copies the
        # surviving 1/n of every local page into fresh pages
        trim_bytes = int(pages_per_worker * pb * (1.0 / n_workers))
        # needs destination pages for remote KV *and* trim scratch
        peak = int(pages_per_worker * sent_fraction) + int(
            pages_per_worker / n_workers) + 1
        n_stages = 1  # phased migration requires in-place reuse
    return MigrationStats(bytes_moved=bytes_moved, segments=segments,
                          trim_bytes=trim_bytes, peak_extra_pages=peak,
                          stages=n_stages)


def sharded_migration_stats(n_workers: int, pages_per_worker: int,
                            kv_slots: int, page_tokens: int,
                            head_dim: int, dtype_bytes: int = 2
                            ) -> MigrationStats:
    """Accounting for ONE ``migrate_scale_up_sharded`` /
    ``migrate_scale_down_sharded`` execution on a ``n_workers``-wide
    mesh: every worker ships the (n-1)/n foreign head-slices of its
    pages, one contiguous segment per (page, destination) pair — the
    header-centric property the kernel path realizes literally.  This
    is what ``core.calibrate`` prices its isolated micro-measurements
    against (and fits ``LinkModel`` from)."""
    return account_scale_up("header_centric", n_workers,
                            pages_per_worker, kv_slots, page_tokens,
                            head_dim, dtype_bytes=dtype_bytes)


def simulate_phased_migration(n_workers: int, pages_per_worker: int,
                              n_stages: int, headroom_pages: int
                              ) -> Tuple[int, bool]:
    """Stage-level simulation of the phased all-to-all (Fig. 5d).

    Each worker starts with ``pages_per_worker`` live pages and
    ``headroom_pages`` free pages.  In each stage it receives 1/n_stages of
    its share of remote pages, then frees 1/n_stages of its shippable local
    pages (header-centric layout: freeing is O(1) block reshaping).  The
    metadata exchange means freed pages are usable by the *next* stage.
    Returns (peak_pages_used, fits_within_headroom)."""
    send_total = pages_per_worker * (n_workers - 1) // n_workers
    recv_total = send_total  # balanced-load assumption (paper §4.3)
    per_stage = max(1, -(-recv_total // n_stages))
    live = pages_per_worker
    capacity = pages_per_worker + headroom_pages
    peak = live
    sent = recv = 0
    fits = True
    while sent < send_total or recv < recv_total:
        r = min(per_stage, recv_total - recv)
        live += r
        recv += r
        peak = max(peak, live)
        if live > capacity:
            fits = False
        s = min(per_stage, send_total - sent)
        live -= s  # contiguous frees: immediately reusable next stage
        sent += s
    return peak, fits


# ---------------------------------------------------------------------------
# Data plane: real pool migration as resharding (runs on any mesh)
# ---------------------------------------------------------------------------

def merge_pools_local(pools: jax.Array, tp: int) -> jax.Array:
    """Reference (single-host) TP1 x W -> TPw merge.

    pools: (W, NP, kv_slots, 2, P, dh) canonical layout — worker w's local
    pages.  Returns (W*NP, kv_slots, 2, P, dh): the union pool, which on a
    real mesh is sharded on the *head* axis instead of the page axis.
    """
    W, NP = pools.shape[:2]
    return pools.reshape(W * NP, *pools.shape[2:])


def split_pool_local(pool: jax.Array, n_workers: int) -> jax.Array:
    """TPn -> TP1 x W reverse reference."""
    NP = pool.shape[0]
    assert NP % n_workers == 0
    return pool.reshape(n_workers, NP // n_workers, *pool.shape[1:])


def reshard_scale_up(pools: jax.Array, mesh: jax.sharding.Mesh,
                     axis: str = "tp") -> jax.Array:
    """The actual Gyges scale-up on a device mesh.

    Input sharding:  pools (W, NP, H, 2, P, dh) sharded on dim 0 (each
    worker holds its own pages, all heads).
    Output sharding: (W*NP, H, 2, P, dh) sharded on dim 1 (every worker
    holds all pages, its head slice) — one all-to-all.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P_

    out_sharding = NamedSharding(mesh, P_(None, axis))

    @jax.jit
    def go(p):
        merged = p.reshape(p.shape[0] * p.shape[1], *p.shape[2:])
        return jax.lax.with_sharding_constraint(merged, out_sharding)

    return go(pools)


def reshard_scale_down(pool: jax.Array, n_workers: int,
                       mesh: jax.sharding.Mesh, axis: str = "tp"
                       ) -> jax.Array:
    from jax.sharding import NamedSharding, PartitionSpec as P_

    out_sharding = NamedSharding(mesh, P_(axis))

    @jax.jit
    def go(p):
        split = p.reshape(n_workers, p.shape[0] // n_workers, *p.shape[1:])
        return jax.lax.with_sharding_constraint(split, out_sharding)

    return go(pool)


# ---------------------------------------------------------------------------
# Data plane: cross-pool migration (live cross-instance merge, paper Fig. 3)
# ---------------------------------------------------------------------------
#
# A live merge parks a donor engine and hands its devices to the target.
# Two pool operations make that real:
#
#   * ``resize_slot_capacity`` — the target's slot-partitioned pools grow
#     by the donors' per-slot allocation (and shrink back on split), so
#     physical KV memory follows the TP degree (the §3.4 memory model);
#   * ``migrate_slot_pages`` — a donor slot's live pages land in the
#     target pool: ``device_put`` moves the bytes across engines, then
#     the §4.1 ``copy_page_slices`` kernel scatters them in place — one
#     contiguous segment per page, the header-centric property.

def resize_slot_capacity(state, new_mps: int, batch: int):
    """Grow or shrink a slot-partitioned ``PagedState`` to ``new_mps``
    pages per slot (identity page tables: slot ``b`` owns pool pages
    ``[b*mps, (b+1)*mps)``).

    Growth appends zero pages to every slot's range (existing content
    keeps its page index within the slot); shrink truncates trailing
    pages, which the caller must have verified empty (every live
    context <= the new capacity).  Handles stacked leading dims (the
    layer-group axis).  Ring/window caches must not be resized — their
    capacity is the attention window, not the sequence ceiling."""
    from repro.paged.pool import PagedState

    pool, pt, seq_lens, pos = state
    mps = pt.shape[-1]
    if mps == new_mps:
        return state
    nd = pool.ndim
    lead = pool.shape[:nd - 5]
    NP, kvs, two, Pg, dh = pool.shape[nd - 5:]
    assert NP == batch * mps, (NP, batch, mps)
    pool_b = pool.reshape(*lead, batch, mps, kvs, two, Pg, dh)
    ax = len(lead) + 1
    if new_mps > mps:
        pad = [(0, 0)] * pool_b.ndim
        pad[ax] = (0, new_mps - mps)
        pool_b = jnp.pad(pool_b, pad)
    else:
        pool_b = jax.lax.slice_in_dim(pool_b, 0, new_mps, axis=ax)
    new_pool = pool_b.reshape(*lead, batch * new_mps, kvs, two, Pg, dh)
    ident = (jnp.arange(batch)[:, None] * new_mps
             + jnp.arange(new_mps)[None, :]).astype(pt.dtype)
    new_pt = jnp.broadcast_to(ident, pt.shape[:-2] + (batch, new_mps))
    pos_b = pos.reshape(*pos.shape[:-1], mps, Pg)
    if new_mps > mps:
        pad = [(0, 0)] * pos_b.ndim
        pad[-2] = (0, new_mps - mps)
        pos_b = jnp.pad(pos_b, pad, constant_values=-1)
    else:
        pos_b = jax.lax.slice_in_dim(pos_b, 0, new_mps, axis=pos_b.ndim - 2)
    new_pos = pos_b.reshape(*pos.shape[:-1], new_mps * Pg)
    return PagedState(new_pool, new_pt, seq_lens, new_pos)


def migrate_slot_pages(src_pool: jax.Array, dst_pool: jax.Array,
                       n_pages: int, dst_page_start: int, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Cross-pool page migration (the live-merge KV import): write the
    first ``n_pages`` pages of ``src_pool`` (a donor slot's page range,
    already ``device_put`` onto the destination devices) into
    ``dst_pool`` starting at page ``dst_page_start``; every other
    destination page is untouched.

    Canonical header-centric pools (5-D, optionally one stacked leading
    dim) take the §4.1 Pallas scatter — ``copy_page_slices`` with the
    full head dimension as ONE slice, i.e. one contiguous segment per
    page, which is exactly the layout property the paper's Fig. 5
    sells.  Anything else falls back to a page-range ``dynamic_update``
    copy of identical content."""
    from repro.kernels import page_migrate as PM

    nd = dst_pool.ndim
    src = src_pool.astype(dst_pool.dtype)
    assert nd == src.ndim and dst_pool.shape[nd - 4:] == src.shape[nd - 4:], (
        f"incompatible page geometry: src {src.shape} vs dst "
        f"{dst_pool.shape}")
    if nd in (5, 6) and (nd == 5 or dst_pool.shape[0] == src.shape[0]):
        kvs = dst_pool.shape[nd - 4]
        src_pages = jnp.arange(n_pages, dtype=jnp.int32)
        zeros = jnp.zeros((n_pages,), jnp.int32)
        dst_pages = dst_page_start + src_pages

        def scatter(s, d):
            return PM.copy_page_slices(s, d, src_pages, zeros, dst_pages,
                                       zeros, heads_per_slice=kvs,
                                       interpret=interpret)

        if nd == 5:
            return scatter(src, dst_pool)
        return jax.vmap(scatter)(src, dst_pool)
    moved = jax.lax.slice_in_dim(src, 0, n_pages, axis=nd - 5)
    return jax.lax.dynamic_update_slice_in_dim(dst_pool, moved,
                                               dst_page_start, axis=nd - 5)


# ---------------------------------------------------------------------------
# Data plane: the explicit kernel path (paper §4.1 as written)
# ---------------------------------------------------------------------------
#
# ``reshard_scale_up`` above delegates the all-to-all to GSPMD; the paper
# instead hand-implements it: each worker extracts contiguous
# per-(page, head-slice) send segments, exchanges them, and DMAs arrivals
# into its local pool.  ``migrate_scale_up_sharded`` is that pipeline —
# pallas gather kernel -> lax.all_to_all -> placement — run per device
# under shard_map, so it executes on a fake-device CPU mesh and on real
# TPUs alike.  Content-equivalence with the GSPMD path is asserted in
# tests/test_transform_integration.py.

@functools.lru_cache(maxsize=64)
def _sharded_migration_jit(direction: str, mesh: jax.sharding.Mesh,
                           axis: str, shape: Tuple[int, ...], dtype,
                           interpret: Optional[bool]):
    """Jitted shard_map pipeline, cached so repeated schedule steps with
    the same geometry reuse one compiled collective instead of
    re-tracing per call (step timing then measures the migration, not
    the compile)."""
    from jax.sharding import PartitionSpec as P_

    from repro.kernels import page_migrate as PM

    W = mesh.shape[axis]
    if direction == "up":
        NPt, H, _, Pg, dh = shape
        assert NPt % W == 0 and H % W == 0, (shape, W)
        NP = NPt // W
        hps = H // W

        def per_worker(local):                  # local: (NP, H, 2, P, dh)
            # send buffer for peer u = my pages' head-slice u — one
            # contiguous segment per (page, destination): the
            # header-centric property
            pages = jnp.tile(jnp.arange(NP, dtype=jnp.int32), W)
            hblk = jnp.repeat(jnp.arange(W, dtype=jnp.int32), NP)
            send = PM.gather_page_slices(local, pages, hblk,
                                         heads_per_slice=hps,
                                         interpret=interpret)
            send = send.reshape(W, NP, hps, 2, Pg, dh)
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # recv[u, p] = peer u's page p, my head slice; global page id
            # u*NP + p -> local placement is the identity layout
            return recv.reshape(W * NP, hps, 2, Pg, dh)

        in_specs, out_specs = P_(axis), P_(None, axis)
    else:
        # shape is the GLOBAL pool: heads sharded over the axis, so the
        # per-worker slice width is H/W
        NPt, H, _, Pg, dh = shape
        assert NPt % W == 0 and H % W == 0, (shape, W)
        NP = NPt // W
        hps = H // W

        def per_worker(local):             # local: (NPt, hps, 2, P, dh)
            # ship to peer u my head-slice of u's pages [u*NP, (u+1)*NP)
            pages = jnp.arange(NPt, dtype=jnp.int32)
            zeros = jnp.zeros((NPt,), jnp.int32)
            send = PM.gather_page_slices(local, pages, zeros,
                                         heads_per_slice=hps,
                                         interpret=interpret)
            send = send.reshape(W, NP, hps, 2, Pg, dh)
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # recv[u, p] = head-slice u of my local page p: scatter each
            # into head block u of page p (in-place adopt; dst aliased)
            dst = jnp.zeros((NP, W * hps, 2, Pg, dh), dtype)
            src_pages = jnp.arange(W * NP, dtype=jnp.int32)
            src_zeros = jnp.zeros((W * NP,), jnp.int32)
            dst_pages = jnp.tile(jnp.arange(NP, dtype=jnp.int32), W)
            dst_hblk = jnp.repeat(jnp.arange(W, dtype=jnp.int32), NP)
            return PM.copy_page_slices(
                recv.reshape(W * NP, hps, 2, Pg, dh), dst, src_pages,
                src_zeros, dst_pages, dst_hblk, heads_per_slice=hps,
                interpret=interpret)

        in_specs, out_specs = P_(None, axis), P_(axis)

    return jax.jit(jax.shard_map(per_worker, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def migrate_scale_up_sharded(pool: jax.Array, mesh: jax.sharding.Mesh,
                             axis: str, *,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Header-centric TP1 x W -> TPW on a 1-D device axis.

    pool: global (NP_total, H, 2, P, dh), page-sharded over ``axis``
    (each of the W workers holds NP_total/W local pages, all heads).
    Returns the same logical array head-sharded over ``axis`` (every
    worker: all pages, its H/W head slice) — moved by the explicit
    gather-kernel + all_to_all data plane.
    """
    return _sharded_migration_jit("up", mesh, axis, pool.shape,
                                  pool.dtype, interpret)(pool)


def migrate_scale_down_sharded(pool: jax.Array, mesh: jax.sharding.Mesh,
                               axis: str, *,
                               interpret: Optional[bool] = None
                               ) -> jax.Array:
    """Reverse of ``migrate_scale_up_sharded``: head-sharded -> page-
    sharded, via per-(page, head-slice) send segments + scatter kernel."""
    return _sharded_migration_jit("down", mesh, axis, pool.shape,
                                  pool.dtype, interpret)(pool)
