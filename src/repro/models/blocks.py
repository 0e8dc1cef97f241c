"""Per-block-kind parameter init and apply functions.

A "block" is one transformer layer of a given kind (see configs.base):
attn / sliding (attention + dense MLP), moe (attention + MoE MLP),
rglru (Griffin recurrent block + MLP), mlstm, slstm (xLSTM cells),
plus the whisper decoder block (self-attn + cross-attn + MLP).

All params are plain dicts of jnp arrays; every apply function is pure.
Padded slots (heads / d_ff / experts) carry zero weights so the padded
model equals the unpadded model exactly (tests/test_padding.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, MLSTM, MOE, RGLRU, SLIDING, SLSTM,
                                ModelConfig)
from repro.core.padding import PaddingPlan
from repro.kernels import chunk_prefill as CP
from repro.kernels import paged_attention as PA
from repro.models import layers as Lyr
from repro.models import shardhints
from repro.paged import pool as pp

Params = Dict[str, jax.Array]
CONV_K = 4  # griffin temporal conv width


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _dense(rng, fan_in: int, shape, dtype) -> jax.Array:
    return (jax.random.normal(rng, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _head_perm_embed(w: jax.Array, mask, dh: int) -> jax.Array:
    """Zero out padded head slots. w: (d, n_slots*dh); mask: per-slot."""
    d, _ = w.shape
    n = len(mask)
    w = w.reshape(d, n, dh)
    m = jnp.asarray(mask, dtype=w.dtype)[None, :, None]
    return (w * m).reshape(d, n * dh)


# ===========================================================================
# Attention sub-layer (shared by attn / sliding / moe / whisper blocks)
# ===========================================================================

def init_attention(rng, cfg: ModelConfig, plan: PaddingPlan) -> Params:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = plan.q_heads_padded, plan.kv_padded
    dt = _dt(cfg)
    ks = jax.random.split(rng, 4)
    wq = _dense(ks[0], d, (d, Hq * dh), dt)
    wq = _head_perm_embed(wq, plan.q_head_mask(), dh)
    wk = _dense(ks[1], d, (d, Hkv * dh), dt)
    wk = _head_perm_embed(wk, plan.kv_head_mask(), dh)
    wv = _dense(ks[2], d, (d, Hkv * dh), dt)
    wv = _head_perm_embed(wv, plan.kv_head_mask(), dh)
    wo = _dense(ks[3], Hq * dh, (Hq * dh, d), dt)
    # zero rows of wo for padded q slots -> padded heads cannot contribute
    mo = jnp.repeat(jnp.asarray(plan.q_head_mask(), dt), dh)[:, None]
    wo = wo * mo
    return {"wq": wq, "wk": wk, "wv": wv, "wo": wo}


def _project_qkv(p: Params, x: jax.Array, cfg: ModelConfig,
                 plan: PaddingPlan, positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,d) -> q: (B,S,Hq,dh); k,v replicated to kv_slots."""
    B, S, d = x.shape
    dh = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, plan.q_heads_padded, dh)
    k = (x @ p["wk"]).reshape(B, S, plan.kv_padded, dh)
    v = (x @ p["wv"]).reshape(B, S, plan.kv_padded, dh)
    q = Lyr.apply_rope(q, positions, cfg.rope_theta)
    k = Lyr.apply_rope(k, positions, cfg.rope_theta)
    if plan.kv_replication > 1:
        k = jnp.repeat(k, plan.kv_replication, axis=2)
        v = jnp.repeat(v, plan.kv_replication, axis=2)
    return q, k, v


def attention_seq(p: Params, x: jax.Array, cfg: ModelConfig,
                  plan: PaddingPlan, positions: jax.Array,
                  window: int = 0, banded: bool = False
                  ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence (train/prefill) self-attention.
    Returns (out, (k, v)) with k, v: (B, S, kv_slots, dh) for cache fill."""
    B, S, d = x.shape
    q, k, v = _project_qkv(p, x, cfg, plan, positions)
    if banded and window > 0 and S % 512 == 0 and S > window:
        attn = Lyr.banded_attention(q, k, v, positions, positions, window)
    else:
        attn = Lyr.chunked_attention(q, k, v, positions, positions,
                                     causal=True, window=window)
    out = attn.reshape(B, S, -1) @ p["wo"]
    return out, (k, v)


def attention_chunk(p: Params, x: jax.Array, cfg: ModelConfig,
                    plan: PaddingPlan, positions: jax.Array,
                    cache: pp.PagedState, window: int = 0,
                    layout: str = "header_centric",
                    first_chunk: bool = False,
                    identity_pages: bool = False,
                    use_kernel: bool = False,
                    sp: int = 1, mesh=None
                    ) -> Tuple[jax.Array, pp.PagedState]:
    """Chunk-continuation prefill: queries are the chunk's tokens
    (x: (B,S,d), positions: (B,S) global), keys are the CACHED prefix
    plus the chunk itself.

    The cached K/V are gathered BEFORE the chunk is written, then the
    chunk's freshly-projected K/V are appended to the key sequence —
    so ring (sliding-window) caches still see the keys the oldest chunk
    rows need even when writing the chunk would evict them.  For
    full-attention caches (slot == position, no wrap) the valid keys
    appear in ascending position order with only exactly-zero masked
    terms between them, which keeps the online-softmax accumulation
    identical to whole-prompt ``attention_seq`` — chunked prefill is
    bit-exact there (asserted by tests/test_chunked_prefill.py).

    first_chunk=True (static): the prefix is known-empty, so the gather
    + concat of an all-invalid prefix is skipped in both paths.
    use_kernel=True: the fused Pallas kernel walks the paged pool page
    by page (no dense prefix materialization) and scatters the chunk's
    K/V in the same pass; a shape the kernel doesn't cover is an error
    (sequence-parallel layouts, ``sp > 1``, take the jnp path).  ``mesh``
    is the instance mesh the cache lives on: over several devices the
    kernel runs per kv-head shard (``chunk_prefill_sharded``)."""
    B, S, d = x.shape
    q, k, v = _project_qkv(p, x, cfg, plan, positions)
    if use_kernel and sp == 1:
        if not CP.chunk_prefill_eligible(cache.pool, S, cache.capacity):
            raise ValueError(
                f"fused chunk-prefill kernel cannot take this chunk: pool "
                f"rank {cache.pool.ndim}, {S} tokens, capacity "
                f"{cache.capacity}")
        pool_c = pp.canonical(cache.pool, layout)
        attn, pool_c = CP.chunk_prefill_sharded(
            mesh, q, k, v, pool_c, cache.page_table, cache.positions,
            positions, window=window, attend_prefix=not first_chunk)
        cache = pp.adopt_chunk_pool(cache, pool_c, positions, layout)
    else:
        if first_chunk:
            attn = Lyr.chunked_attention(q, k, v, positions, positions,
                                         causal=True, window=window)
        else:
            kk, vv, kv_pos, valid = pp.gather_kv(
                cache, layout, identity_pages=identity_pages)
            kk = jnp.concatenate([kk, k], axis=1)
            vv = jnp.concatenate([vv, v], axis=1)
            kv_pos = jnp.concatenate([kv_pos, positions], axis=1)
            valid = jnp.concatenate(
                [valid, jnp.ones((B, S), dtype=bool)], axis=1)
            attn = Lyr.chunked_attention(q, kk, vv, positions, kv_pos,
                                         kv_valid=valid, causal=True,
                                         window=window, sp=sp)
        cache = pp.write_chunk(cache, k, v, positions, layout,
                               identity_pages=identity_pages)
    out = attn.reshape(B, S, -1) @ p["wo"]
    return out, cache


def attention_decode(p: Params, x: jax.Array, cfg: ModelConfig,
                     plan: PaddingPlan, positions: jax.Array,
                     cache: pp.PagedState, window: int = 0,
                     layout: str = "header_centric",
                     identity_pages: bool = False,
                     use_kernel: bool = False,
                     sp: int = 1, mesh=None
                     ) -> Tuple[jax.Array, pp.PagedState]:
    """One-token decode. x: (B,1,d); positions: (B,1) global positions.
    ``sp > 1`` runs the sequence-parallel page walk: each sp shard walks
    its slice of the slot's pages and the partial softmax states combine
    across the sp axis (see ``Lyr.paged_decode_attention``).
    use_kernel=True (``sp == 1``): the Pallas paged-attention kernel
    reads the slot's live pages in place from the pool
    (``kernels.paged_attention``); ``mesh`` is the instance mesh the
    cache lives on, over which the kernel runs per kv-head and replica
    shard.  Sequence-parallel meshes keep the jnp paths."""
    B, _, d = x.shape
    dh = cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x, cfg, plan, positions)
    cache = pp.append_token(cache, k[:, 0], v[:, 0], layout,
                            identity_pages=identity_pages)
    if (use_kernel and sp == 1 and cache.pool.ndim == 5
            and (mesh is None or mesh.shape["sp"] == 1)):
        attn = PA.paged_attention_sharded(
            mesh, q[:, 0], pp.canonical(cache.pool, layout),
            cache.page_table, cache.seq_lens, cache.positions,
            positions[:, 0], window=window)
        attn = attn[:, None]
    elif identity_pages:
        # §Perf iteration 4: walk the header-centric pool in place (jnp
        # mirror of the Pallas kernel) — no transposed K/V copies.
        pool_c = pp.canonical(cache.pool, layout)
        NP, kvs, _, P, dh2 = pool_c.shape
        pages = pool_c.reshape(B, NP // B, kvs, 2, P, dh2)
        attn = Lyr.paged_decode_attention(q[:, 0], pages, cache.positions,
                                          positions[:, 0], window=window,
                                          sp=sp)
        attn = attn[:, None]
    else:
        kk, vv, kv_pos, valid = pp.gather_kv(cache, layout)
        attn = Lyr.chunked_attention(q, kk, vv, positions, kv_pos,
                                     kv_valid=valid, causal=True,
                                     window=window, sp=sp)
    out = attn.reshape(B, 1, -1) @ p["wo"]
    return out, cache


# ===========================================================================
# Dense MLP sub-layer
# ===========================================================================

def init_mlp(rng, cfg: ModelConfig, plan: PaddingPlan,
             d_ff: Optional[int] = None, d_ff_padded: Optional[int] = None
             ) -> Params:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    ffp = d_ff_padded if d_ff_padded is not None else plan.d_ff_padded
    dt = _dt(cfg)
    k1, k2 = jax.random.split(rng)
    gated = cfg.activation in ("swiglu", "geglu")
    ncol = 2 * ffp if gated else ffp
    wi = _dense(k1, d, (d, ncol), dt)
    wo = _dense(k2, ff, (ffp, d), dt)
    # zero the padded ff columns/rows (paper Eq. 2 equivalence)
    col_mask = (jnp.arange(ffp) < ff).astype(dt)
    if gated:
        wi = wi * jnp.concatenate([col_mask, col_mask])[None, :]
    else:
        wi = wi * col_mask[None, :]
    wo = wo * col_mask[:, None]
    return {"wi": wi, "wo": wo}


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return Lyr.dense_mlp(x, p["wi"], p["wo"], cfg.activation)


# ===========================================================================
# MoE MLP sub-layer (capacity-based top-k routing, expert axis padded)
# ===========================================================================

def init_moe_mlp(rng, cfg: ModelConfig, plan: PaddingPlan) -> Params:
    assert cfg.moe is not None
    d, ff = cfg.d_model, cfg.d_ff
    ffp = plan.d_ff_padded
    E, Ep = plan.num_experts, plan.experts_padded
    dt = _dt(cfg)
    ks = jax.random.split(rng, 4)
    gated = cfg.activation in ("swiglu", "geglu")
    ncol = 2 * ffp if gated else ffp
    wi = _dense(ks[0], d, (Ep, d, ncol), dt)
    wo = _dense(ks[1], ff, (Ep, ffp, d), dt)
    emask = (jnp.arange(Ep) < E).astype(dt)[:, None, None]
    col_mask = (jnp.arange(ffp) < ff).astype(dt)
    cm = jnp.concatenate([col_mask, col_mask]) if gated else col_mask
    wi = wi * emask * cm[None, None, :]
    wo = wo * emask * col_mask[None, :, None]
    out = {"router": _dense(ks[2], d, (d, Ep), dt), "wi": wi, "wo": wo}
    if cfg.moe.shared_expert:
        out["shared"] = init_mlp(ks[3], cfg, plan)
    return out


def apply_moe_mlp(p: Params, x: jax.Array, cfg: ModelConfig,
                  plan: PaddingPlan) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss). Capacity-based top-k routing with drops;
    padded experts are masked to -inf in the router.

    Dispatch is *hierarchical*: tokens are split into ``nb`` blocks (the
    launcher hints nb = the data-axis size) and each block computes its
    own cumsum positions into a per-block capacity slice.  A single global
    cumsum would serialize across every device (§Perf P2 iterations 1/3:
    the global-position scatter lowered to full-buffer all-reduces); the
    blocked form keeps routing local and the expert GEMM shards cleanly
    over (block->data, expert->model)."""
    moe = cfg.moe
    B, S, d = x.shape
    T = B * S
    Ep, E = plan.experts_padded, plan.num_experts
    nb = shardhints.get("moe_blocks") or 1
    while T % nb:
        nb //= 2
    nb = max(nb, 1)
    Tb = T // nb
    xt = x.reshape(nb, Tb, d)
    logits = (xt @ p["router"]).astype(jnp.float32)
    logits = jnp.where(jnp.arange(Ep)[None, None, :] < E, logits, -jnp.inf)
    gates = jax.nn.softmax(logits, axis=-1)                   # (nb, Tb, Ep)
    topv, topi = jax.lax.top_k(gates, moe.top_k)              # (nb, Tb, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(Tb * moe.top_k * moe.capacity_factor / E))
    # block-local position of each (t, k) inside its expert's buffer slice
    onehot = jax.nn.one_hot(topi, Ep, dtype=jnp.int32)    # (nb, Tb, k, Ep)
    flat = onehot.reshape(nb, Tb * moe.top_k, Ep)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat
    pos = (pos_in_e * flat).sum(-1).reshape(nb, Tb, moe.top_k)
    keep = pos < cap
    e_idx = topi
    # dispatch: (nb, Ep, cap, d)
    buf = jnp.zeros((nb, Ep, cap, d), x.dtype)
    b_idx = jnp.broadcast_to(jnp.arange(nb)[:, None, None],
                             (nb, Tb, moe.top_k))
    t_idx = jnp.broadcast_to(jnp.arange(Tb)[None, :, None],
                             (nb, Tb, moe.top_k))
    safe_pos = jnp.where(keep, pos, cap - 1)
    buf = buf.at[b_idx, e_idx, safe_pos].set(
        jnp.where(keep[..., None], xt[b_idx, t_idx], 0), mode="drop")
    buf = shardhints.constrain(buf, "moe_buf")
    # expert computation
    gated = cfg.activation in ("swiglu", "geglu")
    h = jnp.einsum("becd,edf->becf", buf, p["wi"])
    h = shardhints.constrain(h, "moe_hidden")
    if gated:
        g, u = jnp.split(h, 2, axis=-1)
        h = Lyr._act(cfg.activation, g) * u
    else:
        h = Lyr._act(cfg.activation, h)
    yb = jnp.einsum("becf,efd->becd", h, p["wo"])         # (nb, Ep, cap, d)
    # NOTE: yb is deliberately unconstrained — pinning it to the dispatch
    # layout forces the TP all-reduce onto the 12x-inflated capacity
    # buffer instead of the combined token activations (§Perf P2 it. 6)
    yb = shardhints.constrain(yb, "moe_out")
    # combine
    y = (yb[b_idx, e_idx, safe_pos]
         * jnp.where(keep, topv, 0.0)[..., None].astype(x.dtype)).sum(
             axis=2)
    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg)
    # load-balance aux loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi[..., 0], Ep, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(gates, axis=(0, 1))
    aux = jnp.sum(frac_tokens * frac_probs) * (E ** 2) / max(E, 1)
    return y, aux


# ===========================================================================
# Block init / apply dispatch
# ===========================================================================

def init_block(rng, kind: str, cfg: ModelConfig, plan: PaddingPlan) -> Params:
    d = cfg.d_model
    dt = _dt(cfg)
    ks = jax.random.split(rng, 8)
    z = lambda *shape: jnp.zeros(shape, dt)
    if kind in (ATTN, SLIDING, MOE):
        p = {"ln1": z(d), "ln2": z(d),
             "attn": init_attention(ks[0], cfg, plan)}
        if kind == MOE:
            p["mlp"] = init_moe_mlp(ks[1], cfg, plan)
        else:
            p["mlp"] = init_mlp(ks[1], cfg, plan)
        return p
    if kind == RGLRU:
        return {
            "ln1": z(d), "ln2": z(d),
            "w_in": _dense(ks[0], d, (d, 2 * d), dt),
            "conv_w": _dense(ks[1], CONV_K, (CONV_K, d), dt),
            "conv_b": z(d),
            "w_gx": _dense(ks[2], d, (d, d), dt),
            "w_ga": _dense(ks[3], d, (d, d), dt),
            "a_param": jnp.linspace(0.5, 2.0, d).astype(jnp.float32),
            "w_out": _dense(ks[4], d, (d, d), dt),
            "mlp": init_mlp(ks[5], cfg, plan),
        }
    if kind == MLSTM:
        up = 2 * d
        H = cfg.num_heads
        return {
            "ln": z(d),
            "wq": _dense(ks[0], d, (d, up), dt),
            "wk": _dense(ks[1], d, (d, up), dt),
            "wv": _dense(ks[2], d, (d, up), dt),
            "w_if": _dense(ks[3], d, (d, 2 * H), dt),
            "w_og": _dense(ks[4], d, (d, up), dt),
            "w_out": _dense(ks[5], up, (up, d), dt),
        }
    if kind == SLSTM:
        return {
            "ln": z(d),
            "w_zifo": _dense(ks[0], d, (d, 4 * d), dt),
            "r_diag": z(4, d),
            "w_out": _dense(ks[1], d, (d, d), dt),
        }
    raise ValueError(kind)


def _window_of(kind: str, cfg: ModelConfig) -> int:
    """Effective attention window for a block. SLIDING blocks always use
    cfg.window; ATTN/MOE blocks become windowed under the long-context
    variant (cfg.attention == "sliding", see launch.specs)."""
    if kind == SLIDING:
        return cfg.window
    if kind in (ATTN, MOE) and cfg.attention == "sliding":
        return cfg.window
    return 0


def full_attention_capacity(max_seq: int, page_tokens: int) -> int:
    """Page-rounded token capacity of a FULL-ATTENTION paged cache at
    pool allocation ``max_seq`` (see ``init_block_cache``): the
    discriminator the engine uses to tell full-attention PagedStates —
    which track the pool allocation through resizes and distributed-pool
    spill extensions — from window/ring caches, whose capacity is the
    window and never moves."""
    return -(-max_seq // page_tokens) * page_tokens


def is_full_attention_state(state, max_seq: int, page_tokens: int) -> bool:
    """True iff ``state`` is a PagedState sized like a full-attention
    cache at allocation ``max_seq`` — the leaf-selection predicate of
    the pool-resize and KV-spill walkers (only these leaves grow; rings
    keep their window, recurrent leaves carry O(1) state)."""
    from repro.paged import pool as pp
    return (isinstance(state, pp.PagedState)
            and state.positions.shape[-1]
            == full_attention_capacity(max_seq, page_tokens))


def apply_block_seq(kind: str, p: Params, cfg: ModelConfig,
                    plan: PaddingPlan, x: jax.Array, positions: jax.Array,
                    banded: bool = False, want_kv: bool = False,
                    state_in: Optional[Dict] = None):
    """Full-sequence forward for one block.

    Returns (y, extras) where extras carries:
      - ("kv", (k, v)) for attention blocks when want_kv
      - ("state", pytree) recurrent final state for rec blocks (for prefill)
      - ("aux", scalar) MoE aux loss
    """
    extras: Dict = {}
    if kind in (ATTN, SLIDING, MOE):
        h = Lyr.rmsnorm(x, p["ln1"], cfg.norm_eps)
        attn_out, kv = attention_seq(p["attn"], h, cfg, plan, positions,
                                     window=_window_of(kind, cfg),
                                     banded=banded)
        x = x + attn_out
        h = Lyr.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if kind == MOE:
            mlp_out, aux = apply_moe_mlp(p["mlp"], h, cfg, plan)
            extras["aux"] = aux
        else:
            mlp_out = apply_mlp(p["mlp"], h, cfg)
        x = x + mlp_out
        if want_kv:
            extras["kv"] = kv
        return x, extras

    if kind == RGLRU:
        h = Lyr.rmsnorm(x, p["ln1"], cfg.norm_eps)
        u = h @ p["w_in"]
        xb, yb = jnp.split(u, 2, axis=-1)
        conv_state = state_in.get("conv") if state_in else None
        h0 = state_in.get("h") if state_in else None
        xb, conv_state = Lyr.causal_conv1d(xb, p["conv_w"], p["conv_b"],
                                           conv_state)
        gx = xb @ p["w_gx"]
        ga = xb @ p["w_ga"]
        y, h_last = Lyr.rglru(xb, gx, ga, p["a_param"], h0=h0)
        y = y * jax.nn.gelu(yb)
        x = x + y @ p["w_out"]
        h = Lyr.rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h, cfg)
        extras["state"] = {"conv": conv_state, "h": h_last}
        return x, extras

    if kind == MLSTM:
        B, S, d = x.shape
        H = cfg.num_heads
        h = Lyr.rmsnorm(x, p["ln"], cfg.norm_eps)
        up = p["wq"].shape[1]
        dh = up // H
        q = (h @ p["wq"]).reshape(B, S, H, dh)
        k = (h @ p["wk"]).reshape(B, S, H, dh)
        v = (h @ p["wv"]).reshape(B, S, H, dh)
        gif = h @ p["w_if"]
        ig, fg = gif[..., :H], gif[..., H:]
        st = state_in.get("mlstm") if state_in else None
        hh, st = Lyr.mlstm_chunkwise(q, k, v, ig, fg, state=st,
                                     chunk=min(256, S))
        og = jax.nn.sigmoid(h @ p["w_og"])
        out = (hh.reshape(B, S, up) * og) @ p["w_out"]
        extras["state"] = {"mlstm": st}
        return x + out, extras

    if kind == SLSTM:
        B, S, d = x.shape
        h = Lyr.rmsnorm(x, p["ln"], cfg.norm_eps)
        zifo = (h @ p["w_zifo"]).reshape(B, S, 4, d)
        st = state_in.get("slstm") if state_in else None
        hh, st = Lyr.slstm_seq(zifo, p["r_diag"], state=st)
        extras["state"] = {"slstm": st}
        return x + hh @ p["w_out"], extras

    raise ValueError(kind)


def apply_block_chunk(kind: str, p: Params, cfg: ModelConfig,
                      plan: PaddingPlan, x: jax.Array,
                      positions: jax.Array, cache,
                      layout: str = "header_centric",
                      first_chunk: bool = False,
                      identity_pages: bool = False,
                      use_kernel: bool = False,
                      sp: int = 1, mesh=None):
    """Prefill-chunk forward for one block: like ``apply_block_seq``
    but continuing from per-slot cache state.  x: (B,S,d), positions:
    (B,S) global.  Attention kinds attend over cached prefix + chunk
    and write the chunk's K/V into the paged cache; recurrent kinds
    carry their state (the decode-cache tree IS the sequence carry —
    the zero/identity init of ``init_block_cache`` equals the
    ``state=None`` init of the sequence kernels, so the first chunk
    matches ``apply_block_seq`` exactly).  Returns (y, new_cache)."""
    if kind in (ATTN, SLIDING, MOE):
        h = Lyr.rmsnorm(x, p["ln1"], cfg.norm_eps)
        attn_out, cache = attention_chunk(
            p["attn"], h, cfg, plan, positions, cache,
            window=_window_of(kind, cfg), layout=layout,
            first_chunk=first_chunk, identity_pages=identity_pages,
            use_kernel=use_kernel, sp=sp, mesh=mesh)
        x = x + attn_out
        h = Lyr.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if kind == MOE:
            mlp_out, _ = apply_moe_mlp(p["mlp"], h, cfg, plan)
        else:
            mlp_out = apply_mlp(p["mlp"], h, cfg)
        return x + mlp_out, cache
    # recurrent kinds: delegate to the sequence form with the cache as
    # the inbound carry; the returned final state is the new cache
    x, ex = apply_block_seq(kind, p, cfg, plan, x, positions,
                            state_in=cache)
    return x, ex["state"]


def apply_block_decode(kind: str, p: Params, cfg: ModelConfig,
                       plan: PaddingPlan, x: jax.Array,
                       positions: jax.Array, cache,
                       layout: str = "header_centric",
                       identity_pages: bool = False,
                       use_kernel: bool = False,
                       sp: int = 1, mesh=None):
    """Single-token decode for one block. x: (B,1,d). cache is the block's
    state: PagedState for attention kinds, dict for recurrent kinds.
    ``use_kernel`` / ``mesh``: see ``attention_decode``."""
    if kind in (ATTN, SLIDING, MOE):
        h = Lyr.rmsnorm(x, p["ln1"], cfg.norm_eps)
        attn_out, cache = attention_decode(
            p["attn"], h, cfg, plan, positions, cache,
            window=_window_of(kind, cfg), layout=layout,
            identity_pages=identity_pages, use_kernel=use_kernel,
            sp=sp, mesh=mesh)
        x = x + attn_out
        h = Lyr.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if kind == MOE:
            mlp_out, _ = apply_moe_mlp(p["mlp"], h, cfg, plan)
        else:
            mlp_out = apply_mlp(p["mlp"], h, cfg)
        return x + mlp_out, cache

    if kind == RGLRU:
        h = Lyr.rmsnorm(x, p["ln1"], cfg.norm_eps)
        u = h @ p["w_in"]
        xb, yb = jnp.split(u, 2, axis=-1)
        xb, conv_state = Lyr.causal_conv1d(xb, p["conv_w"], p["conv_b"],
                                           cache["conv"])
        gx = (xb @ p["w_gx"])[:, 0]
        ga = (xb @ p["w_ga"])[:, 0]
        hn, hs = Lyr.rglru_step(xb[:, 0], gx, ga, p["a_param"], cache["h"])
        y = hn[:, None, :] * jax.nn.gelu(yb)
        x = x + y @ p["w_out"]
        h = Lyr.rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h, cfg)
        return x, {"conv": conv_state, "h": hs}

    if kind == MLSTM:
        B, _, d = x.shape
        H = cfg.num_heads
        h = Lyr.rmsnorm(x, p["ln"], cfg.norm_eps)
        up = p["wq"].shape[1]
        dh = up // H
        q = (h[:, 0] @ p["wq"]).reshape(B, H, dh)
        k = (h[:, 0] @ p["wk"]).reshape(B, H, dh)
        v = (h[:, 0] @ p["wv"]).reshape(B, H, dh)
        gif = h[:, 0] @ p["w_if"]
        hh, st = Lyr.mlstm_step(q, k, v, gif[..., :H], gif[..., H:],
                                cache["mlstm"])
        og = jax.nn.sigmoid(h @ p["w_og"])
        out = (hh.reshape(B, 1, up) * og) @ p["w_out"]
        return x + out, {"mlstm": st}

    if kind == SLSTM:
        B, _, d = x.shape
        h = Lyr.rmsnorm(x, p["ln"], cfg.norm_eps)
        zifo = (h @ p["w_zifo"]).reshape(B, 1, 4, d)
        hh, st = Lyr.slstm_seq(zifo, p["r_diag"], state=cache["slstm"])
        return x + hh @ p["w_out"], {"slstm": st}

    raise ValueError(kind)


# ===========================================================================
# Decode-cache construction per block kind
# ===========================================================================

def init_block_cache(kind: str, cfg: ModelConfig, plan: PaddingPlan,
                     batch: int, max_seq: int, page_tokens: int,
                     layout: str = "header_centric",
                     specs_only: bool = False):
    d = cfg.d_model
    dt = _dt(cfg)
    mk = (jax.ShapeDtypeStruct if specs_only
          else (lambda shape, dtype: jnp.zeros(shape, dtype)))
    if kind in (ATTN, MOE, SLIDING):
        w = _window_of(kind, cfg)
        cap = max_seq if w == 0 else min(max_seq, w)
        cap = -(-cap // page_tokens) * page_tokens
        mps = cap // page_tokens
        num_pages = batch * mps
        fn = pp.state_specs if specs_only else pp.make_state
        return fn(num_pages, plan.kv_slots, page_tokens,
                  cfg.resolved_head_dim, batch, mps, dt, layout)
    if kind == RGLRU:
        return {"conv": mk((batch, CONV_K - 1, d), dt),
                "h": mk((batch, d), dt)}
    if kind == MLSTM:
        H, up = cfg.num_heads, 2 * d
        dh = up // H
        f32 = jnp.float32
        m0 = (mk((batch, H), f32) if specs_only
              else jnp.full((batch, H), Lyr.NEG_INF, f32))
        return {"mlstm": (mk((batch, H, dh, dh), f32),
                          mk((batch, H, dh), f32), m0)}
    if kind == SLSTM:
        f32 = jnp.float32
        n0 = (mk((batch, d), f32) if specs_only
              else jnp.ones((batch, d), f32))
        return {"slstm": (mk((batch, d), f32), n0,
                          mk((batch, d), f32), mk((batch, d), f32))}
    raise ValueError(kind)
