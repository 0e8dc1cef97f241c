"""Model assembly: embedding -> scanned block stack -> head.

The layer stack is executed with ``lax.scan`` over *pattern groups* so the
compiled HLO contains each distinct layer kind once regardless of depth
(essential for 48-layer 400B dry-run compiles).  A pattern group is one
repetition of ``cfg.layer_pattern`` (or a single layer for homogeneous
stacks); remainder layers (e.g. recurrentgemma's 38 = 12*3 + 2) are
unrolled explicitly.

Entry points:
    init_params(rng, cfg, plan)
    forward_train(params, cfg, plan, batch)      -> (logits, aux)
    init_decode_caches(cfg, plan, batch, max_seq, ...)
    prefill(params, cfg, plan, batch, caches)    -> (logits_last, caches)
    decode_step(params, cfg, plan, caches, tokens, positions)
                                                 -> (logits, caches)
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, MLSTM, MOE, RGLRU, SLIDING, SLSTM,
                                ModelConfig)
from repro.core.padding import PaddingPlan
from repro.models import blocks as B
from repro.models import layers as Lyr
from repro.paged import pool as pp

PAGE_TOKENS = 64  # tokens per KV page (page bytes scale with kv_slots*dh)


# ---------------------------------------------------------------------------
# Pattern-group bookkeeping
# ---------------------------------------------------------------------------

def pattern_unit(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.layer_pattern if cfg.layer_pattern else cfg.pattern[:1]


def group_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(#scanned groups, #remainder layers)."""
    unit = pattern_unit(cfg)
    return cfg.num_layers // len(unit), cfg.num_layers % len(unit)


def _tree_index(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _run_groups(body, carry, xs, unroll: bool):
    """lax.scan over layer groups, or a Python loop when ``unroll`` — the
    unrolled form is used by the roofline dry-run variants because XLA's
    cost_analysis visits a while body once regardless of trip count."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    G = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for g in range(G):
        carry, y = body(carry, _tree_index(xs, g))
        ys.append(y)
    if ys and ys[0] is not None:
        return carry, _tree_stack(ys)
    return carry, None


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "plan"))
def init_params(rng, cfg: ModelConfig, plan: PaddingPlan) -> Dict[str, Any]:
    """Seeded random weights, as one compiled program, so XLA can fuse
    each float32 draw with its cast to ``cfg.dtype`` instead of running
    (and compiling) every op eagerly."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(rng, 8)

    embed = (jax.random.normal(keys[0], (plan.vocab_padded, cfg.d_model),
                               jnp.float32) * 0.02).astype(dt)
    vmask = (jnp.arange(plan.vocab_padded) < plan.vocab).astype(dt)
    embed = embed * vmask[:, None]

    def init_stacked(rng_k, kind):
        ks = jax.random.split(rng_k, G)
        return jax.vmap(lambda k: B.init_block(k, kind, cfg, plan))(ks)

    bkeys = jax.random.split(keys[1], len(unit))
    blocks = [init_stacked(bkeys[i], kind) for i, kind in enumerate(unit)]

    rkeys = jax.random.split(keys[2], max(R, 1))
    rem = [B.init_block(rkeys[i], unit[i], cfg, plan) for i in range(R)]

    params: Dict[str, Any] = {
        "embed": embed,
        "blocks": blocks,
        "rem": rem,
        "final_ln": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        head = (jax.random.normal(keys[3], (cfg.d_model, plan.vocab_padded),
                                  jnp.float32) * 0.02).astype(dt)
        params["lm_head"] = head * vmask[None, :]

    if cfg.vision is not None:
        params["vision_proj"] = B._dense(keys[4], cfg.d_model,
                                         (cfg.d_model, cfg.d_model), dt)
    if cfg.encoder is not None:
        ekeys = jax.random.split(keys[5], cfg.encoder.num_layers + 2)
        params["encoder"] = {
            "blocks": [jax.vmap(
                lambda k: B.init_block(k, ATTN, cfg, plan))(
                    jax.random.split(ekeys[0], cfg.encoder.num_layers))],
            "final_ln": jnp.zeros((cfg.d_model,), dt),
            "frame_proj": B._dense(ekeys[1], cfg.d_model,
                                   (cfg.d_model, cfg.d_model), dt),
        }
        # cross-attention params per decoder layer (stacked over G)
        xkeys = jax.random.split(keys[6], G)
        params["cross"] = jax.vmap(
            lambda k: {"ln_x": jnp.zeros((cfg.d_model,), dt),
                       **B.init_attention(k, cfg, plan)})(xkeys)
    return params


# ---------------------------------------------------------------------------
# Embedding / head helpers
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (x: (B,S,d), positions: (B,S)). For VLMs, patch embeddings
    (stub frontend output) are prepended to token embeddings."""
    tok = batch["tokens"]
    x = params["embed"][tok]
    if cfg.vision is not None and "patches" in batch:
        img = batch["patches"].astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([img, x], axis=1)
    Btot, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                 (Btot, S))
    return x, positions


def lm_logits(params, cfg: ModelConfig, plan: PaddingPlan, x: jax.Array
              ) -> jax.Array:
    x = Lyr.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    mask = jnp.where(jnp.arange(plan.vocab_padded) < plan.vocab, 0.0,
                     Lyr.NEG_INF)
    return logits.astype(jnp.float32) + mask[None, None, :]


# ---------------------------------------------------------------------------
# Encoder (whisper) — bidirectional over stub frame embeddings
# ---------------------------------------------------------------------------

def run_encoder(params, cfg: ModelConfig, plan: PaddingPlan,
                frames: jax.Array) -> jax.Array:
    enc = params["encoder"]
    x = frames.astype(jnp.dtype(cfg.dtype)) @ enc["frame_proj"]
    Bt, F, d = x.shape
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None, :],
                                 (Bt, F))

    def body(xc, gp):
        h = Lyr.rmsnorm(xc, gp["ln1"], cfg.norm_eps)
        q, k, v = B._project_qkv(gp["attn"], h, cfg, plan, positions)
        attn = Lyr.chunked_attention(q, k, v, positions, positions,
                                     causal=False)
        xc = xc + attn.reshape(Bt, F, -1) @ gp["attn"]["wo"]
        h = Lyr.rmsnorm(xc, gp["ln2"], cfg.norm_eps)
        xc = xc + B.apply_mlp(gp["mlp"], h, cfg)
        return xc, None

    x, _ = jax.lax.scan(body, x, enc["blocks"][0])
    return Lyr.rmsnorm(x, enc["final_ln"], cfg.norm_eps)


def cross_attention(p, x: jax.Array, cfg: ModelConfig, plan: PaddingPlan,
                    mem_k: jax.Array, mem_v: jax.Array) -> jax.Array:
    """x: (B,S,d); mem_k/v: (B,F,kv_slots,dh) precomputed from encoder."""
    Bt, S, d = x.shape
    dh = cfg.resolved_head_dim
    h = Lyr.rmsnorm(x, p["ln_x"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(Bt, S, plan.q_heads_padded, dh)
    qpos = jnp.zeros((Bt, S), jnp.int32)
    kpos = jnp.zeros((Bt, mem_k.shape[1]), jnp.int32)
    attn = Lyr.chunked_attention(q, mem_k, mem_v, qpos, kpos, causal=False)
    return attn.reshape(Bt, S, -1) @ p["wo"]


def encode_cross_kv(params, cfg: ModelConfig, plan: PaddingPlan,
                    enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-decoder-layer cross K/V, stacked over groups: (G,B,F,kvs,dh)."""
    dh = cfg.resolved_head_dim

    def per_layer(cp):
        k = (enc_out @ cp["wk"]).reshape(*enc_out.shape[:2], plan.kv_padded, dh)
        v = (enc_out @ cp["wv"]).reshape(*enc_out.shape[:2], plan.kv_padded, dh)
        if plan.kv_replication > 1:
            k = jnp.repeat(k, plan.kv_replication, axis=2)
            v = jnp.repeat(v, plan.kv_replication, axis=2)
        return k, v

    return jax.lax.map(per_layer, params["cross"])


# ---------------------------------------------------------------------------
# Full-sequence forward (training / teacher forcing)
# ---------------------------------------------------------------------------

def forward_train(params, cfg: ModelConfig, plan: PaddingPlan,
                  batch: Dict[str, jax.Array], banded: bool = False,
                  unroll: bool = False, remat: bool = True
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,Vp), aux_loss scalar).

    remat: activation checkpointing at layer-group granularity (standard
    for training at 4k x 256 batch; without it the dry-run memory analysis
    shows multi-TB activation footprints)."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    x, positions = embed_inputs(params, cfg, batch)

    cross_kv = None
    if cfg.encoder is not None:
        enc_out = run_encoder(params, cfg, plan, batch["frames"])
        cross_kv = encode_cross_kv(params, cfg, plan, enc_out)

    def group_body(carry, xs):
        xc, aux = carry
        gparams = xs[:len(unit)]
        for i, kind in enumerate(unit):
            fn = partial(B.apply_block_seq, unit[i], cfg=cfg, plan=plan,
                         positions=positions, banded=banded)
            blk = (jax.checkpoint(lambda p_, x_: B.apply_block_seq(
                       unit[i], p_, cfg, plan, x_, positions,
                       banded=banded), static_argnums=())
                   if remat else
                   (lambda p_, x_: B.apply_block_seq(
                       unit[i], p_, cfg, plan, x_, positions,
                       banded=banded)))
            xc, ex = blk(gparams[i], xc)
            if "aux" in ex:
                aux = aux + ex["aux"]
        if cfg.encoder is not None:
            cp, (ck, cv) = xs[len(unit)], xs[len(unit) + 1]
            xc = xc + cross_attention(cp, xc, cfg, plan, ck, cv)
        return (xc, aux), None

    xs: Tuple = tuple(params["blocks"])
    if cfg.encoder is not None:
        xs = xs + (params["cross"], cross_kv)
    (x, aux), _ = _run_groups(group_body, (x, jnp.float32(0.0)), xs,
                              unroll)

    for i in range(R):
        x, ex = B.apply_block_seq(unit[i], params["rem"][i], cfg, plan, x,
                                  positions, banded=banded)
        if "aux" in ex:
            aux = aux + ex["aux"]

    return lm_logits(params, cfg, plan, x), aux


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_decode_caches(cfg: ModelConfig, plan: PaddingPlan, batch: int,
                       max_seq: int, page_tokens: int = PAGE_TOKENS,
                       layout: str = "header_centric",
                       specs_only: bool = False) -> Dict[str, Any]:
    """Caches mirror the params structure: one stacked cache per pattern
    position (+ per-remainder-layer caches + cross-attn memory)."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)

    def one(kind, stacked: bool):
        c = B.init_block_cache(kind, cfg, plan, batch, max_seq, page_tokens,
                               layout, specs_only=specs_only)
        if not stacked:
            return c
        if specs_only:
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((G,) + s.shape, s.dtype), c)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (G,) + a.shape).copy(), c)

    caches: Dict[str, Any] = {
        "groups": [one(kind, True) for kind in unit],
        "rem": [one(unit[i], False) for i in range(R)],
    }
    if cfg.encoder is not None:
        F = cfg.encoder.num_frames
        shp = (G, batch, F, plan.kv_slots, cfg.resolved_head_dim)
        dt = jnp.dtype(cfg.dtype)
        mk = (jax.ShapeDtypeStruct if specs_only
              else (lambda s, d: jnp.zeros(s, d)))
        caches["cross_kv"] = (mk(shp, dt), mk(shp, dt))
    return caches


# ---------------------------------------------------------------------------
# Prefill: run the prompt, fill the caches
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, plan: PaddingPlan,
            batch: Dict[str, jax.Array], caches: Dict[str, Any],
            layout: str = "header_centric", banded: bool = False,
            unroll: bool = False) -> Tuple[jax.Array, Dict[str, Any]]:
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    x, positions = embed_inputs(params, cfg, batch)

    if cfg.encoder is not None:
        enc_out = run_encoder(params, cfg, plan, batch["frames"])
        caches = dict(caches)
        caches["cross_kv"] = encode_cross_kv(params, cfg, plan, enc_out)

    def group_body(x_carry, xs):
        xc = x_carry
        gparams = xs[:len(unit)]
        gcaches = list(xs[len(unit):len(unit) * 2])
        for i, kind in enumerate(unit):
            if kind in (ATTN, SLIDING, MOE):
                xc, ex = B.apply_block_seq(kind, gparams[i], cfg, plan, xc,
                                           positions, banded=banded,
                                           want_kv=True)
                k, v = ex["kv"]
                gcaches[i] = pp.write_prefill(gcaches[i], k, v, layout)
            else:
                xc, ex = B.apply_block_seq(kind, gparams[i], cfg, plan, xc,
                                           positions)
                gcaches[i] = ex["state"]
        if cfg.encoder is not None:
            cp, (ck, cv) = xs[-2], xs[-1]
            xc = xc + cross_attention(cp, xc, cfg, plan, ck, cv)
        return xc, tuple(gcaches)

    xs: Tuple = tuple(params["blocks"]) + tuple(caches["groups"])
    if cfg.encoder is not None:
        xs = xs + (params["cross"], caches["cross_kv"])
    x, new_group_caches = _run_groups(group_body, x, xs, unroll)

    new_rem = []
    for i in range(R):
        kind = unit[i]
        if kind in (ATTN, SLIDING, MOE):
            x, ex = B.apply_block_seq(kind, params["rem"][i], cfg, plan, x,
                                      positions, banded=banded, want_kv=True)
            k, v = ex["kv"]
            new_rem.append(pp.write_prefill(caches["rem"][i], k, v, layout))
        else:
            x, ex = B.apply_block_seq(kind, params["rem"][i], cfg, plan, x,
                                      positions)
            new_rem.append(ex["state"])

    out = {"groups": list(new_group_caches), "rem": new_rem}
    if cfg.encoder is not None:
        out["cross_kv"] = caches["cross_kv"]
    logits = lm_logits(params, cfg, plan, x[:, -1:, :])
    return logits, out


# ---------------------------------------------------------------------------
# Chunked prefill: one page-aligned chunk of the prompt per call
# ---------------------------------------------------------------------------

def prefill_chunk(params, cfg: ModelConfig, plan: PaddingPlan,
                  tokens: jax.Array, start_pos: jax.Array,
                  caches: Dict[str, Any],
                  layout: str = "header_centric",
                  first_chunk: bool = False,
                  identity_pages: bool = False,
                  use_kernel: bool = False,
                  sp: int = 1, mesh=None
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run ONE prefill chunk and fold it into the caches.

    tokens: (B, S) the chunk's token ids; start_pos: (B,) global
    position of the chunk's first token (traced — one compile per chunk
    SHAPE, not per offset).  Attention layers attend over the cached
    prefix plus the chunk and write the chunk's K/V through the paged
    pool (``pool.write_chunk``); recurrent layers carry their
    decode-cache state across chunks.  With ``start_pos == 0`` on fresh
    caches the result is equivalent to ``prefill`` (bit-exact for
    full-attention models; see ``blocks.attention_chunk``), so the
    serving engine's token-budgeted chunked prefill emits the same
    streams as the whole-prompt path it replaces.

    MoE capacity routing is evaluated per chunk — with capacity-based
    token dropping the dropped set can differ from whole-prompt
    evaluation, exactly as it differs across batch shapes.  Encoder /
    vision frontends are not chunkable (their memory is not causal);
    the engine keeps those prompts whole.  ``mesh`` is the instance mesh
    the caches live on (the fused kernel runs per kv-head shard there).
    """
    if cfg.encoder is not None or cfg.vision is not None:
        raise NotImplementedError(
            "chunked prefill covers causal decoder-only models")
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    S = tokens.shape[1]
    x = params["embed"][tokens]
    positions = start_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    B_chunk = B.apply_block_chunk

    def group_body(x_carry, xs):
        xc = x_carry
        gparams = xs[:len(unit)]
        gcaches = list(xs[len(unit):len(unit) * 2])
        for i, kind in enumerate(unit):
            xc, gcaches[i] = B_chunk(kind, gparams[i], cfg, plan, xc,
                                     positions, gcaches[i], layout,
                                     first_chunk=first_chunk,
                                     identity_pages=identity_pages,
                                     use_kernel=use_kernel, sp=sp,
                                     mesh=mesh)
        return xc, tuple(gcaches)

    xs: Tuple = tuple(params["blocks"]) + tuple(caches["groups"])
    x, new_group_caches = _run_groups(group_body, x, xs, False)

    new_rem = []
    for i in range(R):
        x, c = B_chunk(unit[i], params["rem"][i], cfg, plan, x,
                       positions, caches["rem"][i], layout,
                       first_chunk=first_chunk,
                       identity_pages=identity_pages,
                       use_kernel=use_kernel, sp=sp, mesh=mesh)
        new_rem.append(c)

    out = {"groups": list(new_group_caches), "rem": new_rem}
    logits = lm_logits(params, cfg, plan, x[:, -1:, :])
    return logits, out


# ---------------------------------------------------------------------------
# Decode step: one token for every sequence in the batch
# ---------------------------------------------------------------------------

def decode_step(params, cfg: ModelConfig, plan: PaddingPlan,
                caches: Dict[str, Any], tokens: jax.Array,
                positions: jax.Array, layout: str = "header_centric",
                unroll: bool = False, identity_pages: bool = False,
                use_kernel: bool = False, sp: int = 1, mesh=None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens: (B,) int32; positions: (B,) global positions.  ``sp`` is
    the sequence-parallel shard count of the engine's current layout
    (``Layout.sp``): >1 computes attention in the per-shard-partials +
    cross-shard-combine form matching the pool's page sharding.
    ``use_kernel`` runs decode attention through the Pallas paged-
    attention kernel over each slot's live pages (``sp == 1``); ``mesh``
    is the instance mesh the caches live on (see
    ``blocks.attention_decode``)."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    x = params["embed"][tokens][:, None, :]          # (B,1,d)
    pos2 = positions[:, None]

    def group_body(xc, xs):
        gparams = xs[:len(unit)]
        gcaches = list(xs[len(unit):len(unit) * 2])
        for i, kind in enumerate(unit):
            xc, gcaches[i] = B.apply_block_decode(
                kind, gparams[i], cfg, plan, xc, pos2, gcaches[i], layout,
                identity_pages=identity_pages, use_kernel=use_kernel,
                sp=sp, mesh=mesh)
        if cfg.encoder is not None:
            cp, (ck, cv) = xs[-2], xs[-1]
            xc = xc + cross_attention(cp, xc, cfg, plan, ck, cv)
        return xc, tuple(gcaches)

    xs: Tuple = tuple(params["blocks"]) + tuple(caches["groups"])
    if cfg.encoder is not None:
        xs = xs + (params["cross"], caches["cross_kv"])
    x, new_group_caches = _run_groups(group_body, x, xs, unroll)

    new_rem = []
    for i in range(R):
        x, c = B.apply_block_decode(unit[i], params["rem"][i], cfg, plan, x,
                                    pos2, caches["rem"][i], layout,
                                    identity_pages=identity_pages,
                                    use_kernel=use_kernel, sp=sp, mesh=mesh)
        new_rem.append(c)

    out = {"groups": list(new_group_caches), "rem": new_rem}
    if cfg.encoder is not None:
        out["cross_kv"] = caches["cross_kv"]
    logits = lm_logits(params, cfg, plan, x)[:, 0, :]
    return logits, out


# ---------------------------------------------------------------------------
# Per-layer (unstacked) decode: the transformation-time execution path
# ---------------------------------------------------------------------------
#
# A live TP transformation moves the model ONE layer at a time (paper
# §4.3: MLP-first / layer-staggered / reversed traversal), so mid-
# transform different layers live on different mesh factorizations.  The
# scan-stacked representation cannot express that (one jax.Array covers
# every layer of a pattern position), so a transforming instance unstacks
# into per-layer trees, decodes through this path while the schedule
# executes, and restacks when the transformation completes.  Values are
# bit-identical to the stacked path — only the iteration strategy
# changes.
#
# CROSS-DEVICE sessions (merge/split) add one more ingredient: layer
# dicts carry a ``"mesh"`` tag and each layer lives on exactly one
# coherent device assembly (the session enforces a layer-coherent
# schedule), so the per-layer paths below ``device_put`` the activations
# once at the boundary between migrated and not-yet-migrated layers —
# decode and chunked prefill keep running through the session.

def unstack_cache_tree(caches: Dict[str, Any], cfg: ModelConfig
                       ) -> List[Any]:
    """Split a stacked cache-shaped tree (``{"groups": [...], "rem":
    [...]}`` — decode caches or a prefill recurrent carry, which may
    hold ``None`` where pools were stripped) into execution-ordered
    per-layer trees."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    out: List[Any] = []
    for g in range(G):
        for i in range(len(unit)):
            out.append(_tree_index(caches["groups"][i], g))
    out.extend(caches["rem"][i] for i in range(R))
    return out


def restack_cache_tree(layer_caches: List[Any], cfg: ModelConfig
                       ) -> Dict[str, Any]:
    """Inverse of ``unstack_cache_tree``."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    return {
        "groups": [
            _tree_stack([layer_caches[g * len(unit) + i]
                         for g in range(G)])
            for i in range(len(unit))],
        "rem": list(layer_caches[G * len(unit):]),
    }


def unstack_decode_state(params, cfg: ModelConfig, caches: Dict[str, Any]
                         ) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """Split stacked params+caches into execution-ordered per-layer
    entries ``{"kind", "params", "cache"}`` plus the non-layer ``static``
    params (embed / final_ln / lm_head)."""
    if cfg.encoder is not None or cfg.vision is not None:
        raise NotImplementedError(
            "per-layer transformation does not cover encoder/vision yet")
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    layer_caches = unstack_cache_tree(caches, cfg)
    layers: List[Dict[str, Any]] = []
    for g in range(G):
        for i, kind in enumerate(unit):
            layers.append({
                "kind": kind,
                "params": _tree_index(params["blocks"][i], g),
                "cache": layer_caches[g * len(unit) + i],
            })
    for i in range(R):
        layers.append({"kind": unit[i], "params": params["rem"][i],
                       "cache": layer_caches[G * len(unit) + i]})
    static = {k: v for k, v in params.items() if k not in ("blocks", "rem")}
    return layers, static


def restack_decode_state(layers: List[Dict[str, Any]],
                         static: Dict[str, Any], cfg: ModelConfig
                         ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Inverse of ``unstack_decode_state``."""
    unit = pattern_unit(cfg)
    G, R = group_counts(cfg)
    params: Dict[str, Any] = dict(static)
    params["blocks"] = [
        _tree_stack([layers[g * len(unit) + i]["params"]
                     for g in range(G)])
        for i in range(len(unit))]
    params["rem"] = [l["params"] for l in layers[G * len(unit):]]
    caches = restack_cache_tree([l["cache"] for l in layers], cfg)
    return params, caches


def _assembly(mesh) -> Optional[frozenset]:
    """The device set a mesh spans (None when untracked)."""
    return None if mesh is None else frozenset(mesh.devices.flat)


def _boundary_put(x: jax.Array, mesh, cur: Optional[frozenset]
                  ) -> Tuple[jax.Array, Optional[frozenset]]:
    """Move the activation onto ``mesh``'s device assembly (replicated)
    iff it currently lives on a DIFFERENT assembly — the one explicit
    transfer at the boundary between already-migrated and
    not-yet-migrated layers of a cross-device transform session.
    Same-assembly transitions (in-place re-factorizations) are free:
    mixed shardings on one device set compose without a copy."""
    if mesh is None:
        return x, cur
    devs = _assembly(mesh)
    if cur is not None and devs != cur:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        x = jax.device_put(x, NamedSharding(mesh, P()))
    return x, devs


# The per-layer paths run one compiled program per layer.  Dispatched op
# by op instead, every Pallas call (and every shard_map around one)
# would be traced, lowered and compiled anew on each call.
@partial(jax.jit, static_argnames=("kind", "cfg", "plan", "layout",
                                   "identity_pages", "use_kernel", "mesh"))
def _block_decode(p, x, positions, cache, *, kind, cfg, plan, layout,
                  identity_pages, use_kernel, mesh):
    return B.apply_block_decode(kind, p, cfg, plan, x, positions, cache,
                                layout, identity_pages=identity_pages,
                                use_kernel=use_kernel, mesh=mesh)


@partial(jax.jit, static_argnames=("kind", "cfg", "plan", "layout",
                                   "first_chunk", "identity_pages",
                                   "use_kernel", "mesh"))
def _block_chunk(p, x, positions, cache, *, kind, cfg, plan, layout,
                 first_chunk, identity_pages, use_kernel, mesh):
    return B.apply_block_chunk(kind, p, cfg, plan, x, positions, cache,
                               layout, first_chunk=first_chunk,
                               identity_pages=identity_pages,
                               use_kernel=use_kernel, mesh=mesh)


def decode_step_layers(layers: List[Dict[str, Any]],
                       static: Dict[str, Any], cfg: ModelConfig,
                       plan: PaddingPlan, tokens: jax.Array,
                       positions: jax.Array,
                       layout: str = "header_centric",
                       identity_pages: bool = False,
                       static_mesh=None, on_layer=None,
                       use_kernel: bool = False
                       ) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """One decode step over per-layer state; numerically identical to
    ``decode_step`` on the restacked equivalents.

    Mid-cross-device-session the layers span TWO device assemblies (each
    layer coherently on one); layer dicts then carry a ``"mesh"`` tag
    and ``static_mesh`` locates the embed/head params — activations are
    ``device_put`` once per assembly boundary, so a single decode step
    runs across the mixed state without stalling.  With ``use_kernel``
    each layer's decode attention runs the paged-attention kernel on
    that layer's mesh.

    ``on_layer(i)`` (optional) is called after layer ``i``'s compute has
    been enqueued — the hook a transform session uses to stream the next
    layer's weights while this one computes (intra-step overlap)."""
    x = static["embed"][tokens][:, None, :]
    pos2 = positions[:, None]
    cur = _assembly(static_mesh)
    new_layers = []
    for i, layer in enumerate(layers):
        x, cur = _boundary_put(x, layer.get("mesh"), cur)
        x, c = _block_decode(layer["params"], x, pos2, layer["cache"],
                             kind=layer["kind"], cfg=cfg, plan=plan,
                             layout=layout, identity_pages=identity_pages,
                             use_kernel=use_kernel,
                             mesh=layer.get("mesh") if use_kernel else None)
        new_layers.append({**layer, "cache": c})
        if on_layer is not None:
            on_layer(i)
    x, cur = _boundary_put(x, static_mesh, cur)
    logits = lm_logits(static, cfg, plan, x)[:, 0, :]
    return logits, new_layers


def prefill_chunk_layers(layers: List[Dict[str, Any]],
                         static: Dict[str, Any], cfg: ModelConfig,
                         plan: PaddingPlan, tokens: jax.Array,
                         start_pos: jax.Array, slot_caches: List[Any],
                         layout: str = "header_centric",
                         static_mesh=None,
                         first_chunk: bool = False,
                         identity_pages: bool = False,
                         use_kernel: bool = False
                         ) -> Tuple[jax.Array, List[Any]]:
    """One prefill chunk through per-layer (unstacked) state — the
    mid-transform twin of ``prefill_chunk``, so chunked prefill keeps
    advancing while a session migrates layers.

    ``slot_caches`` are the caller's per-layer batch-1 slot cache views
    (each already resident on its layer's assembly); the chunk attends
    over cached prefix + chunk and the updated views are returned for
    the caller to scatter back into the per-layer engine caches.
    Activations cross assembly boundaries exactly like
    ``decode_step_layers``."""
    if cfg.encoder is not None or cfg.vision is not None:
        raise NotImplementedError(
            "chunked prefill covers causal decoder-only models")
    S = tokens.shape[1]
    x = static["embed"][tokens]
    positions = start_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    cur = _assembly(static_mesh)
    new_caches = []
    for layer, c in zip(layers, slot_caches):
        x, cur = _boundary_put(x, layer.get("mesh"), cur)
        x, c = _block_chunk(layer["params"], x, positions, c,
                            kind=layer["kind"], cfg=cfg, plan=plan,
                            layout=layout, first_chunk=first_chunk,
                            identity_pages=identity_pages,
                            use_kernel=use_kernel,
                            mesh=layer.get("mesh") if use_kernel else None)
        new_caches.append(c)
    x, cur = _boundary_put(x, static_mesh, cur)
    logits = lm_logits(static, cfg, plan, x[:, -1:, :])
    return logits, new_caches
