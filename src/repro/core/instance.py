"""A transformable serving instance group (paper §3.4/§4, JAX-native).

The paper merges four TP1 processes into one TP4 process.  The JAX-native
formulation: a host's W devices always form a 3-D mesh ``(rep, sp, tp)``
with ``rep * sp * tp == W`` (``launch.mesh.Layout``).  Request batches
shard over ``rep``; heads / d_ff / KV heads shard over ``tp``; KV *pages*
— the sequence dimension of the paged pool — shard over ``(rep, sp)``,
so an sp shard owns a slice of every slot's context (elastic sequence
parallelism) — with *identical* PartitionSpecs for every layout.  A
parallelism transformation is then exactly:

    re-factorize the mesh (rep, sp, tp) -> (rep', sp', tp')  and
    device_put every live array to the same spec on the new mesh.

XLA lowers that device_put to the all-to-all the paper hand-implements;
the header-centric pool layout makes each shard transfer contiguous (the
head axis is major inside a block), and weight padding makes every weight
shard page- and tile-aligned, so the reshard is pure DMA.

Deviation from the paper (recorded in DESIGN.md §6): we also reshard
attention weights (the paper keeps them duplicated, MLP = 88% of bytes);
set ``transform_attn_weights=False`` to reproduce the faithful behavior.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.padding import PaddingPlan, make_plan
from repro.launch.mesh import Layout
from repro.models import model as M
from repro.paged.pool import PagedState

REP, SP, TP = "rep", "sp", "tp"


# ---------------------------------------------------------------------------
# PartitionSpec trees (identical for every TP degree)
# ---------------------------------------------------------------------------

def _leaf_pspec(path: str, ndim: int, transform_attn: bool) -> P:
    """Sharding rule by parameter name; extra leading dims (layer-group
    stacking) are unsharded."""
    def last(axis):  # shard last dim
        return P(*([None] * (ndim - 1) + [axis]))

    def second_last(axis):
        return P(*([None] * (ndim - 2) + [axis, None]))

    name = path.split("/")[-1]
    attn_names_col = ("wq", "wk", "wv")
    if name in attn_names_col:
        return last(TP) if transform_attn else P()
    if name == "wo" and "attn" in path or name == "wo" and "cross" in path:
        return second_last(TP) if transform_attn else P()
    if name == "wi":
        return last(TP)
    if name == "wo":                      # mlp down-proj
        return second_last(TP)
    if name in ("w_in", "wzifo", "w_zifo", "w_og"):
        return last(TP)
    if name in ("wq_m", "wk_m"):
        return P()
    if name == "w_out":                   # recurrent out projections
        return second_last(TP)
    if name in ("router", "embed", "lm_head", "vision_proj", "frame_proj"):
        return P()                        # replicated (small / gathered)
    return P()                            # norms, gates, biases


def param_pspecs(params, transform_attn: bool = True):
    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, f"{path}/{i}") for i, v in enumerate(tree)]
            return type(tree)(t) if not isinstance(tree, tuple) else tuple(t)
        return _leaf_pspec(path, tree.ndim, transform_attn)
    return walk(params, "")


def layer_cache_pspecs(c, bdim: int = 0):
    """Cache specs for ONE layer's cache tree (``bdim`` = batch axis of
    recurrent-state leaves; stacked group caches pass 1).  KV pools:
    pages over ``(rep, sp)`` (each replica owns its requests' pages; an
    sp shard owns a slice of each page range — sequence parallelism),
    kv heads over ``tp`` — one spec valid for all layouts."""
    if isinstance(c, PagedState):
        from repro.models.shardhints import instance_kv_hint
        nd = c.pool.ndim  # (G?, NP, kvs, 2, P, dh) canonical
        return PagedState(
            pool=instance_kv_hint(lead=nd - 5),
            page_table=P(*([None] * (c.page_table.ndim - 2)), REP, None),
            seq_lens=P(*([None] * (c.seq_lens.ndim - 1)), REP),
            positions=P(*([None] * (c.positions.ndim - 2)), REP, None),
        )
    if isinstance(c, dict):
        return {k: layer_cache_pspecs(v, bdim) for k, v in c.items()}
    if isinstance(c, (list, tuple)):
        res = [layer_cache_pspecs(v, bdim) for v in c]
        return tuple(res) if isinstance(c, tuple) else res
    # recurrent state leaf: batch at dim `bdim` -> shard over rep
    if c.ndim <= bdim:
        return P()
    spec = [None] * c.ndim
    spec[bdim] = REP
    return P(*spec)


def cache_pspecs(caches):
    out = {}
    for k, v in caches.items():
        if k == "rem":
            out[k] = [layer_cache_pspecs(c, 0) for c in v]
        else:
            out[k] = layer_cache_pspecs(v, 1)
    return out


# ---------------------------------------------------------------------------
# Instance group
# ---------------------------------------------------------------------------

class InstanceGroup:
    """W devices serving one model with a transformable TP degree."""

    def __init__(self, cfg: ModelConfig, devices: List[jax.Device],
                 batch_per_replica: int, max_seq: int,
                 page_tokens: int = 16, rng: Optional[jax.Array] = None,
                 transform_attn: bool = True, params=None):
        self.cfg = cfg
        self.devices = devices
        self.W = len(devices)
        self.plan = make_plan(cfg, self.W, mode="page")
        self.batch = batch_per_replica * self.W  # global, fixed across TPs
        self.max_seq = max_seq
        self.page_tokens = page_tokens
        self.transform_attn = transform_attn
        self.tp = 1
        self.par_layout = Layout.of(1)
        self.mesh = self._mesh(1)
        self.transform_count = 0
        self._session = None

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        host_params = params if params is not None else M.init_params(
            rng, cfg, self.plan)
        self._pspecs = param_pspecs(host_params, transform_attn)
        self.params = jax.device_put(host_params,
                                     self._shardings(self._pspecs))
        host_caches = M.init_decode_caches(cfg, self.plan, self.batch,
                                           max_seq, page_tokens)
        self._cspecs = cache_pspecs(host_caches)
        self.caches = jax.device_put(host_caches,
                                     self._shardings(self._cspecs))
        self._decode_jit: Dict[int, Any] = {}

    # -- mesh / sharding helpers ------------------------------------------
    def _mesh(self, layout) -> Mesh:
        from repro.launch.mesh import make_instance_mesh
        return make_instance_mesh(self.devices, layout)

    def _shardings(self, pspec_tree, mesh: Optional[Mesh] = None):
        from repro.core.transform_engine import shard_tree
        return shard_tree(pspec_tree, mesh or self.mesh)

    # -- the paper's §4: the transformation itself -------------------------
    def transform(self, new_tp) -> None:
        """Cross-instance parallelism transformation: re-factorize the mesh
        and reshard every live array (weights + KV pools) to it.
        ``new_tp`` is a TP degree or a full ``Layout``."""
        assert self._session is None, (
            "scheduled transformation in progress: the live state is the "
            "session's per-layer view, not self.params/self.caches")
        lay = Layout.of(new_tp)
        if lay == self.par_layout:
            return
        new_mesh = self._mesh(lay)
        self.params = jax.device_put(
            self.params, self._shardings(self._pspecs, new_mesh))
        self.caches = jax.device_put(
            self.caches, self._shardings(self._cspecs, new_mesh))
        self.mesh = new_mesh
        self.tp = lay.degree
        self.par_layout = lay
        self.transform_count += 1

    # -- §4.3: the scheduled transformation (step-by-step data plane) ------
    def begin_transform(self, new_tp, layers_per_step: int = 1,
                        interpret=None):
        """Start a step-wise transformation: unstack to per-layer state,
        build the §4.3 schedule (MLP-first on scale-up, layer-staggered on
        scale-down, reversed traversal) and return the live
        ``TransformSession``.  While the session is open, ``decode`` runs
        through the per-layer path so serving continues between steps.
        ``new_tp`` is a TP degree or a full ``Layout``."""
        from repro.core import transform_engine as TE

        lay = Layout.of(new_tp)
        return TE.open_owner_session(
            self, lay.degree, self._mesh(lay),
            param_spec_fn=lambda t: param_pspecs(t, self.transform_attn),
            cache_spec_fn=layer_cache_pspecs,
            layers_per_step=layers_per_step, interpret=interpret,
            layout_to=lay)

    def finish_transform(self) -> None:
        """Restack per-layer state once every schedule step has run."""
        from repro.core import transform_engine as TE

        TE.close_owner_session(self)
        self.transform_count += 1

    def transform_scheduled(self, new_tp, layers_per_step: int = 1,
                            between_steps=None, interpret=None):
        """Run a full scheduled transformation; ``between_steps(report)``
        fires after each step (e.g. to interleave decode iterations).
        Returns the per-step ``StepReport`` list."""
        if Layout.of(new_tp) == self.par_layout:
            return []
        session = self.begin_transform(new_tp, layers_per_step, interpret)
        reports = session.run(between_steps)
        self.finish_transform()
        return reports

    # -- serving ------------------------------------------------------------
    def _decode_fn(self):
        if self.tp not in self._decode_jit:
            cfg, plan = self.cfg, self.plan

            def fn(params, caches, tokens, positions):
                return M.decode_step(params, cfg, plan, caches, tokens,
                                     positions)

            self._decode_jit[self.tp] = jax.jit(fn, donate_argnums=(1,))
        return self._decode_jit[self.tp]

    def prefill(self, batch: Dict[str, jax.Array]) -> jax.Array:
        assert self._session is None, (
            "scheduled transformation in progress: prefill would write "
            "into the stale stacked caches that finish_transform discards")
        cfg, plan = self.cfg, self.plan
        with jax.set_mesh(self.mesh):
            logits, self.caches = M.prefill(self.params, cfg, plan, batch,
                                            self.caches)
        return logits

    def decode(self, tokens: jax.Array, positions: jax.Array) -> jax.Array:
        if self._session is not None:
            # mid-transformation: layers live on mixed mesh
            # factorizations, so decode runs the per-layer path
            s = self._session
            logits, s.layers = M.decode_step_layers(
                s.layers, s.static, self.cfg, self.plan, tokens,
                positions, static_mesh=s.static_mesh)
            return logits
        with jax.set_mesh(self.mesh):
            logits, self.caches = self._decode_fn()(
                self.params, self.caches, tokens, positions)
        return logits
