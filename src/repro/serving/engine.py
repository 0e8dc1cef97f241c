"""Continuous-batching serving engine over the paged KV substrate.

Slot-based continuous batching (Orca-style iteration-level scheduling):
the decode batch has ``max_batch`` fixed slots; a request occupies one
slot from prefill until EOS/limit, then the slot is immediately reusable.
The KV pool is slot-partitioned (identity page tables).

Prefill is CHUNKED and policy-driven (``core.scheduler.PrefillPolicy``
— the same object the simulator models): each engine step spends up to
the policy's token budget advancing partially-prefilled slots by
page-aligned chunks (``models.model.prefill_chunk``), in the policy's
priority mode (prefill-first, decode-first with bounded deferral, or
mixed) and service order (FCFS / shortest-remaining-first).  A
partially-prefilled slot's KV lives in the engine's paged pool like any
other slot's — whole pages plus at most one trailing partial page — so
page migration (``copy_page_slices``) and transform/merge sessions
remain valid mid-prefill; ALL prefills keep ADVANCING while a session
is open (per-layer chunk path — whole-prompt plans run as one
first-chunk call).  The default policy (no budget) degenerates to the
classic one-whole-prompt-per-step prefill.

Two placements:

  * single device (default) — the unit-test configuration;
  * ``devices=[...]`` — the engine owns a ``(rep, sp, tp)`` mesh over
    those devices (the paper's instance group) and its parallelism
    layout can be **transformed live**: ``transform(tp_to)`` (optionally
    with a full ``layout=Layout(sp, tp)``) builds the §4.3 schedule
    and ``step()`` executes ONE schedule step before each decode
    iteration, so page migration (pallas gather/scatter + all_to_all)
    interleaves with serving and in-flight request KV crosses the TP
    boundary bit-exactly.  Exercised by tests/test_transform_integration
    and examples/serve_transform.py.

The engine also implements the ``InstanceView`` protocol from
``core/scheduler.py`` (load, kv_used_fraction, max_seq, kv_free_tokens,
has_long_request, reserved, width), so the §5 scheduler that drives the
simulator drives live engines unchanged — ``serving/cluster.py`` is that
control plane.  The physical-vs-policy capacity contract
(``max_seq_alloc`` vs ``max_seq()``) is defined in ONE place:
``Engine.max_seq_at``.  Engines also participate in cross-instance
merges (adopt_devices / park / revive / export_active /
import_request — see the "merge lifecycle" section below and
docs/transformation-lifecycle.md).
"""
from __future__ import annotations

import itertools
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.padding import PaddingPlan, make_plan
from repro.core.scheduler import PrefillPolicy
from repro.launch.mesh import Layout
from repro.models import model as M
from repro.serving import tracing
from repro.serving.request import ServeRequest, State


def _sample(logits: jax.Array, temperature: float, rng: jax.Array
            ) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature).astype(jnp.int32)


class Engine:
    _ids = itertools.count()

    def __init__(self, cfg: ModelConfig, params=None, max_batch: int = 4,
                 max_seq: int = 256, page_tokens: int = 16,
                 rng: Optional[jax.Array] = None,
                 layout: str = "header_centric",
                 devices: Optional[List[jax.Device]] = None,
                 transform_attn: bool = True,
                 iid: Optional[int] = None,
                 plan: Optional[PaddingPlan] = None,
                 prefill_policy: Optional[PrefillPolicy] = None,
                 clock=None,
                 pallas_kernels: Optional[bool] = None):
        """``plan`` overrides the padding plan; a cluster whose engines
        may MERGE must pass one built for the full device-pool width so
        weight shard boundaries stay page-aligned at every reachable TP
        degree (a wider plan is valid at any narrower degree).

        ``clock`` is the REQUEST-timestamp source (default wall clock):
        an event-driven replay injects a ``core.events.VirtualClock`` so
        TTFT/TPOT/goodput are measured in virtual trace time.  Data-
        plane measurements (transform ``wall_s``, ``StepReport`` spans)
        deliberately stay on the wall clock — they time real device
        work, not the serving schedule.

        ``pallas_kernels`` routes chunk prefills through the fused
        Pallas paged-attention + scatter kernel
        (``kernels.chunk_prefill``) and decode attention through the
        Pallas paged-attention kernel over each slot's live pages
        (``kernels.paged_attention``).  Default (None) enables both on
        real TPU backends only: off-TPU the kernels run in interpret
        mode — correct but slow — and the jnp paths keep CI streams
        bit-identical to the pre-kernel engine."""
        self.cfg = cfg
        self._clock = clock if clock is not None else time.monotonic
        self.devices = list(devices) if devices else None
        self.W = len(devices) if devices else 1
        if plan is not None:
            self.plan = plan
        else:
            self.plan = (make_plan(cfg, self.W, mode="page") if devices
                         else make_plan(cfg, 1))
        self.max_batch = max_batch
        self.max_seq_alloc = max_seq
        self.page_tokens = page_tokens
        self.iid = iid if iid is not None else next(Engine._ids)
        self.reserved = False
        self.layout = layout
        self.transform_attn = transform_attn
        # -- capacity contract (THE one place; see max_seq_at) ----------
        # seq_quantum is the per-device admission share, FROZEN at
        # construction; max_seq_alloc (the allocated per-slot pool
        # ceiling) tracks seq_quantum * W as devices are adopted and
        # released, so physical KV always backs the policy ceiling.
        if devices:
            assert max_seq % self.W == 0, (
                f"max_seq={max_seq} must divide over the {self.W} devices"
                " (per-device admission quantum must be whole)")
            assert max_seq % page_tokens == 0, (
                f"max_seq={max_seq} must be page-aligned "
                f"(page_tokens={page_tokens}) so merge-time pool resizes "
                "stay pure page-range copies")
        self.seq_quantum = max_seq // self.W if devices else max_seq
        # -- cross-instance merge lifecycle -----------------------------
        self.home_devices = list(devices) if devices else None
        self.adopted_devices: List[jax.Device] = []
        self.parked = False
        self._pending_devices: Optional[List[jax.Device]] = None
        self._session_cross = False
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.rng = rng
        self.params = params if params is not None else M.init_params(
            jax.random.fold_in(rng, 1), cfg, self.plan)
        self.caches = M.init_decode_caches(cfg, self.plan, max_batch,
                                           self.max_seq_alloc, page_tokens,
                                           layout)
        self.slots: List[Optional[ServeRequest]] = [None] * max_batch
        self.waiting: List[ServeRequest] = []
        # -- chunked prefill (core.scheduler.PrefillPolicy) -------------
        self.prefill_policy = prefill_policy or PrefillPolicy()
        # slot -> {"req", "chunks", "ci", "done", "rec"}: page-aligned
        # chunk plan, progress, and the recurrent-state carry between
        # chunks (attention KV lives in the slot's pool pages)
        self._prefilling: Dict[int, Dict] = {}
        self._prefill_deferred = 0      # consecutive decode-priority defers
        # -- KV spill (Infinite-LLM-style distributed pool) -------------
        # guest side: slot -> {"req", "host", "hosting", "ext_tokens"};
        # plans keyed by rid until the request admits into a slot.
        # host side: handle -> {"slots", "pages"} — whole local slots
        # reserved to carry a neighbor's overflow pages.
        self._spills: Dict[int, Dict] = {}
        self._spill_plans: Dict[int, Dict] = {}
        self._hosted: Dict[int, Dict] = {}
        self._hosted_ids = itertools.count()
        # set by the control plane while a pending partial merge will
        # grow this engine's pool: over-ceiling requests wait in the
        # queue instead of admitting into a slot they would overflow
        self.awaiting_devices = False
        # chunk continuation needs causal caches (encoder/vision memory
        # is not causal; such models keep whole-prompt prefill).
        # Sliding-window RING caches chunk too: ``_pin_prefill_cursors``
        # confines decode filler to the single slot the next chunk
        # overwrites, and the one prefix key that slot evicts (position
        # ``done - capacity``) is out-of-window for every remaining
        # query (capacity >= window), so chunked == whole-prompt streams
        # — provided each chunk fits the smallest ring (``_begin_prefill``
        # splits the policy's chunks to the min attention capacity).
        self._can_chunk = cfg.encoder is None and cfg.vision is None
        self.pallas_kernels = (
            jax.default_backend() == "tpu" if pallas_kernels is None
            else bool(pallas_kernels))
        self.steps = 0
        self.tp = 1
        self.par_layout = Layout.of(1)
        self.tp_pending: Optional[int] = None
        self.mesh = None
        self._session = None
        self._session_t0 = 0.0
        self.transform_reports = []
        # per-action transform records (wall/measured/modeled seconds,
        # cross-device flag) surfaced by ClusterEngine.metrics
        self.transform_log: List[Dict] = []
        # realized spill page-copy wall times, same feedback schema
        # (kind/wall_s/bytes); kept OUT of transform_log so merge-wall
        # metrics and the parity diff keep their action-only semantics
        self.spill_log: List[Dict] = []
        if devices:
            from repro.core import instance as I
            assert layout == "header_centric", (
                "mesh placement shards the canonical header-centric pool")
            assert max_batch % self.W == 0, (
                f"max_batch={max_batch} must be divisible by the device "
                f"count {self.W}: batch (slots) shards over the rep axis, "
                f"which is W-wide at TP1")
            self.mesh = self._make_mesh(1)
            self._pspecs = I.param_pspecs(self.params, transform_attn)
            self._cspecs = I.cache_pspecs(self.caches)
            self.params = jax.device_put(
                self.params, self._shardings(self._pspecs, self.mesh))
            self.caches = jax.device_put(
                self.caches, self._shardings(self._cspecs, self.mesh))

        cfgc, planc, layoutc = cfg, self.plan, layout

        # ``sp`` (the sequence-parallel factor of the current
        # ``par_layout``) is STATIC: each layout's decode/chunk trace
        # folds the sp shards into the batch dimension and combines
        # partial softmax states across them (elastic sequence
        # parallelism) — a layout change simply keys a fresh trace.
        # ``mesh`` is static too: the paged-attention kernel runs per
        # shard of it (``kernels.paged_attention_sharded``)
        use_kernel = self.pallas_kernels

        @partial(jax.jit, static_argnames=("sp", "mesh"))
        def _decode(params, caches, tokens, positions, sp=1, mesh=None):
            return M.decode_step(params, cfgc, planc, caches, tokens,
                                 positions, layoutc, use_kernel=use_kernel,
                                 sp=sp, mesh=mesh)

        self._decode = _decode

        # chunked-prefill hot path: ONE jit whose trace cache is keyed
        # by (batch, chunk_len) shape — start_pos is traced, so every
        # chunk of the same shape reuses the compile; ``first_chunk``
        # is STATIC (empty-prefix chunks skip the prefix walk/gather
        # entirely).  A chunk that compiled says so on its
        # ``engine.chunk`` span (``serving.tracing``).  The slot
        # views are extracted with fresh identity page tables, so the
        # GSPMD-local identity gather/scatter path is always valid here.
        @partial(jax.jit, static_argnames=("first_chunk", "sp", "mesh"))
        def _chunk(params, tokens, start_pos, sub, first_chunk=False,
                   sp=1, mesh=None):
            return M.prefill_chunk(params, cfgc, planc, tokens,
                                   start_pos, sub, layoutc,
                                   first_chunk=first_chunk,
                                   identity_pages=True,
                                   use_kernel=use_kernel, sp=sp,
                                   mesh=mesh)

        self._prefill_chunk_jit = _chunk

        # whole-prompt prefill, same treatment: without the jit every
        # single-chunk prefill re-traces M.prefill's layer scan (a full
        # XLA compile per request); with it the trace cache is keyed by
        # prompt length, so repeated lengths are compile-free
        @jax.jit
        def _whole(params, tokens, sub):
            return M.prefill(params, cfgc, planc, {"tokens": tokens},
                             sub, layoutc)

        self._prefill_whole_jit = _whole
        self._b1_tmpls: Dict = {}     # (kind, alloc) -> batch-1 template

    def _block_window(self, kind: str) -> int:
        from repro.models.blocks import _window_of
        return _window_of(kind, self.cfg)

    def _min_chunk_cap(self) -> int:
        """Largest chunk a single prefill call may carry: the smallest
        attention-cache capacity across block kinds (a ring's page-
        rounded window; ``max_seq_alloc`` for full attention).  A chunk
        longer than a ring would scatter one slot twice in a single
        write — and its own oldest queries would lose in-window keys."""
        from repro.configs.base import ATTN, MOE, SLIDING
        caps = []
        for k in set(self.cfg.pattern):
            if k in (ATTN, SLIDING, MOE):
                w = self._block_window(k)
                cap = (self.max_seq_alloc if w == 0
                       else min(self.max_seq_alloc, w))
                caps.append(-(-cap // self.page_tokens) * self.page_tokens)
        return min(caps) if caps else self.max_seq_alloc

    # -- mesh helpers (mesh placement only) ------------------------------
    def _make_mesh(self, layout, devices=None):
        """``layout`` is a ``Layout`` or a bare TP degree (sp=1)."""
        from repro.launch.mesh import make_instance_mesh
        return make_instance_mesh(devices or self.devices, layout)

    def _shardings(self, pspec_tree, mesh):
        from repro.core.transform_engine import shard_tree
        return shard_tree(pspec_tree, mesh)

    # -- §4.3 live transformation ----------------------------------------
    def transform(self, tp_to: int, layers_per_step: int = 1,
                  interpret=None,
                  devices: Optional[List[jax.Device]] = None,
                  layout=None) -> int:
        """Begin a live parallelism transformation to degree ``tp_to``.
        ``layout`` optionally names the FULL target factorization (a
        ``launch.mesh.Layout`` or anything ``Layout.of`` accepts) — a
        same-degree target with a different (sp, tp) split is a LAYOUT
        CHANGE (e.g. TP4 -> SP2xTP2): capacity is untouched but every
        byte of weights and KV re-partitions through the same §4.3
        layer-coherent schedule, serving uninterrupted.  Returns the
        number of schedule steps; each subsequent ``step()`` executes
        one of them before its decode iteration, and the engine returns
        to the stacked fast path once the schedule drains.

        Two regimes — BOTH keep serving through the session:

        * SAME device set (the default): in-flight requests keep
          decoding throughout via the per-layer path; their KV crosses
          the TP boundary bit-exactly (the data plane only moves bytes).
        * CROSS device set — the target mesh spans adopted devices
          (merge, after ``adopt_devices``) or a ``devices=`` subset
          (split: the engine sheds its adopted devices when the session
          drains).  The session stages the widened/shrunk mesh PER
          LAYER (layer-coherent schedule steps), so mid-session every
          layer sits on exactly one device assembly; the per-layer
          decode/chunk paths ``device_put`` activations once at the
          migrated/unmigrated boundary and decoding (and chunked
          prefill) continue with zero stalled steps — streams stay
          bit-exact, and now their timing does too.

        Invariants: no session may already be open; ``tp_to`` divides
        the target device count; a merge transform requires
        ``adopt_devices`` to have grown the pool first so migrated KV
        has page-aligned room."""
        from repro.core import instance as I
        from repro.core import transform_engine as TE

        assert self.mesh is not None, "transform requires devices="
        assert self._session is None, "transformation already in progress"
        assert not self._spills and not self._hosted, (
            "no transforms while KV spill regions are open: a pool "
            "resize would move hosted/overflow pages out from under "
            "their distributed page tables (release the spill first)")
        lay_to = Layout.of(layout if layout is not None else tp_to)
        assert lay_to.degree == tp_to, (
            f"layout {lay_to} (degree {lay_to.degree}) disagrees with "
            f"tp_to={tp_to}")
        target_devs = list(devices) if devices is not None else self.devices
        if (tp_to == self.tp and lay_to == self.par_layout
                and target_devs == self.devices):
            return 0
        if tp_to == self.tp and lay_to == self.par_layout:
            # same-degree device migration (a partial-merge donor
            # shedding devices, or widening back onto a returned loan):
            # the sharding layout is unchanged, so the whole state moves
            # in one synchronous re-shard — no §4.3 session, and the
            # engine never stops serving (callers run this between
            # steps).  Live contexts must fit the new width's
            # allocation; donor_loanable() guarantees it on the shrink
            # side.
            live = [r for r in self.slots if r is not None] + self.waiting
            need = max((r.total_tokens for r in live), default=0)
            need = -(-need // self.page_tokens) * self.page_tokens
            alloc = self.seq_quantum * len(target_devs)
            assert need <= alloc, (
                f"live context ({need} tok) exceeds the retained "
                f"width's allocation ({alloc} tok)")
            self.mesh = self._make_mesh(self.par_layout, target_devs)
            self.devices = list(target_devs)
            self.W = len(target_devs)
            self.params = jax.device_put(
                self.params, self._shardings(self._pspecs, self.mesh))
            self.repin_cache_shardings()
            self._resize_pool(alloc)
            self.check_capacity_invariant()
            return 0
        # memory follows the TP degree (§3.4): grow the physical pool to
        # back the TARGET policy ceiling before migration needs the room
        # (the shrink half runs in _finish_transform, once live KV has
        # landed on the narrower degree)
        if self.max_seq_alloc < self.seq_quantum * tp_to:
            self._resize_pool(self.seq_quantum * tp_to)
        session = TE.open_owner_session(
            self, tp_to, self._make_mesh(lay_to, target_devs),
            param_spec_fn=lambda t: I.param_pspecs(t, self.transform_attn),
            cache_spec_fn=I.layer_cache_pspecs,
            layers_per_step=layers_per_step,
            storage_layout=self.layout, interpret=interpret,
            layout_to=lay_to)
        self.tp_pending = tp_to
        self._pending_devices = (target_devs
                                 if target_devs != self.devices else None)
        self._session_cross = (set(self.mesh.devices.flat)
                               != set(target_devs))
        self._session_t0 = time.monotonic()
        return session.schedule.n_steps

    @property
    def transforming(self) -> bool:
        return self._session is not None

    # -- InstanceView protocol (control-plane side, paper §5) -----------
    # The scheduler in core/scheduler.py drives live engines through the
    # same narrow view it drives SimInstances through; these methods are
    # the live implementation of that protocol.

    @property
    def max_tp(self) -> int:
        """Largest TP degree this engine can transform to in place
        (its current device count; merging raises it)."""
        return self.W

    @property
    def width(self) -> int:
        """Devices this engine spans — what it contributes as a merge
        donor (``InstanceView.width``)."""
        return self.W

    def max_seq_at(self, tp: int) -> int:
        """Admission ceiling (tokens per request) at TP degree ``tp``.

        THE capacity contract — the single place the physical/policy
        split is defined (everything else derives from it):

        * ``seq_quantum`` — per-device admission share (tokens), frozen
          at construction (the paper's fixed per-device KV budget);
        * ``max_seq_at(tp) == seq_quantum * tp`` — the POLICY ceiling at
          degree ``tp``; ``tp`` may exceed ``max_tp`` when the scheduler
          prospects a merge (borrowed devices bring their budget along);
        * ``max_seq_alloc`` — the PHYSICAL per-slot pool ceiling, kept
          ``== seq_quantum * W`` by adopt/release (asserted in
          ``check_capacity_invariant``), so any in-place policy ceiling
          (``tp <= W``) is always physically backed.

        Single-device engines (``devices=None``) have no transformable
        axis and expose the full allocation at any degree."""
        assert tp >= 1, tp
        if self.devices is None:
            return self.max_seq_alloc
        return self.seq_quantum * tp

    def max_seq(self) -> int:
        """Admission ceiling at the *policy* degree: while a scale-up is
        in flight the engine is routable at its target capacity (queued
        requests admit once the new degree is resident), so the router
        sends follow-up long requests here instead of transforming a
        second instance."""
        return self.max_seq_at(self.tp_pending or self.tp)

    def check_capacity_invariant(self) -> None:
        """Assert the ``max_seq_alloc``/``max_seq()`` contract from
        ``max_seq_at``: physical backs policy at every lifecycle point
        (construction, adopt, transform, release, revive).

        Since memory follows the TP degree on EVERY transform (not just
        merges), the allocation sits between the active policy ceiling
        (``seq_quantum * (tp_pending or tp)`` — always physically
        backed) and the engine's full device budget (``seq_quantum *
        W`` — construction / adopt allocate it; ``_finish_transform``
        trims to ``seq_quantum * tp`` when a transform lands)."""
        if self.devices is None or self.parked:
            return
        assert (self.seq_quantum * (self.tp_pending or self.tp)
                <= self.max_seq_alloc
                <= self.seq_quantum * self.W), (
            self.max_seq_alloc, self.seq_quantum, self.tp,
            self.tp_pending, self.W)
        assert (self.tp_pending or self.tp) <= self.W, (
            self.tp, self.tp_pending, self.W)
        assert self.max_seq() <= self.max_seq_alloc

    def kv_capacity_tokens(self) -> int:
        """Slot-partitioned pools: every slot owns max_seq() tokens."""
        return self.max_batch * self.max_seq()

    def kv_used_tokens(self) -> int:
        used = sum(r.context_len for r in self.slots if r is not None)
        # whole slots reserved to host a neighbor's spilled pages are
        # consumed capacity as far as admission control is concerned
        used += sum(len(h["slots"]) for h in self._hosted.values()) \
            * self.max_seq()
        return used + sum(len(r.prompt) for r in self.waiting)

    def kv_used_fraction(self) -> float:
        return self.kv_used_tokens() / max(self.kv_capacity_tokens(), 1)

    def kv_free_tokens(self) -> int:
        return max(0, self.kv_capacity_tokens() - self.kv_used_tokens())

    def load(self) -> float:
        # same shape as SimInstance.load: KV pressure + queue pressure
        return self.kv_used_fraction() + 0.05 * len(self.waiting)

    def has_long_request(self) -> bool:
        """A request is long for Alg 2 if its final context would not fit
        this engine at TP1 — scale-down must wait for it to finish."""
        cap1 = self.max_seq_at(1)
        live = [r for r in self.slots if r is not None] + self.waiting
        return any(r.total_tokens > cap1 for r in live)

    def _finish_transform(self) -> None:
        from repro.core import transform_engine as TE

        session = TE.close_owner_session(self)
        self.tp_pending = None
        self.transform_reports.extend(session.reports)
        try:
            cache_bytes = sum(int(x.nbytes)
                              for x in jax.tree.leaves(self.caches)
                              if hasattr(x, "nbytes"))
        except Exception:
            cache_bytes = 0
        lay_from, lay_to = session.schedule.resolved_layouts()
        self.transform_log.append({
            "kind": "transform",
            "tp_from": session.schedule.tp_from,
            "tp_to": session.schedule.tp_to,
            "layout_from": str(lay_from),
            "layout_to": str(lay_to),
            # pool-size proxy for what the session moved — selects the
            # measured-EWMA size bucket (core.calibrate.MeasuredCosts),
            # nothing downstream treats it as exact transfer bytes
            "bytes": cache_bytes,
            "cross": self._session_cross,
            "steps": session.schedule.n_steps,
            "wall_s": time.monotonic() - self._session_t0,
            # measured_s: the StepReport step times (dispatch ->
            # resident).  For overlapped steps the span includes
            # whatever serving work the transfer hid under, so the
            # derived drift UPPER-BOUNDS model error on this path;
            # the HONEST model error is core.calibrate's isolated
            # micro-spans (nothing hides under them).  exposed_s
            # (dispatch + blocking wait — the cost serving actually
            # paid, the Fig. 11 overhead) rides alongside
            "measured_s": sum(r.seconds for r in session.reports),
            "exposed_s": sum(r.blocked_s for r in session.reports),
            "modeled_s": sum(r.modeled_s for r in session.reports),
            # PER-STEP relative errors: action-level sums let signed
            # step errors cancel, which would show 0 drift on a badly
            # miscalibrated model
            "step_drifts": [abs(r.seconds - r.modeled_s) / r.modeled_s
                            for r in session.reports
                            if r.modeled_s > 0.0],
            # fraction of the session's transfer windows hidden under
            # serving compute (per-layer intra-step streaming): 1 -
            # exposed/measured, clamped — the trajectory's informational
            # weight_stream_overlap_frac column
            "overlap_frac": (
                max(0.0, 1.0 - (sum(r.blocked_s for r in session.reports)
                                / max(sum(r.seconds
                                          for r in session.reports),
                                      1e-12)))),
        })
        self._session_cross = False
        if self._pending_devices is not None:
            # split after a merge: the drained session landed every array
            # on the retained subset — shed the adopted devices
            self.devices = list(self._pending_devices)
            self.W = len(self.devices)
            self.adopted_devices = []
            self._pending_devices = None
        # memory follows the TP degree on EVERY transform (the former
        # merge-only resize, ROADMAP item): trim the pool to the landed
        # degree's allocation.  Alg 2 only shrinks instances whose every
        # live context fits the target ceiling (and the grow half ran
        # before the session opened), but the raw transform API carries
        # no such guarantee — never trim below a live context's final
        # footprint (page-rounded), only down, never up.
        live = [s for s in self.slots if s is not None] + self.waiting
        need = max((r.total_tokens for r in live), default=0)
        need = -(-need // self.page_tokens) * self.page_tokens
        target = max(self.seq_quantum * self.tp, need)
        if target < self.max_seq_alloc:
            self._resize_pool(target)
        self.check_capacity_invariant()

    # -- cross-instance merge lifecycle (paper Fig. 3, §3.4) -------------
    #
    # The control plane (serving/cluster.py) drives a merge as:
    #   donor.export_active() -> donor.park() -> target.adopt_devices()
    #   -> target.import_request(...) -> target.transform(combined_W)
    # and a split as transform(1, devices=home_devices) followed by
    # donor.revive().  Each method keeps the capacity contract
    # (max_seq_at) true at every intermediate point.

    def adopt_devices(self, devs: List[jax.Device]) -> None:
        """Widen this engine with a parked donor's devices.  The pool
        grows by the donors' per-slot allocation BEFORE the transform so
        migrated KV has page-aligned room; the mesh still spans the old
        subset until ``transform`` carries the state across."""
        assert self.mesh is not None and not self.transforming
        assert self.tp == 1, "merge targets must be at TP1 (Fig. 3)"
        assert devs, "nothing to adopt"
        self.adopted_devices = self.adopted_devices + list(devs)
        self.devices = self.devices + list(devs)
        self.W = len(self.devices)
        self._resize_pool(self.seq_quantum * self.W)
        self.check_capacity_invariant()

    def park(self) -> List[jax.Device]:
        """Donor side of a merge: release every device and drop the live
        state (the control plane has already exported in-flight KV via
        ``export_active``).  Returns the released devices; the engine
        stays constructed and is brought back by ``revive``."""
        assert not self.transforming and not self.parked
        assert all(s is None for s in self.slots) and not self.waiting \
            and not self._prefilling, (
            "park requires a drained engine (export_active first)")
        assert not self._spills and not self._hosted, (
            "cannot park an engine participating in a KV spill "
            "(its pages are reachable from a distributed page table)")
        devs = list(self.devices)
        self.parked = True
        self.params = self.caches = None
        self.mesh = None
        self.devices = []
        return devs

    def revive(self, devices: List[jax.Device], params) -> None:
        """Rebuild a parked engine on ``devices`` (normally its own,
        returned by a split): fresh TP1 mesh, re-sharded ``params``
        (host or donor copies — weights are identical cluster-wide),
        empty KV pool at this width's allocation."""
        assert self.parked
        self.devices = list(devices)
        self.home_devices = list(devices)
        self.W = len(devices)
        self.parked = False
        self.tp = 1
        self.par_layout = Layout.of(1)
        self.max_seq_alloc = self.seq_quantum * self.W
        self.mesh = self._make_mesh(1)
        self.params = jax.device_put(
            params, self._shardings(self._pspecs, self.mesh))
        caches = M.init_decode_caches(self.cfg, self.plan, self.max_batch,
                                      self.max_seq_alloc, self.page_tokens,
                                      self.layout)
        self.caches = jax.device_put(
            caches, self._shardings(self._cspecs, self.mesh))
        self.slots = [None] * self.max_batch
        self._prefilling = {}
        self._prefill_deferred = 0
        self.check_capacity_invariant()

    def _resize_pool(self, new_max_seq: int) -> None:
        """Reallocate every full-attention paged pool at ``new_max_seq``
        tokens per slot (ring/window caches keep their window).  Pure
        page-range copies thanks to the slot-partitioned identity
        layout; runs eagerly on the current mesh."""
        from repro.core import kv_transform as KT
        from repro.paged.pool import PagedState

        if new_max_seq == self.max_seq_alloc:
            return
        # full-attention pools are allocated at the page-rounded ceiling;
        # compare against THAT, not the raw token count, so an unaligned
        # max_seq cannot misclassify them as window caches
        old_cap = -(-self.max_seq_alloc // self.page_tokens) \
            * self.page_tokens
        new_mps = -(-new_max_seq // self.page_tokens)

        def visit(c):
            if isinstance(c, PagedState):
                if c.positions.shape[-1] != old_cap:
                    return c          # window cache: capacity is the window
                return KT.resize_slot_capacity(c, new_mps, self.max_batch)
            if isinstance(c, dict):
                return {k: visit(v) for k, v in c.items()}
            if isinstance(c, (list, tuple)):
                out = [visit(v) for v in c]
                return tuple(out) if isinstance(c, tuple) else out
            return c

        self.caches = {k: visit(v) for k, v in self.caches.items()}
        self.max_seq_alloc = new_max_seq
        if self.mesh is not None:
            # resize builds fresh metadata arrays (identity page
            # tables) that would otherwise sit uncommitted on the
            # default device; re-pin so every cache leaf is committed
            # to the canonical shardings before a session unstacks it
            self.repin_cache_shardings()

    def export_active(self) -> List[Tuple[ServeRequest, Dict,
                                          Optional[Dict]]]:
        """Donor-side KV export: pull every in-flight request out of its
        slot as ``(request, batch-1 cache tree, prefill-progress)``
        triples for ``import_request`` on the merge target.  Slots are
        freed; the byte-exact KV travels with the request.  A slot mid-
        chunked-prefill exports its chunk plan + progress + recurrent
        carry so the target resumes the prefill where the donor stopped
        — mid-prefill engines are valid merge donors."""
        out = []
        for slot, r in enumerate(self.slots):
            if r is None:
                continue
            prog = self._prefilling.pop(slot, None)
            extra = None if prog is None else {
                k: prog[k] for k in ("chunks", "ci", "done", "rec")}
            out.append((r, self._extract_slot_cache(slot), extra))
            self.slots[slot] = None
        return out

    def import_request(self, req: ServeRequest, sub: Dict,
                       repin: bool = True,
                       progress: Optional[Dict] = None) -> None:
        """Target-side KV import (cross-engine ``device_put`` + §4.1
        kernel scatter): land a donor request's slot cache in a free
        local slot and resume decoding it here, bit-exactly.

        The kernel scatter runs on replicated views, so the canonical
        cache shardings must be re-pinned afterwards; pass
        ``repin=False`` when importing a batch and call
        ``repin_cache_shardings`` once at the end (one whole-pool move
        instead of one per request).

        ``progress`` is the donor's exported chunked-prefill state (see
        ``export_active``): the request resumes prefilling here, its
        already-written prefix pages having travelled with ``sub``."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        slot = self._free_slot()
        assert slot is not None, "no free slot for donor import"
        if self.mesh is not None:
            # the cross-engine move: donor arrays -> this engine's devices
            sub = jax.device_put(sub, jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), sub))
        self._import_slot_cache(sub, slot)
        req.slot = slot
        self.slots[slot] = req
        if progress is not None:
            rec = progress["rec"]
            if self.mesh is not None:
                rec = jax.device_put(rec, jax.tree.map(
                    lambda _: NamedSharding(self.mesh, P()), rec))
            self._prefilling[slot] = {"req": req, **progress, "rec": rec}
        if repin and self.mesh is not None:
            self.repin_cache_shardings()

    def repin_cache_shardings(self) -> None:
        """Restore the canonical cache shardings on the current mesh
        (after ops that computed on replicated views)."""
        self.caches = jax.device_put(
            self.caches, self._shardings(self._cspecs, self.mesh))

    # ------------------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        self.waiting.append(req)

    def _free_slot(self) -> Optional[int]:
        hosted = self._hosted_slots()
        for i, s in enumerate(self.slots):
            if s is None and i not in hosted:
                return i
        return None

    def _hosted_slots(self) -> set:
        return {s for h in self._hosted.values() for s in h["slots"]}

    # -- chunked prefill (PrefillPolicy-driven) --------------------------
    #
    # A request is admitted into a slot (``_begin_prefill``) and then
    # advanced by page-aligned chunks (``_run_chunk``): each chunk is
    # extracted as a batch-1 slot view, run through
    # ``models.model.prefill_chunk`` (attention over cached prefix +
    # chunk, chunk K/V written through the paged pool), and scattered
    # back — so a partially-prefilled slot's KV always lives in the
    # engine pool, where transform sessions and ``copy_page_slices``
    # migration find it.  Decode iterations between chunks write
    # masked-out filler into the slot at positions >= the prefilled
    # prefix; ``_sanitize_sub`` re-invalidates those before each chunk
    # (the prefix itself is never touched).

    def _n_decoding(self) -> int:
        return sum(1 for r in self.slots
                   if r is not None and r.state == State.DECODE)

    @staticmethod
    def _strip_tree(c):
        """Drop PagedState nodes from one cache tree (see
        ``_strip_pools``)."""
        from repro.paged.pool import PagedState

        if isinstance(c, PagedState):
            return None
        if isinstance(c, dict):
            return {k: Engine._strip_tree(v) for k, v in c.items()}
        if isinstance(c, (list, tuple)):
            out = [Engine._strip_tree(v) for v in c]
            return tuple(out) if isinstance(c, tuple) else out
        return c

    @staticmethod
    def _strip_pools(tree):
        """Drop PagedState leaves from a prefill carry tree: only the
        recurrent-state leaves are ever read back (the slot's pool pages
        are authoritative for attention KV), and keeping the pools would
        pin a full per-slot cache of dead device memory — and ship it
        cross-engine on merge exports."""
        return {k: Engine._strip_tree(v) for k, v in tree.items()}

    def _begin_prefill(self, req: ServeRequest, slot: int) -> None:
        req.state = State.PREFILL
        req.slot = slot
        self.slots[slot] = req
        plan = self._spill_plans.pop(req.rid, None)
        if plan is not None:
            self._spills[slot] = {"req": req, **plan}
        chunks = (self.prefill_policy.chunk_sizes(len(req.prompt),
                                                  self.page_tokens)
                  if self._can_chunk else [len(req.prompt)])
        if (plan is not None and len(chunks) == 1
                and chunks[0] > self._min_chunk_cap()):
            # spilled prompts longer than the local pool MUST chunk: the
            # whole-prompt path builds a fresh local-capacity cache the
            # prompt would overflow; the chunk path assembles the
            # extended (local + host) view once the cursor crosses the
            # local ceiling
            cap = self._min_chunk_cap()
            c = chunks[0]
            chunks = [cap] * (c // cap) + ([c % cap] if c % cap else [])
        if len(chunks) > 1:
            # ring-cache models: no chunk may exceed the smallest
            # attention capacity (the cap is a page multiple, so the
            # page-boundary chunking invariant survives the split)
            cap = self._min_chunk_cap()
            chunks = [s for c in chunks
                      for s in ([cap] * (c // cap) + ([c % cap] if c % cap
                                                      else []))]
        # the recurrent-state carry between chunks starts from the
        # freshly-initialized cache (== the sequence kernels' state=None
        # init); single-chunk prefills never read it
        rec = None
        if len(chunks) > 1:
            rec = self._strip_pools(M.init_decode_caches(
                self.cfg, self.plan, 1, self.max_seq_alloc,
                self.page_tokens, self.layout))
        self._prefilling[slot] = {"req": req, "chunks": chunks, "ci": 0,
                                  "done": 0, "rec": rec}

    def _admittable_now(self, req: ServeRequest) -> bool:
        """Whether a waiting request may begin prefilling THIS step.
        Outside a session: always.  Mid-session: any chunkABLE model
        admits — multi-chunk plans run the per-layer chunk path, and
        whole-prompt (single-chunk) prefills route through the SAME
        path as a single first-chunk call (``_pin_prefill_cursors``
        masks the decode filler for prefilling slots on session layers
        too), so transform sessions no longer starve short prompts.
        Only models that cannot chunk at all (encoder/vision memory)
        still wait for the drain."""
        if (req.total_tokens > self.max_seq_alloc
                and req.rid not in self._spill_plans
                and (self.awaiting_devices or self.tp_pending is not None)):
            # capacity is on its way (pending partial-merge adoption or
            # an in-flight grow transform): hold the over-ceiling
            # request in the queue instead of admitting it into a slot
            # it would overflow.  Spilled requests carry their own
            # extension; legacy over-ceiling submits with no growth
            # pending keep the old truncate-at-ceiling behavior.
            return False
        if self._session is None:
            return True
        return self._can_chunk

    def _advanceable_now(self, slot: int) -> bool:
        """Every prefill advances every step now: mid-session the
        per-layer chunk path serves single-chunk (whole-prompt) plans
        as one first-chunk call, so nothing waits for the drain."""
        return True

    def _prefill_step(self) -> int:
        """One step of policy-driven prefill work: admit at most one
        waiting request (the classic one-admission-per-step cadence),
        then spend the policy's token quota advancing partially-
        prefilled slots in its service order.  Returns tokens emitted
        (prefill completions emit the first token).  ALL prefills keep
        running DURING transform sessions via the per-layer path (see
        ``_run_chunk_layers``) — whole-prompt plans run as one
        first-chunk call, so transform sessions no longer starve short
        prompts.

        Admission is FCFS over the ADMITTABLE queue: mid-session an
        unchunkable model's request at the head must not block others;
        the skipped request keeps its queue position and admits when
        the session drains."""
        if self.waiting:
            slot = self._free_slot()
            if slot is not None:
                for i, req in enumerate(self.waiting):
                    if self._admittable_now(req):
                        with tracing.span("engine.admit", rid=req.rid,
                                          slot=slot):
                            self._begin_prefill(self.waiting.pop(i), slot)
                        break
        if not self._prefilling:
            self._prefill_deferred = 0
            return 0
        quota = self.prefill_policy.step_quota(self._n_decoding(),
                                               self._prefill_deferred)
        if quota <= 0:
            self._prefill_deferred += 1
            return 0
        self._prefill_deferred = 0
        emitted = 0
        spent = 0.0

        def remaining(slot: int) -> int:
            p = self._prefilling[slot]
            return len(p["req"].prompt) - p["done"]

        for slot in self.prefill_policy.service_order(
                list(self._prefilling), remaining):
            while slot in self._prefilling:
                if not self._advanceable_now(slot):
                    break
                size = self._prefilling[slot]["chunks"][
                    self._prefilling[slot]["ci"]]
                if spent > 0 and spent + size > quota:
                    return emitted      # budget exhausted this step
                emitted += self._run_chunk(slot)
                spent += size
        return emitted

    def _run_chunk(self, slot: int) -> int:
        """Advance the slot's prefill by one chunk; returns 1 when the
        prefill completed (first token emitted), else 0."""
        prog = self._prefilling[slot]
        req = prog["req"]
        start = prog["done"]
        size = prog["chunks"][prog["ci"]]
        with tracing.span("engine.chunk", rid=req.rid, start=start,
                          size=size):
            if req.t_prefill_start is None:
                req.t_prefill_start = self._clock()
            if len(prog["chunks"]) == 1 and self._session is None:
                # whole-prompt fast path: one prefill call on a fresh
                # batch-1 cache (byte-identical to the pre-chunking
                # engine).  Mid-session the same plan falls through to
                # the generic path below and runs as ONE first-chunk
                # call on the per-layer assemblies — whole prompts no
                # longer wait out transform sessions.
                self._prefill_whole(req, slot)
                del self._prefilling[slot]
                return 1
            tokens = jnp.asarray(req.prompt[start:start + size],
                                 jnp.int32)[None, :]
            start_a = jnp.full((1,), start, jnp.int32)
            if self._session is not None:
                # mid-session: the chunk runs the per-layer path across
                # the session's mixed-but-coherent device assemblies
                logits = self._run_chunk_layers(slot, prog, tokens,
                                                start_a)
            else:
                # spilled slot past the local ceiling: the chunk computes
                # on the EXTENDED view (local + host pages) and scatters
                # back through spill_slot; jit keys on shapes, so the
                # extended call simply traces its own entry
                ext = (slot in self._spills
                       and start + size > self._local_page_cap())
                with tracing.span("engine.chunk.view"):
                    view = (self._assemble_spilled(slot) if ext
                            else self._extract_slot_cache(slot))
                    sub = self._sanitize_sub(view, prog["rec"], start)
                with tracing.span("engine.chunk.run"):
                    logits, sub = self._prefill_chunk_jit(
                        self.params, tokens, start_a, sub,
                        first_chunk=start == 0, sp=self.par_layout.sp,
                        mesh=self.mesh)
                with tracing.span("engine.chunk.adopt"):
                    if ext:
                        self.spill_slot(slot, sub)
                    else:
                        self._adopt_slot_cache(sub, slot, start + size)
                    prog["rec"] = self._strip_pools(sub)
            prog["done"] += size
            prog["ci"] += 1
            if prog["done"] >= len(req.prompt):
                del self._prefilling[slot]
                self._finish_prefill(req, slot, logits)
                return 1
            return 0

    def _run_chunk_layers(self, slot: int, prog: Dict, tokens: jax.Array,
                          start_a: jax.Array) -> jax.Array:
        """One prefill chunk while a transform session is open: extract
        the slot's batch-1 view from EACH session layer's cache,
        sanitize it (decode filler past the prefix, recurrent carry),
        run ``models.model.prefill_chunk_layers`` across the session's
        per-layer assemblies, and scatter the updated views back."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        s = self._session
        start = prog["done"]
        if prog["rec"] is None:
            # single-chunk plan admitted before the session opened (the
            # fast path never initializes a carry): build the same
            # fresh-cache carry _begin_prefill gives multi-chunk plans
            prog["rec"] = self._strip_pools(M.init_decode_caches(
                self.cfg, self.plan, 1, self.max_seq_alloc,
                self.page_tokens, self.layout))
        with tracing.span("engine.chunk.view"):
            rec_layers = M.unstack_cache_tree(prog["rec"], self.cfg)
            subs = []
            for layer, rec in zip(s.layers, rec_layers):
                tmpl = self._batch1_layer_tmpl(layer["kind"])
                sub = self._extract_slot_tree(layer["cache"], tmpl, slot)
                subs.append(self._sanitize_tree(sub, rec, start,
                                                layer.get("mesh")))
        with tracing.span("engine.chunk.run"):
            logits, new_subs = M.prefill_chunk_layers(
                s.layers, s.static, self.cfg, self.plan, tokens, start_a,
                subs, self.layout, static_mesh=s.static_mesh,
                first_chunk=start == 0, identity_pages=True,
                use_kernel=self.pallas_kernels)
        with tracing.span("engine.chunk.adopt"):
            for layer, sub in zip(s.layers, new_subs):
                layer["cache"] = self._adopt_slot_tree(layer["cache"],
                                                       sub, slot)
            # the carry stays in the stacked format between chunks (one
            # format everywhere, and sessions may drain mid-prefill) — but
            # mid-cross-session its recurrent leaves come back committed to
            # whichever assembly their layer was on, and jnp.stack cannot
            # stack across disjoint device sets: land every leaf on the
            # TARGET assembly first (the next chunk's sanitize re-pins each
            # leaf to its layer's then-current mesh anyway)
            rec_new = []
            for sub in new_subs:
                t = self._strip_tree(sub)
                rec_new.append(jax.device_put(t, jax.tree.map(
                    lambda _: NamedSharding(s.mesh_to, P()), t)))
            prog["rec"] = M.restack_cache_tree(rec_new, self.cfg)
        return logits

    def _batch1_layer_tmpl(self, kind: str):
        """Memoized batch-1 shape template for one layer kind at the
        CURRENT pool allocation (rebuilt when a resize changes it)."""
        from repro.models import blocks as B

        key = (kind, self.max_seq_alloc)
        tmpl = self._b1_tmpls.get(key)
        if tmpl is None:
            tmpl = B.init_block_cache(kind, self.cfg, self.plan, 1,
                                      self.max_seq_alloc,
                                      self.page_tokens, self.layout,
                                      specs_only=True)
            self._b1_tmpls[key] = tmpl
        return tmpl

    def _pin_prefill_cursors(self) -> None:
        """Decode iterations append masked filler for EVERY slot at its
        ``seq_lens`` cursor, mid-prefill slots included.  Left alone the
        cursor advances one filler token per step, and a slot starved of
        chunk budget for more than ``capacity - done`` steps would ring-
        wrap the filler INTO its prefilled prefix — unrecoverable
        corruption (``_sanitize_sub`` only re-invalidates past the
        prefix).  Re-pinning the cursor to ``done`` after each decode
        confines all filler to the one position the next chunk
        overwrites anyway."""
        if not self._prefilling:
            return
        from repro.paged.pool import PagedState

        idx = jnp.asarray(sorted(self._prefilling), jnp.int32)
        val = jnp.asarray([self._prefilling[s]["done"]
                           for s in sorted(self._prefilling)], jnp.int32)

        def visit(c):
            if isinstance(c, PagedState):
                seq = c.seq_lens.at[..., idx].set(val)
                return PagedState(c.pool, c.page_table, seq, c.positions)
            if isinstance(c, dict):
                return {k: visit(v) for k, v in c.items()}
            if isinstance(c, (list, tuple)):
                out = [visit(v) for v in c]
                return tuple(out) if isinstance(c, tuple) else out
            return c

        if self._session is not None:
            for layer in self._session.layers:
                layer["cache"] = visit(layer["cache"])
        else:
            self.caches = {k: visit(v) for k, v in self.caches.items()}

    def _sanitize_tree(self, dst, carry, done: int, mesh=None):
        """Single-tree form of ``_sanitize_sub``; ``mesh`` is where
        recurrent-carry leaves must land (a session layer's own mesh
        mid-transform, the engine mesh otherwise)."""
        from repro.paged.pool import PagedState

        if isinstance(dst, PagedState):
            # keep exactly the slots holding real prefix tokens: stored
            # position in [0, done).  Slot-INDEX masking (arange < done)
            # would be wrong for ring caches, where done may exceed the
            # capacity and prefix positions wrap around the slots.
            keep = (dst.positions >= 0) & (dst.positions < done)
            pos = jnp.where(keep, dst.positions, -1)
            seq = jnp.full_like(dst.seq_lens, done)
            return PagedState(dst.pool, dst.page_table, seq, pos)
        if isinstance(dst, dict):
            return {k: self._sanitize_tree(dst[k], carry[k], done, mesh)
                    for k in dst}
        if isinstance(dst, (list, tuple)):
            out = [self._sanitize_tree(a, b, done, mesh)
                   for a, b in zip(dst, carry)]
            return tuple(out) if isinstance(dst, tuple) else out
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            carry = jax.device_put(carry, NamedSharding(mesh, P()))
        return carry

    def _sanitize_sub(self, sub, rec, done: int):
        """Prepare an extracted slot view for the next chunk: re-
        invalidate everything past the ``done``-token prefix (decode
        iterations for other slots wrote masked filler there) and
        restore the recurrent carry from the last chunk (decode filler
        overwrote those leaves in the engine cache too)."""
        return {k: self._sanitize_tree(sub[k], rec[k], done, self.mesh)
                for k in sub}

    def _finish_prefill(self, req: ServeRequest, slot: int,
                        logits: jax.Array) -> None:
        nxt = _sample(logits[:, -1], req.temperature,
                      jax.random.fold_in(self.rng, req.rid))
        with tracing.span("engine.sync", kind="prefill"):
            tok = int(nxt[0])
        req.generated.append(tok)
        req.t_first_token = self._clock()
        req.state = State.DECODE
        req.slot = slot
        self.slots[slot] = req
        # the prefill-emitted token counts against the budget too: a
        # 1-token request (or an immediate EOS) must not reach decode
        if (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)
                or req.context_len >= self._slot_ceiling(slot)):
            req.state = State.DONE
            req.t_done = self._clock()
            self.slots[slot] = None

    def _prefill_whole(self, req: ServeRequest, slot: int) -> None:
        """Single-call prefill via a fresh batch-1 cache: runs the whole
        prompt through the model, then scatters the filled pages into
        the slot (slot-partitioned pools make this a pure page-range
        copy — the page-friendly layout at work, paper Table 2 row 2)."""
        prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
        with tracing.span("engine.chunk.run"):
            sub = M.init_decode_caches(self.cfg, self.plan, 1,
                                       self.max_seq_alloc, self.page_tokens,
                                       self.layout)
            logits, sub = self._prefill_whole_jit(self.params, prompt, sub)
        with tracing.span("engine.chunk.adopt"):
            self._adopt_slot_cache(sub, slot, len(req.prompt))
        self._finish_prefill(req, slot, logits)

    def _adopt_slot_tree(self, dst, src, slot: int):
        """Copy one batch-1 cache tree into ``slot`` of ``dst``."""
        from repro.paged.pool import PagedState
        if isinstance(dst, PagedState):
            mps = dst.page_table.shape[-1]
            # pages for this slot occupy [slot*mps, (slot+1)*mps)
            if dst.pool.ndim == src.pool.ndim:  # stacked group dims equal
                pool = jax.lax.dynamic_update_slice_in_dim(
                    dst.pool, src.pool.astype(dst.pool.dtype),
                    slot * mps, axis=dst.pool.ndim - 5)
                seq = jax.lax.dynamic_update_slice_in_dim(
                    dst.seq_lens, src.seq_lens, slot,
                    axis=dst.seq_lens.ndim - 1)
                pos = jax.lax.dynamic_update_slice_in_dim(
                    dst.positions, src.positions, slot,
                    axis=dst.positions.ndim - 2)
                return PagedState(pool, dst.page_table, seq, pos)
            raise ValueError("cache rank mismatch")
        if isinstance(dst, dict):
            return {k: self._adopt_slot_tree(dst[k], src[k], slot)
                    for k in dst}
        if isinstance(dst, (list, tuple)):
            out = [self._adopt_slot_tree(a, b, slot)
                   for a, b in zip(dst, src)]
            return tuple(out) if isinstance(dst, tuple) else out
        # recurrent state leaf: batch axis is -2 for conv (B,K,D),
        # else ...; states are (.., B, feature...) with B at axis
        # (ndim of src where size==1)
        ax = _batch_axis(dst, src)
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=ax)

    def _adopt_slot_cache(self, sub, slot: int, seq_len: int) -> None:
        """Copy the batch-1 cache into `slot` of the engine cache."""
        self.caches = {k: self._adopt_slot_tree(self.caches[k], sub[k],
                                                slot)
                       for k in self.caches}

    def _batch1_specs(self):
        """Shape templates of a batch-1 cache tree (for locating batch
        axes without allocating); memoized per pool allocation — the
        chunked-prefill hot path extracts a slot view every chunk."""
        key = ("__stacked__", self.max_seq_alloc)
        tmpl = self._b1_tmpls.get(key)
        if tmpl is None:
            tmpl = M.init_decode_caches(self.cfg, self.plan, 1,
                                        self.max_seq_alloc,
                                        self.page_tokens, self.layout,
                                        specs_only=True)
            self._b1_tmpls[key] = tmpl
        return tmpl

    def _extract_slot_tree(self, src, tm, slot: int):
        """Slice ``slot`` out of one cache tree as a batch-1 tree
        (``tm`` is the matching batch-1 shape template)."""
        from repro.paged.pool import PagedState

        if isinstance(src, PagedState):
            mps = src.page_table.shape[-1]
            nd = src.pool.ndim
            pool = jax.lax.dynamic_slice_in_dim(
                src.pool, slot * mps, mps, axis=nd - 5)
            pt = jnp.broadcast_to(
                jnp.arange(mps, dtype=src.page_table.dtype),
                src.page_table.shape[:-2] + (1, mps))
            seq = jax.lax.dynamic_slice_in_dim(
                src.seq_lens, slot, 1, axis=src.seq_lens.ndim - 1)
            pos = jax.lax.dynamic_slice_in_dim(
                src.positions, slot, 1, axis=src.positions.ndim - 2)
            return PagedState(pool, pt, seq, pos)
        if isinstance(src, dict):
            return {k: self._extract_slot_tree(src[k], tm[k], slot)
                    for k in src}
        if isinstance(src, (list, tuple)):
            out = [self._extract_slot_tree(a, b, slot)
                   for a, b in zip(src, tm)]
            return tuple(out) if isinstance(src, tuple) else out
        return jax.lax.dynamic_slice_in_dim(
            src, slot, 1, axis=_batch_axis(src, tm))

    def _extract_slot_cache(self, slot: int):
        """Inverse of ``_adopt_slot_cache``: slice ``slot`` out of the
        engine cache as a self-contained batch-1 tree (fresh identity
        page table; pool pages are the slot's own range)."""
        tmpl = self._batch1_specs()
        return {k: self._extract_slot_tree(self.caches[k], tmpl[k], slot)
                for k in self.caches}

    def _import_slot_cache(self, sub, slot: int) -> None:
        """Cross-pool counterpart of ``_adopt_slot_cache``: the source
        tree comes from ANOTHER engine (a merge donor), so per-slot page
        counts may differ — the donor's pages land at the head of this
        slot's (wider) page range via ``kv_transform.migrate_slot_pages``
        (§4.1 kernel scatter on canonical pools)."""
        from repro.core import kv_transform as KT
        from repro.paged.pool import PagedState

        def visit(dst, src):
            if isinstance(dst, PagedState):
                mps_d = dst.page_table.shape[-1]
                mps_s = src.page_table.shape[-1]
                assert mps_s <= mps_d, (
                    "donor slots cannot exceed the grown target slots")
                pool = KT.migrate_slot_pages(src.pool, dst.pool, mps_s,
                                             slot * mps_d)
                seq = jax.lax.dynamic_update_slice_in_dim(
                    dst.seq_lens, src.seq_lens.astype(dst.seq_lens.dtype),
                    slot, axis=dst.seq_lens.ndim - 1)
                cap_d, cap_s = (dst.positions.shape[-1],
                                src.positions.shape[-1])
                pos_src = src.positions
                if cap_s < cap_d:
                    pad = [(0, 0)] * pos_src.ndim
                    pad[-1] = (0, cap_d - cap_s)
                    pos_src = jnp.pad(pos_src, pad, constant_values=-1)
                pos = jax.lax.dynamic_update_slice_in_dim(
                    dst.positions, pos_src.astype(dst.positions.dtype),
                    slot, axis=dst.positions.ndim - 2)
                return PagedState(pool, dst.page_table, seq, pos)
            if isinstance(dst, dict):
                return {k: visit(dst[k], src[k]) for k in dst}
            if isinstance(dst, (list, tuple)):
                out = [visit(a, b) for a, b in zip(dst, src)]
                return tuple(out) if isinstance(dst, tuple) else out
            ax = _batch_axis(dst, src)
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), slot, axis=ax)

        self.caches = {k: visit(self.caches[k], sub[k]) for k in self.caches}

    # -- KV spill (Infinite-LLM / DistAttention; capacity-ladder rung 1) --
    #
    # A pool-ceiling-busting request is served WITHOUT any merge: the
    # guest keeps the first ``max_seq_alloc`` tokens of KV in its own
    # slot, and the overflow pages live in whole slots reserved inside a
    # neighbor (host) engine's pool (``host_spilled``).  While the
    # context still fits locally the slot runs the ordinary batched
    # paths; once it outgrows the local capacity, every chunk/decode
    # assembles a batch-1 EXTENDED view (``paged.pool.concat_spilled``:
    # local pages + host pages as one identity-paged state), computes on
    # it with the ordinary jitted model functions — the distributed-pool
    # read path — and writes the overflow pages back into the host pool
    # through the §4.1 page-migration kernel (``spill_slot``).  The
    # decision policy is ``core.scheduler.decide_spill``; the ledger is
    # ``core.partition.PoolPartitionManager``.

    def _local_page_cap(self) -> int:
        from repro.models.blocks import full_attention_capacity
        return full_attention_capacity(self.max_seq_alloc,
                                       self.page_tokens)

    def host_spilled(self, n_pages: int) -> Optional[Dict]:
        """Host side of a KV spill: reserve whole FREE slots to carry
        ``n_pages`` of a neighbor's overflow.  Returns the hosting
        descriptor (handle, reserved slots, granted page count) or None
        when the pool lacks the free slots — the control plane then
        falls back down the capacity ladder instead of crashing."""
        if self.parked or self.transforming or n_pages <= 0:
            return None
        mps = self._local_page_cap() // self.page_tokens
        need = -(-n_pages // mps)
        hosted = self._hosted_slots()
        free = [i for i, s in enumerate(self.slots)
                if s is None and i not in hosted]
        if len(free) < need:
            return None
        slots = tuple(free[:need])
        handle = next(self._hosted_ids)
        self._hosted[handle] = {"slots": slots, "pages": need * mps}
        return {"handle": handle, "slots": slots, "pages": need * mps,
                "page_tokens": self.page_tokens}

    def release_hosted(self, handle: int) -> None:
        self._hosted.pop(handle, None)

    def admit_spilled(self, req: ServeRequest, host: "Engine",
                      hosting: Dict) -> None:
        """Guest side: queue a request whose overflow KV will live in
        ``host``'s pool (the reservation from ``host.host_spilled``)."""
        assert hosting["page_tokens"] == self.page_tokens, (
            "KV spill requires a uniform page size across the cluster")
        ext_tokens = self._local_page_cap() \
            + hosting["pages"] * self.page_tokens
        assert ext_tokens >= req.total_tokens, (
            ext_tokens, req.total_tokens)
        self._spill_plans[req.rid] = {"host": host, "hosting": hosting,
                                      "ext_tokens": ext_tokens}
        self.submit(req)

    def _slot_ceiling(self, slot: int) -> int:
        """Context ceiling of one slot: the pool allocation, extended by
        the hosted overflow for spilled slots."""
        sp = self._spills.get(slot)
        return self.max_seq_alloc if sp is None else sp["ext_tokens"]

    def _replicate_here(self, tree):
        """Cross-engine device move: land a (sub)tree replicated on this
        engine's mesh (or the default device for meshless engines)."""
        if self.mesh is None:
            return jax.device_put(tree)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        return jax.device_put(tree, jax.tree.map(
            lambda _: NamedSharding(self.mesh, P()), tree))

    def _assemble_spilled(self, slot: int):
        """Extended batch-1 view of a spilled slot: local slot pages
        followed by the host-pool overflow pages, per full-attention
        leaf (window/ring caches and recurrent state never spill — the
        window fits locally and recurrent state is O(1))."""
        from repro.models.blocks import is_full_attention_state
        from repro.paged import pool as PP
        from repro.paged.pool import PagedState

        sp = self._spills[slot]
        host: Engine = sp["host"]
        local = self._extract_slot_cache(slot)
        parts = [self._replicate_here(host._extract_slot_cache(j))
                 for j in sp["hosting"]["slots"]]

        def visit(loc, ps):
            if isinstance(loc, PagedState):
                if is_full_attention_state(loc, self.max_seq_alloc,
                                           self.page_tokens):
                    return PP.concat_spilled([loc] + list(ps))
                return loc
            if isinstance(loc, dict):
                return {k: visit(loc[k], [p[k] for p in ps])
                        for k in loc}
            if isinstance(loc, (list, tuple)):
                out = [visit(a, [p[i] for p in ps])
                       for i, a in enumerate(loc)]
                return tuple(out) if isinstance(loc, tuple) else out
            return loc

        return {k: visit(local[k], [p[k] for p in parts]) for k in local}

    def spill_slot(self, slot: int, ext) -> None:
        """Write a spilled slot back after an extended-view compute: the
        local part lands in the slot's own pages, and the overflow pages
        MIGRATE into the host engine's pool — ``write_spill_pages`` ->
        ``kv_transform.migrate_slot_pages`` -> the §4.1 page-copy
        kernel.  This is the moment KV bytes actually cross engines."""
        from repro.paged import pool as PP
        from repro.paged.pool import PagedState

        t0 = time.monotonic()
        sp = self._spills[slot]
        host: Engine = sp["host"]
        host_slots = sp["hosting"]["slots"]
        counts = [self._local_page_cap() // self.page_tokens] \
            + [host._local_page_cap() // host.page_tokens] * len(host_slots)
        ext_cap = sum(counts) * self.page_tokens
        n_host = len(host_slots)

        def visit(leaf):
            # -> (local leaf, one leaf-or-None per host slot)
            if isinstance(leaf, PagedState):
                if leaf.positions.shape[-1] == ext_cap:
                    parts = PP.split_spilled(leaf, counts)
                    return parts[0], parts[1:]
                return leaf, [None] * n_host
            if isinstance(leaf, dict):
                pairs = {k: visit(v) for k, v in leaf.items()}
                return ({k: p[0] for k, p in pairs.items()},
                        [{k: p[1][i] for k, p in pairs.items()}
                         for i in range(n_host)])
            if isinstance(leaf, (list, tuple)):
                pairs = [visit(v) for v in leaf]
                loc = [p[0] for p in pairs]
                loc = tuple(loc) if isinstance(leaf, tuple) else loc
                hps = []
                for i in range(n_host):
                    hp = [p[1][i] for p in pairs]
                    hps.append(tuple(hp) if isinstance(leaf, tuple)
                               else hp)
                return loc, hps
            return leaf, [None] * n_host

        pairs = {k: visit(v) for k, v in ext.items()}
        self._adopt_slot_cache({k: p[0] for k, p in pairs.items()},
                               slot, 0)
        for i, j in enumerate(host_slots):
            host.write_spill_pages(j, {k: p[1][i]
                                       for k, p in pairs.items()})
        from repro.core.costmodel import kv_bytes_per_token
        overflow_pages = sum(counts[1:])
        self.spill_log.append({
            "kind": "spill", "tp_from": 0, "tp_to": 0,
            "wall_s": time.monotonic() - t0,
            "bytes": kv_bytes_per_token(self.cfg) * overflow_pages
            * self.page_tokens,
            "pages": overflow_pages,
        })

    def write_spill_pages(self, j: int, part) -> None:
        """Host side of ``spill_slot``: land one overflow segment in
        reserved slot ``j``'s page range.  Only full-attention leaves
        carry data (``part`` has None elsewhere); pool bytes move
        through ``kv_transform.migrate_slot_pages`` and the positions
        metadata rides alongside so hosted pages stay self-describing."""
        from repro.core import kv_transform as KT
        from repro.paged.pool import PagedState

        part = self._replicate_here(part)

        def visit(dst, src):
            if src is None:
                return dst
            if isinstance(dst, PagedState):
                mps_d = dst.page_table.shape[-1]
                mps_s = src.page_table.shape[-1]
                assert mps_s <= mps_d, (mps_s, mps_d)
                pool = KT.migrate_slot_pages(src.pool, dst.pool, mps_s,
                                             j * mps_d)
                cap_d, cap_s = (dst.positions.shape[-1],
                                src.positions.shape[-1])
                pos_src = src.positions
                if cap_s < cap_d:
                    pad = [(0, 0)] * pos_src.ndim
                    pad[-1] = (0, cap_d - cap_s)
                    pos_src = jnp.pad(pos_src, pad, constant_values=-1)
                pos = jax.lax.dynamic_update_slice_in_dim(
                    dst.positions, pos_src.astype(dst.positions.dtype),
                    j, axis=dst.positions.ndim - 2)
                return PagedState(pool, dst.page_table, dst.seq_lens, pos)
            if isinstance(dst, dict):
                return {k: visit(dst[k], src[k]) for k in dst}
            if isinstance(dst, (list, tuple)):
                out = [visit(a, b) for a, b in zip(dst, src)]
                return tuple(out) if isinstance(dst, tuple) else out
            return dst

        self.caches = {k: visit(self.caches[k], part[k])
                       for k in self.caches}
        if self.mesh is not None:
            self.repin_cache_shardings()

    def _decode_spilled(self, r: ServeRequest) -> int:
        """One decode step for a slot whose context has outgrown the
        local pool: assemble the extended view, run the ordinary jitted
        decode on it (batch-1; the jit trace cache keys on the extended
        shape), sample exactly like the batched path, write back."""
        assert self._session is None, (
            "spilled slots decode outside transform sessions")
        slot = r.slot
        with tracing.span("engine.decode", rids=(r.rid,)) as sp:
            ext = self._assemble_spilled(slot)
            tok = jnp.asarray([r.generated[-1]], jnp.int32)
            pos = jnp.asarray([r.context_len - 1], jnp.int32)
            sp.set(kv_live_tokens=r.context_len,
                   kv_read_tokens=self._kv_read_tokens(
                       ext, [r.context_len]))
            logits, ext = self._decode(self.params, ext, tok, pos,
                                       sp=self.par_layout.sp,
                                       mesh=self.mesh)
            nxt = _sample(logits, 0.0, self.rng)
            with tracing.span("engine.sync", kind="decode"):
                t = int(nxt[0])
            if r.temperature > 0:
                sub_rng = jax.random.fold_in(
                    jax.random.fold_in(self.rng, r.rid), r.context_len)
                t = int(_sample(logits[0][None], r.temperature,
                                sub_rng)[0])
            self.spill_slot(slot, ext)
        r.generated.append(t)
        if (len(r.generated) >= r.max_new_tokens
                or (r.eos_id is not None and t == r.eos_id)
                or r.context_len >= self._slot_ceiling(slot)):
            r.state = State.DONE
            r.t_done = self._clock()
            self.slots[slot] = None
        return 1

    def _release_spill(self, slot: int) -> None:
        sp = self._spills.pop(slot)
        sp["host"].release_hosted(sp["hosting"]["handle"])

    # -- one engine iteration --------------------------------------------
    def step(self) -> Dict[str, int]:
        """One engine iteration.  A live transformation in progress
        executes ONE §4.3 schedule step per iteration, double-buffered
        against serving: the step's transfers are DISPATCHED before the
        decode iteration and completed at the start of the next one (or
        after this one's decode, for the final step), so weight/KV
        movement hides under decode compute.  Decode and chunked prefill
        run THROUGH the session — cross-device (merge/split) sessions
        included, thanks to layer-coherent schedule steps and boundary
        ``device_put`` of activations — so a transforming engine never
        emits a zero-token step while it holds decodable work."""
        with tracing.span("engine.step", iid=self.iid):
            emitted = 0
            decode_emitted = 0
            if self._session is not None:
                s = self._session
                # complete the transfers dispatched last iteration (they
                # overlapped that iteration's decode), then issue the next
                # step's transfers so THIS decode hides them
                with tracing.span("engine.session") as sp:
                    self._complete_session_step(sp)
                    if self._session is not None:
                        # stage the next step and prime ONE layer group; the
                        # decode iteration's layer walk streams the rest
                        # (``on_decode_layer``: layer L's weights move while
                        # layer L-1 computes), with a drain after the walk
                        # for whatever the walk couldn't safely overlap
                        s.dispatch_step_begin()
                        s.dispatch_step_advance()
            in_session = self._session is not None
            cross_session = in_session and self._session_cross
            # policy-driven prefill work (admissions + chunk advancement);
            # chunked prefills keep advancing during sessions via the
            # per-layer path, whole-prompt prefills wait for the drain
            emitted += self._prefill_step()

            active = [r for r in self.slots
                      if r is not None and r.state == State.DECODE]
            # spilled slots past the local ceiling decode one-by-one on the
            # extended (local + host pages) view; everything else stays on
            # the batched fast path
            lcap = self._local_page_cap() if self._spills else 0
            ext_active = [r for r in active
                          if r.slot in self._spills
                          and r.context_len - 1 >= lcap]
            ext_slots = {r.slot for r in ext_active}
            batch_active = [r for r in active if r.slot not in ext_slots]
            # the batched decode appends masked filler at EVERY row's cursor
            # — including spilled rows whose local pages are completely full
            # of real prefix (cursor % capacity would land ON it).  Save
            # the views of the spilled rows the batch does not decode and
            # restore them after it (a spilled row the batch decodes keeps
            # its new token).
            batch_slots = {r.slot for r in batch_active}
            protect = [s for s in self._spills
                       if s not in batch_slots] if batch_active else []
            saved = {s: self._extract_slot_cache(s) for s in protect}
            if batch_active:
                n = self._decode_batch(batch_active)
                emitted += n
                decode_emitted += n
            for s, sub in saved.items():
                self._adopt_slot_cache(sub, s, 0)
            for r in ext_active:
                n = self._decode_spilled(r)
                emitted += n
                decode_emitted += n
            for s in [s for s in self._spills if self.slots[s] is None]:
                self._release_spill(s)
            # the final schedule step's transfers overlapped this decode;
            # complete them now so the session drains within this iteration
            if self._session is not None and self._session.all_dispatched:
                with tracing.span("engine.session") as sp:
                    self._complete_session_step(sp)
            self.steps += 1
            return {"active": len(active), "waiting": len(self.waiting),
                    "emitted": emitted, "decode_emitted": decode_emitted,
                    "transforming": int(in_session),
                    "cross_session": int(cross_session)}

    def _complete_session_step(self, sp) -> None:
        """Block on the session step dispatched last, record its exposed
        time on ``sp``, and close the session once its schedule ran
        out."""
        rep = self._session.complete_step()
        if rep is not None:
            sp.set(blocked_s=rep.blocked_s)
        if self._session.done:
            self._finish_transform()

    def _decode_batch(self, batch_active: List[ServeRequest]) -> int:
        """One batched decode step for the rows of ``batch_active``;
        returns the tokens emitted."""
        with tracing.span("engine.decode",
                          rids=tuple(r.rid for r in batch_active)) as sp:
            tokens = np.zeros((self.max_batch,), np.int32)
            positions = np.zeros((self.max_batch,), np.int32)
            for r in batch_active:
                tokens[r.slot] = r.generated[-1]
                positions[r.slot] = r.context_len - 1
            caches = (self.caches if self._session is None
                      else [layer["cache"] for layer in self._session.layers])
            contexts = [r.context_len for r in batch_active]
            sp.set(kv_live_tokens=sum(contexts),
                   kv_read_tokens=self._kv_read_tokens(caches, contexts))
            logits = self._decode_dispatch(
                jnp.asarray(tokens), jnp.asarray(positions))
            nxt = _sample(logits, 0.0, self.rng)  # greedy batch default
            with tracing.span("engine.sync", kind="decode"):
                nxt = np.asarray(nxt)
            for r in batch_active:
                tok = int(nxt[r.slot])
                if r.temperature > 0:
                    sub_rng = jax.random.fold_in(
                        jax.random.fold_in(self.rng, r.rid), r.context_len)
                    tok = int(_sample(logits[r.slot][None], r.temperature,
                                      sub_rng)[0])
                r.generated.append(tok)
                if (len(r.generated) >= r.max_new_tokens
                        or (r.eos_id is not None and tok == r.eos_id)
                        or r.context_len >= self._slot_ceiling(r.slot)):
                    r.state = State.DONE
                    r.t_done = self._clock()
                    self.slots[r.slot] = None
            self._pin_prefill_cursors()
        return len(batch_active)

    def _decode_dispatch(self, tokens: jax.Array,
                         positions: jax.Array) -> jax.Array:
        """One decode step on whichever representation is live: the
        per-layer path mid-transformation (layers sit on mixed mesh
        factorizations and, for cross-device sessions, on two device
        assemblies — each layer coherently on one), the stacked jit
        otherwise."""
        if self._session is not None:
            s = self._session
            logits, new_layers = M.decode_step_layers(
                s.layers, s.static, self.cfg, self.plan, tokens,
                positions, self.layout, static_mesh=s.static_mesh,
                on_layer=s.on_decode_layer,
                use_kernel=self.pallas_kernels)
            s.layers = new_layers
            # groups the walk couldn't overlap (their layer was already
            # walked) dispatch now, against the walk's updated layers
            s.dispatch_step_drain()
            return logits
        logits, self.caches = self._decode(self.params, self.caches,
                                           tokens, positions,
                                           sp=self.par_layout.sp,
                                           mesh=self.mesh)
        return logits

    def _kv_read_tokens(self, caches, contexts: List[int]) -> int:
        """KV tokens one decode step's attention reads per layer from
        ``caches`` for its widest pool, ``contexts`` being the decoding
        rows' context lengths.  The jnp paths read every row's whole
        reservation, live or not (rows x capacity of the pool's
        ``positions``).  The paged-attention kernel reads each row's
        live pages: a decoding row's context rounded up to whole pages
        (every page once it passes a ring's capacity), one page of any
        other row (its query sits at position 0)."""
        from repro.paged.pool import PagedState

        if self._session is None:
            kernel = self.par_layout.sp == 1
        else:
            kernel = all(l.get("mesh") is None or l["mesh"].shape["sp"] == 1
                         for l in self._session.layers)
        P = self.page_tokens

        def read(x):
            rows, cap = x.positions.shape[-2:]
            if not (self.pallas_kernels and kernel):
                return rows * cap
            n = cap // P
            pages = sum(min(-(-c // P), n) for c in contexts)
            return (pages + rows - len(contexts)) * P

        pools = jax.tree.leaves(caches,
                                is_leaf=lambda x: isinstance(x, PagedState))
        return max((read(x) for x in pools if isinstance(x, PagedState)),
                   default=0)

    def run_until_done(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if (not self.waiting and not self.transforming
                    and all(s is None for s in self.slots)):
                return
            self.step()
        raise RuntimeError("engine did not drain")


def _batch_axis(dst, src) -> int:
    """Find the batch axis: the one where dst is max_batch and src is 1."""
    for ax in range(dst.ndim):
        if src.shape[ax] == 1 and dst.shape[ax] != 1:
            return ax
    return max(dst.ndim - 2, 0)
