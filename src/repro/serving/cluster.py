"""Multi-instance serving control plane: the §5 scheduler drives LIVE
engines.

``ClusterEngine`` runs N live ``Engine`` instances on disjoint device
subsets of one process (each engine owns its own ``(rep, sp, tp)``
mesh) and
drives them with the *same* ``BaseScheduler``/``GygesScheduler`` that
drives the event simulator:

* **routing** (Alg 1): ``submit`` asks ``scheduler.pick`` for an
  instance view; every live engine implements the ``InstanceView``
  protocol, so the policy is byte-for-byte the one the simulator runs;
* **scale-up** (Alg 1 lines 14-16): a long request that no instance can
  admit yields a declarative ``ScaleUp`` action from
  ``scheduler.decide_scale_up``; the control plane executes it via
  ``Engine.transform(tp_to)`` — the §4.3 schedule then runs one step per
  decode iteration inside ``Engine.step``, so migration interleaves with
  serving and in-flight tokens are bit-exact across the boundary;
* **scale-down** (Alg 2): each cluster step, ``schedule_parallelism``
  scans the dwell-gated instances and returns ``ScaleDown`` actions the
  plane executes the same way;
* **cross-instance merge** (paper Fig. 3): the cluster owns ONE shared
  device pool — every engine's devices are a loanable subset.  A
  ``ScaleUp`` naming ``donor_iids`` is executed by draining + parking
  each donor, exporting its in-flight KV, handing its devices to the
  target (``Engine.adopt_devices`` grows the pool so physical KV
  follows the TP degree), importing the donors' requests
  (cross-engine ``device_put`` + §4.1 kernel scatter), and running the
  SAME ``Engine.transform`` session across the widened mesh — decode
  and chunked prefill keep flowing THROUGH the session (layer-coherent
  schedule steps, per-layer assembly staging; ``stall_steps`` /
  ``tokens_during_session`` measure it and the merge smoke asserts
  zero stalls).  A later ``ScaleDown`` on the merged engine transforms
  back onto its home devices, returns the loan, and revives the parked
  donors.

The sim/live split this closes: ``cluster_sim.Cluster`` and
``ClusterEngine`` consume the same scheduler (including the shared
merge donor-selection policy, ``decide_merge``), the same request
metrics (``serving.metrics.summarize``) and report a key-identical
schema.  See docs/architecture.md (module map) and
docs/transformation-lifecycle.md (an executed merge walkthrough).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.core.partition import Loan, PoolPartitionManager
from repro.core.scheduler import (Action, BaseScheduler, GygesScheduler,
                                  PrefillPolicy, ScaleDown, ScaleUp,
                                  SchedulerConfig, Spill)
from repro.serving import tracing
from repro.serving.engine import Engine
from repro.serving.metrics import summarize
from repro.serving.request import ServeRequest, State


class ClusterEngine:
    """N live transformable engines over one shared device pool, driven
    by one scheduler policy.

    Invariants the control plane maintains:

    * every pool device is owned by exactly one non-parked engine (or on
      loan to a merge target, recorded in ``_loans``);
    * at most one transformation session per engine; scale actions only
      target engines with none in flight;
    * the padding plan is built for the FULL pool width, so any merged
      TP degree keeps weight shards page-aligned (callers passing
      ``params`` must build them with that plan — ``self.plan``);
    * sim parity: ``metrics()`` is key-identical with
      ``cluster_sim.Cluster.metrics`` and every scale decision comes
      from the same ``BaseScheduler`` hooks the simulator consumes.
    """

    def __init__(self, cfg: ModelConfig, devices: Sequence[jax.Device],
                 n_instances: int = 2, max_batch: int = 2,
                 max_seq: int = 64, page_tokens: int = 16,
                 scheduler: Optional[BaseScheduler] = None,
                 rng: Optional[jax.Array] = None, params=None,
                 dwell_steps: int = 8, layout: str = "header_centric",
                 transform_attn: bool = True,
                 prefill_policy: Optional[PrefillPolicy] = None,
                 clock=None, pallas_kernels: Optional[bool] = None):
        if n_instances < 1 or len(devices) < n_instances:
            raise ValueError(f"{n_instances} instances need at least "
                             f"{n_instances} of {len(devices)} devices")
        W = len(devices) // n_instances
        self.cfg = cfg
        # request-timestamp source shared with every engine: the wall
        # clock in normal serving, a core.events.VirtualClock under an
        # event-driven replay (TTFT/TPOT/goodput in virtual trace time)
        self._clock = clock if clock is not None else time.monotonic
        self.dwell_steps = dwell_steps
        self.total_width = n_instances * W      # the shared device pool
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        from repro.core.padding import make_plan
        # plan for the FULL pool width: a merge may factorize any engine
        # across every pool device, and page alignment must survive that
        self.plan = make_plan(cfg, self.total_width, mode="page")
        if params is None:
            from repro.models import model as M
            params = M.init_params(jax.random.fold_in(rng, 1), cfg,
                                   self.plan)
        # revive() re-shards these; host memory keeps that spare copy off
        # every device once the engines' own weights transform
        self._params_src = jax.device_get(params)
        self.prefill_policy = prefill_policy or PrefillPolicy()
        self.engines: List[Engine] = [
            Engine(cfg, params=params, max_batch=max_batch,
                   max_seq=max_seq, page_tokens=page_tokens, rng=rng,
                   layout=layout, devices=list(devices[k * W:(k + 1) * W]),
                   transform_attn=transform_attn, iid=k, plan=self.plan,
                   prefill_policy=self.prefill_policy, clock=self._clock,
                   pallas_kernels=pallas_kernels)
            for k in range(n_instances)]
        if scheduler is None:
            base = self.engines[0].max_seq_at(1)
            scheduler = GygesScheduler(SchedulerConfig(
                long_threshold=base, target_tp=W,
                page_tokens=page_tokens))
        elif hasattr(scheduler, "cfg") \
                and hasattr(scheduler.cfg, "page_tokens"):
            # spill rung costs price segments against THIS pool's page
            # geometry, not the SchedulerConfig default
            scheduler.cfg.page_tokens = page_tokens
        self.scheduler = scheduler
        # measured-cost feedback cursors: how many transform/spill log
        # records per engine have already been fed to the attached cost
        # model's EWMA (core.calibrate.CalibratedCostModel)
        self._cost_fed: Dict[int, Tuple[int, int]] = {}

        self.waiting: List[ServeRequest] = []   # router-level queue
        self.requests: List[ServeRequest] = []  # everything submitted
        self.actions: List[Action] = []         # executed, in order
        self.placements: Dict[int, int] = {}    # rid -> engine iid (the
                                                # routing decision record
                                                # the parity harness
                                                # diffs against the sim)
        self.steps = 0
        self.n_transforms = 0
        self.total_tokens = 0
        # overlap accounting (the Fig. 11 <1% claim, measured live):
        # engine steps taken while a cross-device session was open and
        # decodable work existed, tokens emitted during those steps,
        # and FULL-STALL steps (decode slots active, zero decode
        # tokens) — the quantity bench_e2e --merge-smoke asserts == 0
        self.session_steps = 0
        self.tokens_during_session = 0
        self.stall_steps = 0
        self._last_transform_step = {e.iid: -(10 ** 9) for e in self.engines}
        # device-pool ledger: who holds which device, what is on loan,
        # who is parked, whose overflow pages live where — one
        # first-class object shared conceptually with the simulator
        # (core.partition.PoolPartitionManager)
        self.partition = PoolPartitionManager()
        for e in self.engines:
            self.partition.register(e.iid, list(e.devices))
        self._releasing: Set[int] = set()       # splits awaiting drain
        # partial merges in flight: donors are shrinking; the target
        # adopts the loaned devices once every donor's session drains
        self._pending_partials: List[Dict] = []
        self.spill_pages = 0
        self.partial_merges = 0
        # stamped at the first submit so engine construction / jit
        # compile time does not dilute throughput_tps
        self.t_start: Optional[float] = None
        self._update_reserve()

    # ------------------------------------------------------------------
    @property
    def _loans(self) -> Dict[int, List[Tuple[int, List[jax.Device]]]]:
        """Read-only view of the partition ledger in the legacy
        ``target iid -> [(donor iid, devices)]`` shape (tests and older
        callers peek at it); the ledger itself lives in
        ``self.partition``."""
        out: Dict[int, List[Tuple[int, List[jax.Device]]]] = {}
        for e in self.engines:
            for loan in self.partition.loans_to(e.iid):
                out.setdefault(loan.borrower, []).append(
                    (loan.lender, list(loan.devices)))
        return out

    def _engine(self, iid: int) -> Engine:
        return next(e for e in self.engines if e.iid == iid)

    def _active_engines(self) -> List[Engine]:
        """Engines that currently own devices (parked donors are
        invisible to routing and scheduling until revived)."""
        return [e for e in self.engines if not e.parked]

    def _transformable(self) -> List[Engine]:
        """Scale actions may only target engines with no transformation
        in flight (one open session per engine).  Routing, by contrast,
        sees every non-parked engine: a transforming engine advertises
        its *target* capacity (``Engine.max_seq``) — which is a SERVING
        capacity, not a promise: the engine keeps decoding and
        chunk-prefilling through merge/split sessions (its pool is
        already grown to the target allocation), so follow-up long
        requests ride the existing transformation instead of triggering
        another one and start chunking immediately.  Engines with open
        spill regions (guest or host) cannot transform until they close
        — a pool resize would move hosted/overflow pages out from under
        the distributed page tables — and a partial-merge target
        awaiting its loaned devices is already committed."""
        return [e for e in self.engines
                if not e.transforming and not e.parked
                and not e.awaiting_devices
                and not e._spills and not e._hosted]

    def _update_reserve(self) -> None:
        """update_reserve() (Alg 2 line 9), live form: earmark the
        least-loaded TP1 engine as the next scale-up candidate so short
        requests keep transformation headroom free on it."""
        if not isinstance(self.scheduler, GygesScheduler):
            return
        for e in self.engines:
            e.reserved = False
        # a transforming engine still reports its OLD tp until the
        # session drains — the sim flips tp at execution, so counting
        # one here as a TP1 reserve candidate leaves a stale reserve on
        # what is really a wide instance (and decide_layout skips
        # reserved instances: a live/sim decision divergence)
        tp1 = sorted((e for e in self._active_engines()
                      if e.tp == 1 and not e.transforming),
                     key=lambda e: e.kv_used_fraction())
        if tp1:
            tp1[0].reserved = True

    # ------------------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        """Route one request (Alg 1).  Rejects only requests that exceed
        the whole POOL's merged capacity — anything below that is
        servable by borrowing idle engines."""
        total = req.total_tokens
        if total > max(e.max_seq_at(self.total_width)
                       for e in self._active_engines()):
            raise ValueError(
                f"request {req.rid}: {total} tokens exceeds the device "
                f"pool's merged capacity")
        if self.t_start is None:
            self.t_start = self._clock()
        # restamp on the serving clock: under a virtual-clock replay the
        # constructor default (wall monotonic) is on the wrong axis
        req.t_submit = self._clock()
        self.scheduler.observe_arrival(req.t_submit, total)
        self.requests.append(req)
        with tracing.span("cluster.route") as sp:
            placed = self._place(req)
            if not placed:
                self.waiting.append(req)
            sp.set(placed=int(placed), waiting=len(self.waiting))

    def _place(self, req: ServeRequest) -> bool:
        total = req.total_tokens
        inst = self.scheduler.pick(self._active_engines(),
                                   len(req.prompt), req.max_new_tokens)
        if inst is not None and total > inst.max_seq():
            # transformation-unaware pick (RR/LLF skip the valid() check):
            # capacity must grow AROUND the chosen instance — the paper's
            # Fig. 13 pathology, reproduced live through the SAME
            # decide_seed_scale_up policy the simulator executes
            if inst.transforming:
                return False
            act = self.scheduler.decide_seed_scale_up(
                self._transformable(), inst, total)
            if act is not None and self._execute(act):
                self.placements[req.rid] = act.iid
                self._engine(act.iid).submit(req)
                return True
            # no growth is possible around the seed (e.g. it is already
            # scaled up): fall through to the unrestricted decide path,
            # exactly as the simulator's _place does
            inst = None
        if inst is not None:
            self.placements[req.rid] = inst.iid
            inst.submit(req)
            return True
        act = self.scheduler.decide_scale_up(self._transformable(),
                                             len(req.prompt),
                                             req.max_new_tokens)
        while act is not None:
            if isinstance(act, Spill):
                if self._execute_spill(req, act):
                    self.placements[req.rid] = act.iid
                    return True
                # spill target out of free slots (stale view): fall one
                # rung DOWN the ladder — partial merge, then full merge
                # — instead of failing the placement
                act = (self.scheduler.decide_partial_merge(
                           self._transformable(), total)
                       or self.scheduler.decide_merge(
                           self._transformable(), total))
                continue
            if self._execute(act):
                # the request rides the transforming engine's queue;
                # Engine.step admits it once capacity is resident
                self.placements[req.rid] = act.iid
                self._engine(act.iid).submit(req)
                return True
            return False
        return False

    # ---- action execution (the §5 control plane's write side) ---------
    def _execute(self, act: Action) -> bool:
        """Execute one declarative action.  Returns False when a merge's
        preconditions fail (e.g. no free slots for the donors' in-flight
        requests) — the caller leaves the request waiting and a later
        retry re-decides."""
        eng = self._engine(act.iid)
        if isinstance(act, ScaleUp) and act.donor_devices:
            n_steps = self._merge_partial(act, eng)
            if n_steps is None:
                return False
        elif isinstance(act, ScaleUp) and act.donor_iids:
            n_steps = self._merge(act, eng)
            if n_steps is None:
                return False
        elif isinstance(act, ScaleDown) and self.partition.loans_to(act.iid):
            n_steps = self._split(act, eng)
        else:
            # ScaleUp may carry a target parallelism layout (the elastic
            # -SP rung: a same-degree re-factorization like TP4 ->
            # SP2xTP2); ScaleDown has no layout field — bare degrees
            # resolve to pure TP inside Engine.transform
            n_steps = eng.transform(act.tp_to,
                                    layout=getattr(act, "layout", None))
        self.actions.append(act)
        self.n_transforms += 1
        self._last_transform_step[eng.iid] = self.steps
        self._update_reserve()
        kind = "up" if isinstance(act, ScaleUp) else "down"
        assert n_steps > 0 or act.tp_to == eng.tp \
            or act.donor_devices, (kind, act)
        return True

    def _merge(self, act: ScaleUp, eng: Engine) -> Optional[int]:
        """Cross-instance merge (Fig. 3): park the donors, loan their
        devices to ``eng``, migrate the donors' live KV into its grown
        pool, then transform across the widened mesh.  Returns the
        session's step count, or None if preconditions fail (nothing is
        mutated in that case)."""
        donors = [self._engine(i) for i in act.donor_iids]
        if eng.transforming or eng.parked or eng.tp != 1:
            return None
        if any(d.transforming or d.parked or d.tp != 1 for d in donors):
            return None
        n_inflight = sum(1 for d in donors for s in d.slots
                         if s is not None)
        if n_inflight > eng.slots.count(None):
            return None
        assert all(d.seq_quantum == eng.seq_quantum for d in donors), (
            "merging requires uniform per-device admission quanta")
        exported = []
        adopted: List[jax.Device] = []
        for d in donors:
            # donor queue back to the router (FCFS head: they were
            # admitted before anything currently waiting)
            self.waiting[:0] = d.waiting
            d.waiting = []
            exported += d.export_active()
            devs = d.park()
            loan = self.partition.lend(d.iid, eng.iid, devs, whole=True)
            self.partition.park(d.iid)
            self.partition.adopt(eng.iid, loan)
            adopted += devs
        eng.adopt_devices(adopted)
        for req, sub, progress in exported:
            eng.import_request(req, sub, repin=False, progress=progress)
        if exported:
            eng.repin_cache_shardings()
        n_steps = eng.transform(act.tp_to)
        return n_steps

    def _merge_partial(self, act: ScaleUp, eng: Engine) -> Optional[int]:
        """Partial merge (LoongServe-style fractional elasticity): each
        donor sheds a FRACTION of its devices via an in-place shrink
        transform — it keeps serving at reduced width, nothing parks,
        no KV is exported — and the target widens onto the loaned
        devices once every donor's session drains
        (``_advance_partials``).  Returns the donors' summed session
        steps, or None when preconditions fail (nothing mutated)."""
        donors = [self._engine(i) for i in act.donor_iids]
        if eng.transforming or eng.parked or eng.tp != 1 \
                or eng.awaiting_devices:
            return None
        if any(d.transforming or d.parked or d is eng
               or d.awaiting_devices for d in donors):
            return None
        if any(n <= 0 or n >= d.W
               for d, n in zip(donors, act.donor_devices)):
            return None        # a donor must retain ≥1 device to serve
        assert all(d.seq_quantum == eng.seq_quantum for d in donors), (
            "partial merges require uniform per-device admission quanta")
        n_steps = 0
        loans: List[Loan] = []
        for d, n in zip(donors, act.donor_devices):
            keep = list(d.devices[:d.W - n])
            loaned = list(d.devices[d.W - n:])
            # largest parallel degree the retained width can carry
            new_tp = max(t for t in range(1, min(d.tp, len(keep)) + 1)
                         if len(keep) % t == 0)
            n_steps += d.transform(new_tp, devices=keep)
            loans.append(self.partition.lend(d.iid, eng.iid, loaned,
                                             whole=False))
            self._last_transform_step[d.iid] = self.steps
        eng.awaiting_devices = True
        self._pending_partials.append(
            {"iid": eng.iid, "tp_to": act.tp_to, "loans": loans,
             "donors": [d.iid for d in donors]})
        return n_steps

    def _advance_partials(self) -> None:
        """Second phase of a partial merge: once every donor's shrink
        session has drained (the loaned devices hold no donor arrays),
        the target adopts them and widens across the grown mesh — still
        serving its own work throughout."""
        for p in list(self._pending_partials):
            donors = [self._engine(i) for i in p["donors"]]
            eng = self._engine(p["iid"])
            if any(d.transforming for d in donors) or eng.transforming:
                continue
            self._pending_partials.remove(p)
            devs = [dv for loan in p["loans"] for dv in loan.devices]
            eng.adopt_devices(devs)
            for loan in p["loans"]:
                self.partition.adopt(eng.iid, loan)
            eng.transform(p["tp_to"])
            eng.awaiting_devices = False
            self.partial_merges += 1
            self._last_transform_step[eng.iid] = self.steps
            self._update_reserve()

    def _execute_spill(self, req: ServeRequest, act: Spill) -> bool:
        """Rung 1 of the capacity ladder: serve a pool-ceiling-busting
        request with NO transformation at all — the host engine reserves
        whole free slots for the overflow pages and the guest serves the
        request with decode attention gathering across both pools.
        Returns False (nothing mutated) when the host cannot grant the
        reservation; the caller falls back to a partial/full merge."""
        guest = self._engine(act.iid)
        host = self._engine(act.host_iid)
        if guest is host or guest.transforming or guest.parked \
                or host.transforming or host.parked:
            return False
        if guest._free_slot() is None:
            return False
        pt = guest.page_tokens
        n_pages = -(-max(req.total_tokens - guest._local_page_cap(), 1)
                    // pt)
        hosting = host.host_spilled(n_pages)
        if hosting is None:
            return False
        guest.admit_spilled(req, host, hosting)
        self.partition.open_spill(guest.iid, host.iid, req.rid,
                                  hosting["pages"], hosting["slots"],
                                  handle=hosting["handle"])
        self.actions.append(act)
        self.spill_pages += -(-act.tokens // pt)
        self._update_reserve()
        return True

    def _finalize_spills(self) -> None:
        """Close spill regions whose request has finished (the engines
        already freed the slots and released the hosting reservation)."""
        done = {r.rid for r in self.requests if r.finished}
        for region_id, region in list(self.partition.spills().items()):
            if region.rid in done:
                self.partition.close_spill(region_id)

    def _split(self, act: ScaleDown, eng: Engine) -> int:
        """Undo a merge: transform back onto the engine's home devices;
        the loaned devices are returned and the donors revived once the
        session drains (``_finalize_releases``)."""
        assert act.tp_to == 1, "merged engines decompose fully (Alg 2)"
        n_steps = eng.transform(act.tp_to, devices=eng.home_devices)
        self._releasing.add(eng.iid)
        return n_steps

    def _finalize_releases(self) -> None:
        """Second half of a split: once the shrinking engine's session
        has drained (its arrays live only on its home devices again),
        return each loan — reviving parked whole-engine donors, and
        widening partial donors back onto their returned devices (a
        cross-device grow session; they never stopped serving)."""
        for iid in list(self._releasing):
            eng = self._engine(iid)
            if eng.transforming:
                continue
            self._releasing.discard(iid)
            by_lender: Dict[int, List[Loan]] = {}
            for loan in self.partition.loans_to(iid):
                by_lender.setdefault(loan.lender, []).append(loan)
            for lender_iid, loans in by_lender.items():
                donor = self._engine(lender_iid)
                devs = [d for ln in loans
                        for d in self.partition.return_loan(ln)]
                if any(ln.whole for ln in loans):
                    self.partition.revive(lender_iid)
                    donor.revive(devs, self._params_src)
                else:
                    donor.transform(donor.tp,
                                    devices=list(donor.devices) + devs)
                self._last_transform_step[lender_iid] = self.steps
            self._update_reserve()

    # ------------------------------------------------------------------
    def _any_long_waiting(self) -> bool:
        cap1 = max(e.max_seq_at(1) for e in self._active_engines())
        return any(self.scheduler.is_long(r.total_tokens)
                   or r.total_tokens > cap1 for r in self.waiting)

    def step(self) -> Dict[str, int]:
        """One control-plane iteration: retry routing, run Alg 2, one
        engine iteration each (a transforming engine executes one §4.3
        schedule step before its decode), then finalize any completed
        splits (return device loans, revive parked donors)."""
        with tracing.span("cluster.route") as sp:
            self.scheduler.observe_time(self._clock())
            # FCFS retry of the router queue (stop at the first
            # unplaceable).  Pop BEFORE placing: a merge inside _place
            # prepends the donor's queue to self.waiting, so popping
            # afterwards would drop one of those and leave the placed
            # request queued twice.
            placed = 0
            while self.waiting:
                req = self.waiting.pop(0)
                if not self._place(req):
                    self.waiting.insert(0, req)
                    break
                placed += 1
            sp.set(placed=placed, waiting=len(self.waiting))
        with tracing.span("cluster.plan") as sp:
            # Alg 2 over dwell-gated, non-transforming instances (spill
            # participants cannot transform while their regions are
            # open)
            eligible = [
                e for e in self._active_engines()
                if e.tp > 1 and not e.transforming
                and not e._spills and not e._hosted
                and not e.awaiting_devices
                and self.steps - self._last_transform_step[e.iid]
                >= self.dwell_steps]
            acts = self.scheduler.schedule_parallelism(
                eligible, self._any_long_waiting())
            for act in acts:
                self._execute(act)
            # elastic-SP layout scan (opt-in via SchedulerConfig.layouts),
            # decision-for-decision with cluster_sim.Cluster.advance: any
            # wide instance outside a transform window may re-factorize
            # its degree to the (sp, tp) layout that wins its current
            # workload mix — a same-degree §4.3 session, serving
            # throughout
            lay_eligible = [
                e for e in self._active_engines()
                if e.tp > 1 and not e.transforming
                and not e._spills and not e._hosted
                and not e.awaiting_devices]
            lay_acts = self.scheduler.decide_layout(lay_eligible)
            for act in lay_acts:
                self._execute(act)
            sp.set(eligible=len(eligible),
                   actions=len(acts) + len(lay_acts))
        emitted = active = queued = 0
        for e in self._active_engines():
            # stall detection is computed from CONTROL-PLANE-visible
            # state before the step (session open? decodable slots?),
            # not from the engine's self-report: a regression that
            # early-returns from Engine.step without decoding would
            # also drop the report keys, and a guard built on them
            # would vacuously pass (review finding)
            cross = e.transforming and e._session_cross
            decoding = (sum(1 for r in e.slots if r is not None
                            and r.state == State.DECODE) if cross else 0)
            s = e.step()
            emitted += s["emitted"]
            active += s["active"]
            queued += s["waiting"]
            if cross:
                self.session_steps += 1
                self.tokens_during_session += s["emitted"]
                if decoding > 0 and s.get("decode_emitted", 0) == 0:
                    self.stall_steps += 1
            if e.transforming:
                # dwell counts from transformation END (sim parity:
                # now > transform_until + dwell) — keep re-stamping
                # until the schedule drains
                self._last_transform_step[e.iid] = self.steps
        with tracing.span("cluster.finalize"):
            self._advance_partials()
            self._finalize_releases()
            self._finalize_spills()
            self._feed_measured_costs()
        self.total_tokens += emitted
        self.steps += 1
        return {"active": active, "emitted": emitted,
                "engine_waiting": queued, "router_waiting":
                len(self.waiting),
                "transforming": sum(e.transforming for e in self.engines),
                "parked": sum(e.parked for e in self.engines)}

    def _feed_measured_costs(self) -> None:
        """Measured-cost feedback (core.calibrate): stream every NEW
        realized transform/spill wall time from the engines' logs into
        the attached cost model's EWMA, so ``_rung_cost`` and the
        pressure horizon consume what this backend actually clocked
        once a (kind, degree-pair) key is warm.  A plain ``CostModel``
        has no ``observe_transform`` — the loop is then a no-op and the
        modeled prior keeps deciding (cold-start rule)."""
        cm = getattr(self.scheduler, "cost_model", None)
        if cm is None or not hasattr(cm, "observe_transform"):
            return
        for e in self.engines:
            t_fed, s_fed = self._cost_fed.get(e.iid, (0, 0))
            for rec in e.transform_log[t_fed:]:
                cm.observe_transform(rec)
            for rec in e.spill_log[s_fed:]:
                cm.observe_transform(rec)
            self._cost_fed[e.iid] = (len(e.transform_log),
                                     len(e.spill_log))

    # ------------------------------------------------------------------
    @property
    def idle(self) -> bool:
        return (not self.waiting and not self._releasing
                and not self._pending_partials
                and all(not e.transforming and not e.waiting
                        and all(s is None for s in e.slots)
                        for e in self.engines))

    def run(self, requests: Sequence[ServeRequest] = (),
            max_steps: int = 10_000,
            drain_steps: Optional[int] = None) -> Dict[str, float]:
        """Submit ``requests`` and step until the cluster drains, then
        keep stepping through a quiet window (default: one dwell period)
        so Alg 2 can return scaled-up instances to TP1 — the sim's
        ``drain`` parameter, live."""
        for r in requests:
            self.submit(r)
        drain = self.dwell_steps + 2 if drain_steps is None else drain_steps
        quiet = 0
        for _ in range(max_steps):
            if self.idle:
                if quiet >= drain:
                    return self.metrics()
                quiet += 1
            else:
                quiet = 0
            self.step()
        raise RuntimeError("cluster did not drain")

    def metrics(self) -> Dict[str, float]:
        """Same schema as ``cluster_sim.Cluster.metrics`` — key-for-key
        (tests/test_cluster_engine.py asserts it).  Transform latency /
        drift / merge-wall columns aggregate the per-action records
        every engine keeps (``Engine.transform_log``, built from the
        session ``StepReport``s); parked donors' records included."""
        elapsed = 0.0 if self.t_start is None else (
            self._clock() - self.t_start)
        logs = [t for e in self.engines for t in e.transform_log]
        return summarize(self.requests, elapsed, self.total_tokens,
                         self.n_transforms, transforms=logs,
                         spill_pages=self.spill_pages,
                         partial_merges=self.partial_merges)


class LiveReplayPlane:
    """Adapts a live ``ClusterEngine`` to the ``core.events.replay``
    plane protocol, so the SAME event-driven loop that drives the
    simulator drives real engines: each trace ``Request`` is
    materialized into a token-level ``ServeRequest`` (deterministic
    random prompt ids of its ``in_len``) at its arrival event, and one
    ``ClusterEngine.step`` serves each ``advance``.

    The cluster must have been built with the replay's
    ``core.events.VirtualClock`` as its ``clock`` so request timestamps
    (and therefore TTFT/TPOT/goodput) land on the virtual axis the
    arrival events use."""

    def __init__(self, cluster: ClusterEngine, seed: int = 0):
        import numpy as np
        self.cluster = cluster
        self._rng = np.random.default_rng(seed)
        self.served: Dict[int, ServeRequest] = {}

    def submit(self, trace_req, now: float) -> None:
        prompt = self._rng.integers(0, self.cluster.cfg.vocab_size,
                                    size=trace_req.in_len).tolist()
        sr = ServeRequest(rid=trace_req.rid, prompt=prompt,
                          max_new_tokens=trace_req.out_len,
                          slo=getattr(trace_req, "slo", None))
        self.served[trace_req.rid] = sr
        self.cluster.submit(sr)

    def advance(self, now: float, dt: float) -> None:
        self.cluster.step()

    @property
    def idle(self) -> bool:
        return self.cluster.idle
