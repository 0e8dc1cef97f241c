"""Live cross-instance merge: scale-up borrows whole idle engines.

ISSUE-3 acceptance (subprocess with 8 fake host devices): a 2-engine
``ClusterEngine`` receives a request longer than any single engine's
full-TP ceiling; the scheduler composes a MERGE (``ScaleUp`` with
``donor_iids``), the control plane parks the donor, loans its devices to
the target, migrates the donor's in-flight KV into the target's grown
pool, and runs the §4.3 transform session across the widened mesh.
Post-merge token streams are bit-identical to a reference engine started
at the merged TP width; a subsequent Alg-2 scale-down releases the
loaned devices, shrinks the pool, and revives the donor, which admits
requests again.  Fast (single-device) tests cover the scheduler's merge
composition and the cross-pool data-plane helpers.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_live_merge_bit_exact_and_split_revives_donor():
    """ISSUE-3 acceptance: scheduler-initiated live merge with donor
    in-flight KV migration, bit-exact streams vs a merged-width
    reference, then scale-down returns devices and revives the donor."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.padding import make_plan
        from repro.core.scheduler import ScaleDown, ScaleUp
        from repro.models import model as M
        from repro.serving.cluster import ClusterEngine
        from repro.serving.engine import Engine
        from repro.serving.metrics import METRIC_KEYS
        from repro.serving.request import ServeRequest

        # float32: bit-identical token streams across TP degrees is the
        # claim under test (bf16 reduction order can flip near-ties)
        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()
        plan = make_plan(cfg, len(devs), mode="page")
        host_params = M.init_params(jax.random.PRNGKey(11), cfg, plan)

        rng = np.random.default_rng(0)
        def spec():
            s = [(i, list(rng.integers(0, cfg.vocab_size, size=5 + i)), 8)
                 for i in range(3)]
            # 96 total tokens: above one engine's full-TP ceiling (64),
            # within the 2-engine merged ceiling (128)
            s.append((99, list(rng.integers(0, cfg.vocab_size, size=80)),
                      16))
            return s
        trace = spec()
        mk = lambda t: [ServeRequest(rid=r, prompt=list(p),
                                     max_new_tokens=n) for r, p, n in t]

        cluster = ClusterEngine(cfg, devs, n_instances=2, max_batch=4,
                                max_seq=64, params=host_params,
                                dwell_steps=4)
        assert [e.seq_quantum for e in cluster.engines] == [16, 16]
        live = mk(trace)
        for r in live[:3]:
            cluster.submit(r)
        for _ in range(2):
            cluster.step()
        # both engines must hold in-flight work so the merge really
        # migrates live donor KV
        assert all(any(s is not None for s in e.slots)
                   for e in cluster.engines), (
            [[s and s.rid for s in e.slots] for e in cluster.engines])
        cluster.submit(live[3])           # the merge trigger
        merges = [a for a in cluster.actions
                  if isinstance(a, ScaleUp) and a.donor_iids]
        assert merges, "long request did not trigger a live merge"
        act = merges[0]
        assert act.tp_to == len(devs)
        target = cluster._engine(act.iid)
        donor = cluster._engine(act.donor_iids[0])
        assert donor.parked and donor.devices == []
        assert target.W == len(devs) and target.transforming
        assert target.max_seq_alloc == 128     # pool grew with the loan
        # the donor's in-flight request now decodes on the target
        assert any(s is not None for s in target.slots)

        cluster.run(max_steps=5000)

        # zero-stall overlap (ISSUE-5): the merge/split sessions never
        # produced a step with decode slots active but no decode tokens
        assert cluster.stall_steps == 0, cluster.stall_steps
        assert cluster.tokens_during_session > 0

        downs = [a for a in cluster.actions if isinstance(a, ScaleDown)]
        assert downs, "merged engine never scaled back down"
        # split returned the loan: donor revived on its devices, pool
        # shrunk back, every engine at TP1 and home width.  Memory now
        # follows the TP degree on EVERY transform: the split target's
        # pool trimmed to the TP1 allocation (seq_quantum * tp = 16);
        # the revived donor re-allocates its construction-time budget
        assert all(not e.parked for e in cluster.engines)
        assert all(e.tp == 1 and e.W == 4 for e in cluster.engines)
        for e in cluster.engines:
            assert (e.seq_quantum * e.tp <= e.max_seq_alloc
                    <= e.seq_quantum * e.W), (e.iid, e.max_seq_alloc)
        assert cluster._engine(act.iid).max_seq_alloc == 16
        assert not cluster._loans and not cluster._releasing
        assert all(r.finished for r in live)
        # the §4.3 schedule really executed, with the §4.1 kernel plane
        # on the full-merge KV steps
        assert any(r.kernel_plane for r in target.transform_reports)

        # metrics schema parity holds for merged clusters
        m = cluster.metrics()
        assert list(m) == list(METRIC_KEYS)
        assert m["finished"] == m["total"] == 4
        assert m["n_transforms"] >= 2      # the merge + the split

        # the revived donor admits requests again
        post = ServeRequest(rid=200, prompt=trace[0][1][:4],
                            max_new_tokens=4)
        donor.submit(post)
        donor.run_until_done(500)
        assert post.finished

        # reference: each request alone on an engine STARTED at the
        # merged TP width (all 8 devices; batch 8 so TP1 construction
        # shards — slots are row-independent)
        ref = Engine(cfg, params=host_params, max_batch=8, max_seq=128,
                     devices=devs, plan=plan)
        for want, got in zip(mk(trace), live):
            ref.submit(want)
            ref.run_until_done(2000)
            assert want.generated == got.generated, (
                want.rid, want.generated, got.generated)
        print("MERGE_ACCEPTANCE_OK")
    """)
    assert "MERGE_ACCEPTANCE_OK" in out


@pytest.mark.slow
def test_merge_from_router_retry_keeps_every_request():
    """Regression: a merge decided inside step()'s router-queue retry
    prepends the donor's queued requests to the router queue; the loop
    must not drop one of them nor double-place the request it just
    routed."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.scheduler import ScaleUp
        from repro.serving.cluster import ClusterEngine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        cluster = ClusterEngine(cfg, jax.devices(), n_instances=2,
                                max_batch=4, max_seq=64, dwell_steps=4)
        rng = np.random.default_rng(0)
        mk = lambda rid, n, new: ServeRequest(
            rid=rid, prompt=rng.integers(0, cfg.vocab_size,
                                         size=n).tolist(),
            max_new_tokens=new)
        # one queued short per engine (no step yet, so both sit in
        # engine queues — the second lands on the future donor)
        shorts = [mk(0, 6, 8), mk(1, 6, 8)]
        for r in shorts:
            cluster.submit(r)
        assert sum(len(e.waiting) for e in cluster.engines) == 2
        # inject the merge trigger into the ROUTER queue directly, so
        # the merge is decided by step()'s retry loop, not submit()
        long_r = mk(9, 80, 16)
        cluster.requests.append(long_r)
        cluster.waiting.append(long_r)
        cluster.step()
        merges = [a for a in cluster.actions
                  if isinstance(a, ScaleUp) and a.donor_iids]
        assert merges, cluster.actions
        # nothing dropped, nothing duplicated
        queued = ([r.rid for e in cluster.engines for r in e.waiting]
                  + [r.rid for e in cluster.engines for r in e.slots
                     if r is not None]
                  + [r.rid for r in cluster.waiting])
        assert sorted(queued) == [0, 1, 9], queued
        cluster.run(max_steps=5000)
        for r in shorts + [long_r]:
            assert r.finished and len(r.generated) == r.max_new_tokens, (
                r.rid, len(r.generated))
        print("RETRY_MERGE_OK")
    """)
    assert "RETRY_MERGE_OK" in out


def test_merge_with_fused_chunk_kernel_mid_session():
    """TP1x4 -> TP4 merge with the fused chunk-prefill kernel on (the
    TPU configuration; interpret mode here): a short request finishes
    mid-session, so the long prompt's chunks run the per-layer session
    path, the kernel under ``shard_map`` on migrated layers and alone on
    the rest.  Streams equal an engine started at TP4, and the per-layer
    path compiles each layer program once, not once per call."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.scheduler import PrefillPolicy, ScaleDown, ScaleUp
        from repro.models import model as M
        from repro.serving.cluster import ClusterEngine
        from repro.serving.engine import Engine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("gemma-2b").reduced(),
                                  dtype="float32", num_layers=6)
        devs = jax.devices()[:4]
        Q, PAGE, CHUNK = 64, 8, 16
        policy = PrefillPolicy(token_budget=CHUNK, mode="mixed",
                               long_threshold=Q, order="sjf")
        cluster = ClusterEngine(cfg, devs, n_instances=4, max_batch=4,
                                max_seq=Q, page_tokens=PAGE,
                                prefill_policy=policy,
                                pallas_kernels=True)
        target_chunks = []
        for e in cluster.engines:
            run_layers = e._run_chunk_layers
            def counted(*a, _run=run_layers, _e=e):
                target_chunks.append(_e.iid)
                return _run(*a)
            e._run_chunk_layers = counted
        rng = np.random.default_rng(0)
        mk = lambda rid, n, new: ServeRequest(
            rid=rid, prompt=rng.integers(0, cfg.vocab_size,
                                         size=n).tolist(),
            max_new_tokens=new)
        # request 0 finishes inside the session and frees the slot the
        # long prompt needs; the others decode across its end
        shorts = [mk(0, 8, 4), mk(1, 8, 16), mk(2, 8, 16), mk(3, 8, 16)]
        long_r = mk(9, 10 * CHUNK, 8)       # 160 > the TP1 ceiling (64)
        for r in shorts:
            cluster.submit(r)
        for _ in range(2):
            cluster.step()
        cluster.submit(long_r)
        assert [a for a in cluster.actions
                if isinstance(a, ScaleUp) and a.donor_iids], cluster.actions
        n_compiles = M._block_chunk._cache_size()
        cluster.run(max_steps=2000)
        assert all(r.finished for r in shorts + [long_r])
        assert [a for a in cluster.actions if isinstance(a, ScaleDown)]
        assert cluster.stall_steps == 0, cluster.stall_steps
        assert len(target_chunks) >= 2, target_chunks
        # a few programs per assembly, not one per layer call
        compiled = M._block_chunk._cache_size() - n_compiles
        assert compiled <= 8 < len(target_chunks) * cfg.num_layers, (
            compiled, len(target_chunks))

        ref = Engine(cfg, params=cluster._params_src, max_batch=4,
                     max_seq=4 * Q, page_tokens=PAGE, devices=devs,
                     plan=cluster.plan, prefill_policy=policy,
                     pallas_kernels=True)
        ref.transform(4)
        while ref.transforming:
            ref.step()
        for got in shorts + [long_r]:
            want = ServeRequest(rid=got.rid, prompt=list(got.prompt),
                                max_new_tokens=got.max_new_tokens)
            ref.submit(want)
            ref.run_until_done(2000)
            assert want.generated == got.generated, (
                got.rid, want.generated, got.generated)
        print("KERNEL_MERGE_OK", len(target_chunks))
    """)
    assert "KERNEL_MERGE_OK" in out


# ---------------------------------------------------------------------------
# Fast (single-device) coverage: merge policy + cross-pool data plane
# ---------------------------------------------------------------------------

def _stub(iid, tp=1, width=1, base=16, used=0.0, max_tp=None):
    class V:
        pass

    v = V()
    v.iid, v.tp, v.width = iid, tp, width
    v.reserved = False
    v.max_tp = tp if max_tp is None else max_tp
    v.kv_used_fraction = lambda: used
    v.load = lambda: used
    v.max_seq = lambda: base * tp
    v.max_seq_at = lambda t: base * t
    v.kv_free_tokens = lambda: int(base * tp * (1 - used))
    v.has_long_request = lambda: False
    return v


def test_decide_merge_composes_idle_donors():
    from repro.core.scheduler import GygesScheduler, SchedulerConfig

    sched = GygesScheduler(SchedulerConfig(long_threshold=16, target_tp=4))
    busy = _stub(0, width=4, used=0.5)
    idle = _stub(1, width=4, used=0.1)
    # needs width 6 -> both 4-wide engines; busiest member is the
    # target (fewest live-KV exports), idlest the donor
    act = sched.decide_merge([busy, idle], 96)
    assert act is not None and act.donor_iids == (1,)
    assert act.iid == 0 and act.tp_to == 8
    # fits one engine alone -> still a merge of >= 2 members by contract
    # but never fewer than two members
    assert sched.decide_merge([busy], 96) is None
    # beyond the whole pool -> None
    assert sched.decide_merge([busy, idle], 1000) is None
    # TP>1 instances are not merge members
    assert sched.decide_merge([_stub(0, tp=4, width=4),
                               _stub(1, tp=4, width=4)], 96) is None
    # only pool-divisor widths are executable: a width-6 fit on an
    # 8-wide pool keeps accumulating to 8 instead
    four = [_stub(i, width=2, used=0.1 * i) for i in range(4)]
    act = sched.decide_merge(four, 90)
    assert act is not None and act.tp_to == 8
    assert len(act.donor_iids) == 3


def test_decide_scale_up_prefers_in_place_then_merges():
    from repro.core.scheduler import GygesScheduler, SchedulerConfig

    sched = GygesScheduler(SchedulerConfig(long_threshold=16, target_tp=4))
    a = _stub(0, width=4, max_tp=4, used=0.2)
    b = _stub(1, width=4, max_tp=4, used=0.1)
    # total 48 fits in place at TP4 (4*16=64): no donors
    act = sched.decide_scale_up([a, b], 40, 8)
    assert act.donor_iids == () and act.tp_to <= 4
    # total 96 exceeds any single engine: merge
    act = sched.decide_scale_up([a, b], 80, 16)
    assert act.donor_iids and act.tp_to == 8
    # shorts never transform
    assert sched.decide_scale_up([a, b], 4, 4) is None


def test_sim_merge_width_follows_need():
    """The sim consumes the same decide_merge: a request needing more
    than target_tp GPUs merges wider than target_tp."""
    from repro.core.costmodel import CostModel, H20
    from repro.core.cluster_sim import Cluster
    from repro.core.scheduler import GygesScheduler
    from repro.configs import get_config
    from repro.serving.request import Request

    cfg = get_config("qwen2.5-32b")
    c = Cluster(cfg, n_hosts=1, scheduler=GygesScheduler())
    cm = CostModel(cfg, H20)
    # size the request to need strictly more than target_tp=4 GPUs
    need5 = cm.max_seq(4) + 1
    if cm.max_seq(8) > need5 + 100:
        c.submit(Request(0, 0.0, need5, 100), 0.0)
        assert c.n_transforms == 1
        merged = [i for i in c.instances if i.tp > 1]
        assert len(merged) == 1 and merged[0].tp > 4
        assert sum(i.tp for i in c.instances) == 8


def test_resize_slot_capacity_roundtrip():
    """Grow preserves every slot's pages at its in-slot index; shrink
    restores the original pool exactly."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.kv_transform import resize_slot_capacity
    from repro.paged.pool import PagedState, make_state

    B, mps, kvs, P, dh = 3, 2, 4, 4, 8
    st = make_state(B * mps, kvs, P, dh, B, mps, dtype=jnp.float32)
    pool = jnp.arange(st.pool.size, dtype=jnp.float32).reshape(
        st.pool.shape)
    st = PagedState(pool, st.page_table, st.seq_lens + 5,
                    st.positions.at[:, 0].set(0))
    big = resize_slot_capacity(st, 5, B)
    assert big.pool.shape[0] == B * 5
    assert big.page_table.shape == (B, 5)
    assert big.positions.shape == (B, 5 * P)
    for b in range(B):
        np.testing.assert_array_equal(big.pool[b * 5:b * 5 + mps],
                                      pool[b * mps:(b + 1) * mps])
        assert (np.asarray(big.pool[b * 5 + mps:(b + 1) * 5]) == 0).all()
        np.testing.assert_array_equal(
            big.positions[b, :mps * P], st.positions[b])
        assert (np.asarray(big.positions[b, mps * P:]) == -1).all()
    back = resize_slot_capacity(big, mps, B)
    np.testing.assert_array_equal(back.pool, pool)
    np.testing.assert_array_equal(back.page_table, st.page_table)
    np.testing.assert_array_equal(back.positions, st.positions)
    np.testing.assert_array_equal(back.seq_lens, st.seq_lens)


def test_resize_slot_capacity_stacked_leading_dim():
    import jax.numpy as jnp
    import numpy as np
    from repro.core.kv_transform import resize_slot_capacity
    from repro.paged.pool import PagedState

    G, B, mps, kvs, P, dh = 2, 2, 2, 2, 4, 4
    pool = jnp.arange(G * B * mps * kvs * 2 * P * dh,
                      dtype=jnp.float32).reshape(G, B * mps, kvs, 2, P, dh)
    pt = jnp.broadcast_to(
        (jnp.arange(B)[:, None] * mps + jnp.arange(mps)).astype(jnp.int32),
        (G, B, mps))
    st = PagedState(pool, pt, jnp.zeros((G, B), jnp.int32),
                    jnp.full((G, B, mps * P), -1, jnp.int32))
    big = resize_slot_capacity(st, 3, B)
    assert big.pool.shape == (G, B * 3, kvs, 2, P, dh)
    for g in range(G):
        for b in range(B):
            np.testing.assert_array_equal(
                big.pool[g, b * 3:b * 3 + mps],
                pool[g, b * mps:(b + 1) * mps])


def test_migrate_slot_pages_kernel_matches_fallback():
    """The §4.1 kernel scatter and the dynamic-slice fallback write the
    same bytes; non-named destination pages are untouched."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.kv_transform import migrate_slot_pages

    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.normal(size=(2, 4, 2, 4, 8)), jnp.float32)
    dst = jnp.asarray(rng.normal(size=(12, 4, 2, 4, 8)), jnp.float32)
    got = migrate_slot_pages(src, dst, 2, 6)
    want = np.asarray(dst).copy()
    want[6:8] = np.asarray(src)
    np.testing.assert_array_equal(np.asarray(got), want)
    # stacked leading dim takes the vmapped kernel
    srcg = jnp.stack([src, src * 2])
    dstg = jnp.stack([dst, dst * 3])
    got = migrate_slot_pages(srcg, dstg, 2, 0)
    np.testing.assert_array_equal(np.asarray(got[1][:2]),
                                  np.asarray(srcg[1][:2]))
    np.testing.assert_array_equal(np.asarray(got[1][2:]),
                                  np.asarray(dstg[1][2:]))
    # incompatible page geometry is rejected, not silently mangled
    src3 = jnp.asarray(rng.normal(size=(2, 3, 2, 4, 8)), jnp.float32)
    with np.testing.assert_raises(Exception):
        migrate_slot_pages(src3, dst, 2, 0).block_until_ready()


# ---- partial-merge / spill negative paths (ISSUE-8 satellites) --------


def test_sim_spill_grant_failure_falls_back_to_partial_merge():
    """When every spill grant fails (stale scheduler view: the chosen
    host ran out of free pages), the simulated ladder falls one rung
    down to a partial merge instead of crashing or dropping the
    request."""
    from repro.configs import get_config
    from repro.core.cluster_sim import Cluster
    from repro.core.scheduler import (GygesScheduler, PrefillPolicy,
                                      ScaleUp, SchedulerConfig, Spill)
    from repro.serving.request import Request

    cfg = get_config("llama3-8b").reduced()
    Q = 16
    policy = PrefillPolicy(token_budget=16, mode="mixed",
                           long_threshold=Q, order="sjf")
    sched = GygesScheduler(SchedulerConfig(
        long_threshold=Q, target_tp=4, spill=True, partial_merge=True,
        spill_slack=2.0))
    sim = Cluster(cfg, n_hosts=1, gpus_per_host=8, scheduler=sched,
                  target_tp=4, prefill_policy=policy, seq_quantum=Q,
                  max_batch=2, widths=[2, 2, 2, 2], page_tokens=Q)
    sim._execute_spill = lambda act, req, now: False   # host never grants
    now, dt = 0.0, 0.25
    req = Request(9, now, 24, 16)          # total 40: the spill range
    sim.submit(req, now)
    for _ in range(20000):
        sim.advance(now, dt)
        now += dt
        if req.tokens_done >= req.out_len \
                and all(i.tp == 1 for i in sim.instances):
            break
    else:
        raise RuntimeError("sim did not drain the spilled-over request")
    assert not any(isinstance(a, Spill) for a in sim.actions), sim.actions
    partials = [a for a in sim.actions
                if isinstance(a, ScaleUp) and a.donor_devices]
    assert partials, sim.actions
    m = sim.metrics(now)
    assert m["spill_pages"] == 0
    assert m["partial_merges"] >= 1
    sim.partition.check_invariants()
    assert all(i.width == 2 for i in sim.instances)


@pytest.mark.slow
def test_partial_merge_donor_serves_mid_chunked_prefill():
    """ISSUE-8 negative path: a donor that is MID-chunked-prefill when a
    partial merge shears off one of its devices keeps advancing — its
    in-flight request survives the same-degree shrink, finishes with a
    stream bit-identical to a reference engine, nobody parks, and the
    scale-down widens every donor back to its home width."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.padding import make_plan
        from repro.core.scheduler import (GygesScheduler, PrefillPolicy,
                                          ScaleUp, SchedulerConfig)
        from repro.models import model as M
        from repro.serving.cluster import ClusterEngine
        from repro.serving.engine import Engine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()
        plan = make_plan(cfg, len(devs), mode="page")
        host_params = M.init_params(jax.random.PRNGKey(11), cfg, plan)
        Q = 16
        # chunk boundaries are page boundaries: 4-token pages + a
        # 4-token budget force every 12-token prompt through 3 chunks,
        # so a prefill is reliably mid-flight when the merge fires
        policy = PrefillPolicy(token_budget=4, mode="mixed",
                               long_threshold=Q, order="sjf")
        sched = GygesScheduler(SchedulerConfig(
            long_threshold=Q, target_tp=4, partial_merge=True))
        cluster = ClusterEngine(cfg, devs[:8], n_instances=4,
                                max_batch=2, max_seq=2 * Q,
                                page_tokens=4, dwell_steps=4,
                                params=host_params, scheduler=sched,
                                prefill_policy=policy)
        for e in cluster.engines:
            e.transform(1)
        cluster.run(max_steps=4000)
        assert not cluster.actions

        rng = np.random.default_rng(0)
        prompts = {rid: rng.integers(0, cfg.vocab_size,
                                     size=n).tolist()
                   for rid, n in [(0, 12), (1, 12), (2, 12), (3, 12),
                                  (9, 40)]}
        shorts = [ServeRequest(rid=r, prompt=list(prompts[r]),
                               max_new_tokens=4) for r in range(4)]
        for r in shorts:
            cluster.submit(r)
        # one short per engine, so every merge donor holds live work
        per_engine = [len(e.waiting) + sum(s is not None
                                           for s in e.slots)
                      for e in cluster.engines]
        assert per_engine == [1, 1, 1, 1], per_engine
        cluster.step()
        # every engine is mid-chunk: some but not all prompt tokens
        # prefilled ("done" counts completed tokens)
        assert all(e._prefilling and all(
                       0 < st["done"] < len(st["req"].prompt)
                       for st in e._prefilling.values())
                   for e in cluster.engines), (
            [[(k, st["done"]) for k, st in e._prefilling.items()]
             for e in cluster.engines])

        long_r = ServeRequest(rid=9, prompt=list(prompts[9]),
                              max_new_tokens=16)      # total 56
        cluster.submit(long_r)
        partials = [a for a in cluster.actions
                    if isinstance(a, ScaleUp) and a.donor_devices]
        assert partials, cluster.actions
        act = partials[0]
        donors = [cluster._engine(i) for i in act.donor_iids]
        # the shrink already landed (same-degree re-shard, 0 steps):
        # each donor kept serving width, kept its slot, never parked
        for d, n in zip(donors, act.donor_devices):
            assert not d.parked and d.W == 2 - n and d.tp == 1, (
                d.iid, d.W, d.tp)
            assert any(s is not None for s in d.slots), d.iid
        before = {}
        for d in donors:
            slot = min(d._prefilling)
            before[d.iid] = (slot, d._prefilling[slot]["ci"],
                             len(shorts[d.iid].generated))
        for _ in range(4):
            cluster.step()
        for d in donors:
            slot, ci0, g0 = before[d.iid]
            st = d._prefilling.get(slot)
            advanced = (shorts[d.iid].finished
                        or len(shorts[d.iid].generated) > g0
                        or (st is not None and st["ci"] > ci0))
            assert advanced, (d.iid, before[d.iid],
                              shorts[d.iid].generated)

        cluster.run(max_steps=8000)
        assert all(r.finished for r in shorts) and long_r.finished
        assert cluster.stall_steps == 0, cluster.stall_steps
        assert all(not e.parked and e.tp == 1 and e.W == 2
                   for e in cluster.engines), (
            [(e.iid, e.W, e.tp, e.parked) for e in cluster.engines])
        assert not cluster.partition._loans
        cluster.partition.check_invariants()
        assert cluster.metrics()["partial_merges"] >= 1

        # bit-exact streams vs each request alone on a static engine
        ref = Engine(cfg, params=host_params, max_batch=8, max_seq=64,
                     devices=devs, plan=plan)
        for got in shorts + [long_r]:
            want = ServeRequest(rid=100 + got.rid,
                                prompt=list(prompts[got.rid]),
                                max_new_tokens=got.max_new_tokens)
            ref.submit(want)
            ref.run_until_done(2000)
            assert want.generated == got.generated, (
                got.rid, want.generated, got.generated)
        print("PARTIAL_DONOR_OK")
    """)
    assert "PARTIAL_DONOR_OK" in out


@pytest.mark.slow
def test_live_spill_grant_failure_falls_back_to_partial_merge():
    """ISSUE-8 negative path, live plane: the scheduler decides a spill
    from a (stale) view that shows free host pages, but the host's
    grant fails at execution time — the placement falls down the ladder
    to a partial merge and the request is served, not dropped."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.scheduler import (GygesScheduler, PrefillPolicy,
                                          ScaleUp, SchedulerConfig,
                                          Spill)
        from repro.serving.cluster import ClusterEngine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()
        Q = 16
        policy = PrefillPolicy(token_budget=16, mode="mixed",
                               long_threshold=Q, order="sjf")
        sched = GygesScheduler(SchedulerConfig(
            long_threshold=Q, target_tp=4, spill=True,
            partial_merge=True, spill_slack=2.0))
        cluster = ClusterEngine(cfg, devs[:8], n_instances=4,
                                max_batch=2, max_seq=2 * Q,
                                page_tokens=Q, dwell_steps=4,
                                scheduler=sched, prefill_policy=policy)
        for e in cluster.engines:
            e.transform(1)
        cluster.run(max_steps=4000)
        assert not cluster.actions

        # every would-be host is out of free pages at grant time
        for e in cluster.engines:
            e.host_spilled = lambda n_pages: None

        rng = np.random.default_rng(0)
        long_r = ServeRequest(
            rid=9, prompt=rng.integers(0, cfg.vocab_size,
                                       size=24).tolist(),
            max_new_tokens=16)             # total 40: the spill range
        cluster.submit(long_r)
        assert not any(isinstance(a, Spill) for a in cluster.actions), (
            cluster.actions)
        partials = [a for a in cluster.actions
                    if isinstance(a, ScaleUp) and a.donor_devices]
        assert partials, cluster.actions
        assert not cluster.partition.spills()

        cluster.run(max_steps=8000)
        assert long_r.finished and len(long_r.generated) == 16
        m = cluster.metrics()
        assert m["spill_pages"] == 0 and m["partial_merges"] >= 1, m
        assert all(not e.parked and e.W == 2 for e in cluster.engines)
        cluster.partition.check_invariants()
        print("SPILL_FALLBACK_OK")
    """)
    assert "SPILL_FALLBACK_OK" in out
