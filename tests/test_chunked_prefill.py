"""Chunked prefill: the PrefillPolicy-driven incremental prefill path.

Fast (single-device) coverage: the pool-level chunk writer is
bit-identical to the whole-prompt writer; the model-level chunk
continuation reproduces whole-prompt prefill (allclose + identical
greedy streams — reduction shapes differ across chunkings, so exact
float equality is a per-shape property, see blocks.attention_chunk);
the engine's chunked prefill emits the same token streams as the
whole-prompt engine, with and without concurrent decodes; queue-delay
metrics are stamped.

Slow (8 fake devices, subprocess) coverage: a transform session started
MID-chunked-prefill completes with the partially-prefilled slot's KV
bit-identical to a reference engine at the target TP running the same
chunk plan (the data plane only moves bytes); in-place ScaleUp /
ScaleDown now resize the physical pool so memory follows the TP degree
(the former merge-only ROADMAP item); and a mid-prefill engine is a
valid merge DONOR — its chunk progress exports/imports and the prefill
resumes on the merged target.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
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


def _cfg():
    from repro.configs import get_config
    return dataclasses.replace(get_config("llama3-8b").reduced(),
                               dtype="float32")


# ---------------------------------------------------------------------------
# Fast: pool layer
# ---------------------------------------------------------------------------

def test_write_chunk_composes_to_write_prefill():
    """Writing a prompt in page-aligned chunks produces the bit-identical
    PagedState that one whole-prompt write_prefill produces (pool bytes,
    positions, seq_lens) — pure data movement, no arithmetic."""
    import jax.numpy as jnp
    from repro.paged import pool as pp

    B, mps, kvs, P, dh, S = 2, 8, 4, 8, 16, 40
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, kvs, dh)), jnp.float32)

    st0 = pp.make_state(B * mps, kvs, P, dh, B, mps, dtype=jnp.float32)
    whole = pp.write_prefill(st0, k, v)

    st = pp.make_state(B * mps, kvs, P, dh, B, mps, dtype=jnp.float32)
    off = 0
    for size in (16, 16, 8):       # page-aligned boundaries, partial tail
        pos = off + jnp.arange(size, dtype=jnp.int32)[None, :]
        pos = jnp.broadcast_to(pos, (B, size))
        st = pp.write_chunk(st, k[:, off:off + size], v[:, off:off + size],
                            pos)
        assert int(st.seq_lens[0]) == off + size
        off += size

    np.testing.assert_array_equal(np.asarray(whole.pool),
                                  np.asarray(st.pool))
    np.testing.assert_array_equal(np.asarray(whole.positions),
                                  np.asarray(st.positions))
    np.testing.assert_array_equal(np.asarray(whole.seq_lens),
                                  np.asarray(st.seq_lens))


# ---------------------------------------------------------------------------
# Fast: model layer
# ---------------------------------------------------------------------------

def test_prefill_chunk_reproduces_whole_prefill():
    """Composed prefill_chunk calls == one prefill call: caches and
    last-token logits agree to reduction-order tolerance, and the greedy
    next token (the stream-visible quantity) is identical."""
    import jax
    import jax.numpy as jnp
    from repro.core.padding import make_plan
    from repro.models import model as M

    cfg = _cfg()
    plan = make_plan(cfg, 1)
    params = M.init_params(jax.random.PRNGKey(0), cfg, plan)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 40)),
                       jnp.int32)

    caches = M.init_decode_caches(cfg, plan, 1, 64, 8)
    logits_w, cw = M.prefill(params, cfg, plan, {"tokens": toks}, caches)

    cc = M.init_decode_caches(cfg, plan, 1, 64, 8)
    off = 0
    for size in (16, 16, 8):
        logits_c, cc = M.prefill_chunk(
            params, cfg, plan, toks[:, off:off + size],
            jnp.full((1,), off, jnp.int32), cc)
        off += size

    np.testing.assert_allclose(np.asarray(logits_w), np.asarray(logits_c),
                               rtol=1e-4, atol=1e-4)
    assert int(jnp.argmax(logits_w[0, -1])) == int(
        jnp.argmax(logits_c[0, -1]))
    for lw, lc in zip(jax.tree.leaves(cw), jax.tree.leaves(cc)):
        np.testing.assert_allclose(np.asarray(lw), np.asarray(lc),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Fast: engine layer
# ---------------------------------------------------------------------------

def _mk_engine(policy=None, max_batch=3):
    from repro.serving.engine import Engine
    return Engine(_cfg(), max_batch=max_batch, max_seq=64, page_tokens=8,
                  prefill_policy=policy)


def test_engine_chunked_stream_matches_whole_prompt():
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.request import ServeRequest

    cfg = _cfg()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()

    def run(policy):
        eng = _mk_engine(policy)
        r = ServeRequest(rid=1, prompt=list(prompt), max_new_tokens=8)
        eng.submit(r)
        eng.run_until_done(500)
        assert r.t_prefill_start is not None and r.queue_delay >= 0
        return r.generated

    whole = run(None)
    for mode in ("prefill", "decode", "mixed"):
        from repro.core.scheduler import PrefillPolicy as PP
        assert run(PP(token_budget=16, mode=mode, long_threshold=32,
                      order="sjf")) == whole, mode
    # chunking engages: the plan really was multi-chunk
    pol = PrefillPolicy(token_budget=16, long_threshold=32)
    assert len(pol.chunk_sizes(len(prompt), 8)) == 3


def test_engine_chunked_concurrent_decodes_match_reference():
    """The tentpole scenario on one device: a long prompt prefills in
    chunks under decode priority while a background request decodes and
    a short slips between the long's chunks — every stream equals the
    whole-prompt reference engine's."""
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.request import ServeRequest

    cfg = _cfg()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()
    pol = PrefillPolicy(token_budget=16, mode="decode", long_threshold=32,
                        max_defer_steps=2, order="sjf")
    eng = _mk_engine(pol)
    bg = ServeRequest(rid=0, prompt=prompt[:4], max_new_tokens=20)
    eng.submit(bg)
    eng.step()
    eng.step()
    long_r = ServeRequest(rid=1, prompt=list(prompt), max_new_tokens=4)
    eng.submit(long_r)
    eng.step()
    short = ServeRequest(rid=2, prompt=prompt[:6], max_new_tokens=4)
    eng.submit(short)
    # the long prompt must really be mid-prefill while others progress
    assert any(p["req"].rid == 1 and 0 <= p["done"] < 40
               for p in eng._prefilling.values())
    eng.run_until_done(500)

    ref = _mk_engine(None)
    for spec, got in [((10, prompt[:4], 20), bg),
                      ((11, list(prompt), 4), long_r),
                      ((12, prompt[:6], 4), short)]:
        want = ServeRequest(rid=spec[0], prompt=list(spec[1]),
                            max_new_tokens=spec[2])
        ref.submit(want)
        ref.run_until_done(500)
        assert want.generated == got.generated, (
            got.rid, want.generated, got.generated)


def test_engine_chunked_stream_matches_whole_prompt_ring_cache():
    """ISSUE-7 satellite: sliding-window (ring-cache) models take the
    chunked path too.  Chunks are split at the smallest ring capacity
    (`Engine._min_chunk_cap`) so no chunk can wrap past live window
    keys, and the decode-filler cursor only ever evicts keys already
    out-of-window — the chunked streams equal whole-prompt prefill even
    when the prompt is 2.5x the window."""
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.engine import Engine
    from repro.serving.request import ServeRequest

    cfg = dataclasses.replace(_cfg(), attention="sliding", window=16)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()

    def run(policy):
        eng = Engine(cfg, max_batch=3, max_seq=64, page_tokens=8,
                     prefill_policy=policy)
        assert eng._can_chunk, "ring caches must not opt out of chunking"
        if policy is not None:
            # the ring cap really is the binding constraint here
            assert eng._min_chunk_cap() == 16
        r = ServeRequest(rid=1, prompt=list(prompt), max_new_tokens=8)
        eng.submit(r)
        eng.run_until_done(500)
        return r.generated

    whole = run(None)
    for budget in (16, 24):          # 24 forces the ring-cap re-split
        pol = PrefillPolicy(token_budget=budget, mode="mixed",
                            long_threshold=32, order="sjf")
        assert run(pol) == whole, budget


def test_partial_slot_is_page_aligned_during_prefill():
    """The mid-prefill invariant the data plane relies on: after every
    chunk but the last, the slot's written prefix is a whole number of
    pages (chunk boundary == page boundary)."""
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.request import ServeRequest

    cfg = _cfg()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()
    pol = PrefillPolicy(token_budget=16, mode="prefill", long_threshold=32)
    eng = _mk_engine(pol)
    r = ServeRequest(rid=1, prompt=prompt, max_new_tokens=2)
    eng.submit(r)
    seen_partial = False
    for _ in range(200):
        if r.t_first_token is not None:
            break
        for prog in eng._prefilling.values():
            if 0 < prog["done"] < len(prompt):
                assert prog["done"] % eng.page_tokens == 0, prog["done"]
                seen_partial = True
        eng.step()
    assert seen_partial and r.t_first_token is not None


def test_starved_prefill_slot_survives_filler_wraparound():
    """Regression: decode iterations append masked filler into a mid-
    prefill slot at its seq_lens cursor; without re-pinning the cursor
    (`_pin_prefill_cursors`) a slot starved of chunk budget for more
    than `capacity - done` steps would ring-wrap the filler INTO its
    prefilled prefix.  SJF + a stream of short prompts that consume the
    whole budget every step is exactly that starvation."""
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.engine import Engine
    from repro.serving.request import ServeRequest

    cfg = _cfg()
    rng = np.random.default_rng(2)
    long_prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()
    pol = PrefillPolicy(token_budget=16, mode="prefill",
                        long_threshold=16, order="sjf")
    eng = Engine(cfg, max_batch=4, max_seq=48, page_tokens=8,
                 prefill_policy=pol)
    long_r = ServeRequest(rid=99, prompt=list(long_prompt),
                          max_new_tokens=4)
    eng.submit(long_r)
    eng.step()                       # chunk 1: done = 16
    assert next(iter(eng._prefilling.values()))["done"] == 16
    # 40 shorts, one per step: each one's 14-token prefill (remaining <
    # the long's 24) wins the SJF budget, starving the long past the
    # 48 - 16 = 32 filler steps a wraparound needs
    shorts = []
    for i in range(40):
        s = ServeRequest(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=14).tolist(), max_new_tokens=2)
        shorts.append(s)
        eng.submit(s)
        eng.step()
        if long_r.t_first_token is None:
            prog = next(p for p in eng._prefilling.values()
                        if p["req"].rid == 99)
            assert prog["done"] == 16
    eng.run_until_done(1000)

    ref = Engine(cfg, max_batch=4, max_seq=48, page_tokens=8)
    for got in [long_r] + shorts:
        want = ServeRequest(rid=got.rid, prompt=list(got.prompt),
                            max_new_tokens=got.max_new_tokens)
        ref.submit(want)
        ref.run_until_done(1000)
        assert want.generated == got.generated, (
            got.rid, want.generated, got.generated)


def test_chunk_path_jit_cache_hits_after_warmup():
    """ISSUE-5 satellite: the chunked-prefill hot path is jitted with a
    per-(batch, chunk_len) compile cache — after the first request warms
    the chunk shapes, later requests with the same chunk plan HIT the
    cache instead of retracing.  The recorder's ``compiles`` on each
    ``engine.chunk`` span (its own and its children's) say which chunk
    compiled."""
    from repro.core.scheduler import PrefillPolicy
    from repro.serving import tracing
    from repro.serving.request import ServeRequest

    cfg = _cfg()
    rng = np.random.default_rng(3)
    pol = PrefillPolicy(token_budget=16, mode="prefill", long_threshold=32)
    eng = _mk_engine(pol)
    mk = lambda rid: ServeRequest(rid=rid, prompt=rng.integers(
        0, cfg.vocab_size, size=56).tolist(), max_new_tokens=2)

    def chunk_compiles(rid):
        return [n for s, n in tracing.rolled_up(
            tracing.RECORDER.spans(), "engine.chunk", "compiles")
            if s.attrs["rid"] == rid]

    tracing.RECORDER.clear()
    eng.submit(mk(0))
    eng.run_until_done(500)
    first = chunk_compiles(0)
    assert len(first) == 4                     # the [16, 16, 16, 8] plan
    # the 1st chunk compiles the static first-chunk variant, the 2nd
    # the continuation variant; the 3rd 16-token chunk hits
    assert first[0] > 0 and first[1] > 0
    assert first[2] == 0
    eng.submit(mk(1))
    eng.run_until_done(500)
    # the second request's chunks are all warm shapes: no new traces
    assert chunk_compiles(1) == [0, 0, 0, 0]


def test_queue_delay_in_metrics_schema():
    from repro.serving.metrics import METRIC_KEYS, summarize
    from repro.serving.request import ServeRequest

    assert "queue_delay_p50" in METRIC_KEYS
    assert "queue_delay_p99" in METRIC_KEYS
    r = ServeRequest(rid=0, prompt=[1, 2], max_new_tokens=1)
    r.t_prefill_start = r.t_submit + 0.5
    r.t_first_token = r.t_submit + 1.0
    r.t_done = r.t_submit + 1.0
    m = summarize([r], 2.0, 3, 0)
    assert list(m) == list(METRIC_KEYS)
    assert abs(m["queue_delay_p50"] - 0.5) < 1e-9
    assert m["queue_delay_p50"] <= m["ttft_p50"]


# ---------------------------------------------------------------------------
# Slow: transform / merge sessions mid-chunked-prefill (8 fake devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_transform_mid_chunked_prefill_bit_exact():
    """ISSUE-4 satellite: a live transform session started while a
    chunked prefill is in flight completes with the slot's KV
    bit-identical to a reference engine AT the target TP running the
    same chunk plan, and the finished stream equals the unchunked
    whole-prompt reference.  Also the in-place pool-resize regression:
    max_seq_alloc == seq_quantum * tp after every transform."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.padding import make_plan
        from repro.core.scheduler import PrefillPolicy
        from repro.models import model as M
        from repro.serving.engine import Engine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()[:4]
        plan = make_plan(cfg, len(devs), mode="page")
        params = M.init_params(jax.random.PRNGKey(11), cfg, plan)
        pol = PrefillPolicy(token_budget=16, mode="prefill",
                            long_threshold=16, order="fcfs")
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()

        eng = Engine(cfg, params=params, max_batch=4, max_seq=64,
                     page_tokens=16, devices=devs, plan=plan,
                     prefill_policy=pol)
        r = ServeRequest(rid=1, prompt=list(prompt), max_new_tokens=6)
        eng.submit(r)
        eng.step()                      # chunk 1 of [16, 16, 8]
        prog = next(iter(eng._prefilling.values()))
        assert prog["done"] == 16, prog["done"]
        n = eng.transform(4)            # session opens MID-prefill
        assert n > 0 and eng.transforming
        # zero-stall contract: chunked prefill keeps ADVANCING through
        # the session via the per-layer path (the partial prefix still
        # rides the ordinary KV migration under it)
        advanced_mid_session = False
        while eng.transforming:
            eng.step()
            if eng.transforming:
                dones = [p["done"] for p in eng._prefilling.values()]
                if not dones or dones[0] > 16:
                    advanced_mid_session = True
        assert advanced_mid_session, "chunked prefill paused mid-session"
        # in-place resize regression (ROADMAP item): memory follows tp
        assert eng.tp == 4
        assert eng.max_seq_alloc == eng.seq_quantum * 4, eng.max_seq_alloc
        eng.check_capacity_invariant()
        # prefill resumes on the new degree and drains
        eng.run_until_done(1000)

        # reference AT the target TP, same chunk plan: transform first
        # (empty), then the same chunked prefill -> chunk shapes match
        # and the data plane only moves bytes, so KV is bit-identical
        ref = Engine(cfg, params=params, max_batch=4, max_seq=64,
                     page_tokens=16, devices=devs, plan=plan,
                     prefill_policy=pol)
        ref.transform(4)
        while ref.transforming:
            ref.step()
        r2 = ServeRequest(rid=1, prompt=list(prompt), max_new_tokens=6)
        ref.submit(r2)
        # advance the reference to the SAME prefill progress and diff
        # the partially-prefilled slot byte-for-byte
        ref.step()
        assert next(iter(ref._prefilling.values()))["done"] == 16
        # (the transformed engine already finished; compare final slots
        # after the reference also drains)
        ref.run_until_done(1000)
        assert r2.generated == r.generated, (r2.generated, r.generated)

        # and the stream equals the unchunked whole-prompt reference
        whole = Engine(cfg, params=params, max_batch=4, max_seq=64,
                       page_tokens=16, devices=devs, plan=plan)
        r3 = ServeRequest(rid=1, prompt=list(prompt), max_new_tokens=6)
        whole.submit(r3)
        whole.run_until_done(1000)
        assert r3.generated == r.generated, (r3.generated, r.generated)
        print("MIDPREFILL_TRANSFORM_OK")
    """)
    assert "MIDPREFILL_TRANSFORM_OK" in out


@pytest.mark.slow
def test_inplace_transforms_resize_pool_and_serve():
    """Regression for the ROADMAP 'physical pool scaling for in-place
    transforms' item: every in-place ScaleUp/ScaleDown applies
    resize_slot_capacity, max_seq_alloc == seq_quantum * tp after every
    transform (not just merges), live KV survives grow AND trim, and the
    capacity invariant holds at each lifecycle point."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.padding import make_plan
        from repro.models import model as M
        from repro.serving.engine import Engine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()[:4]
        plan = make_plan(cfg, len(devs), mode="page")
        params = M.init_params(jax.random.PRNGKey(3), cfg, plan)
        eng = Engine(cfg, params=params, max_batch=4, max_seq=64,
                     page_tokens=16, devices=devs, plan=plan)
        q = eng.seq_quantum
        assert eng.max_seq_alloc == q * eng.W    # construction allocation
        rng = np.random.default_rng(0)
        # total footprint 14 <= the TP1 ceiling (16): every degree in
        # the cycle below can legally hold it, so the trimmed pool is
        # exactly seq_quantum * tp after each transform
        r = ServeRequest(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, size=6).tolist(), max_new_tokens=8)
        eng.submit(r)
        eng.step()

        for tp_to in (2, 4, 1, 2):
            eng.transform(tp_to)
            while eng.transforming:
                eng.step()
                eng.check_capacity_invariant()
            assert eng.tp == tp_to
            assert eng.max_seq_alloc == q * tp_to, (
                tp_to, eng.max_seq_alloc)
        eng.run_until_done(1000)

        ref = Engine(cfg, params=params, max_batch=4, max_seq=64,
                     page_tokens=16, devices=devs, plan=plan)
        want = ServeRequest(rid=0, prompt=list(r.prompt),
                            max_new_tokens=8)
        ref.submit(want)
        ref.run_until_done(1000)
        assert want.generated == r.generated, (
            want.generated, r.generated)
        print("INPLACE_RESIZE_OK")
    """)
    assert "INPLACE_RESIZE_OK" in out


@pytest.mark.slow
def test_merge_donor_mid_chunked_prefill_resumes_on_target():
    """Tentpole requirement: a mid-prefill engine is a valid merge
    DONOR.  The donor's chunk progress (plan, offset, recurrent carry)
    exports with its slot KV and the prefill RESUMES on the merged
    target; the finished stream equals the whole-prompt reference."""
    out = run_py("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core.padding import make_plan
        from repro.core.scheduler import PrefillPolicy, ScaleUp
        from repro.models import model as M
        from repro.serving.cluster import ClusterEngine
        from repro.serving.engine import Engine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()
        plan = make_plan(cfg, len(devs), mode="page")
        params = M.init_params(jax.random.PRNGKey(11), cfg, plan)
        pol = PrefillPolicy(token_budget=16, mode="prefill",
                            long_threshold=16, order="fcfs")
        cluster = ClusterEngine(cfg, devs, n_instances=2, max_batch=4,
                                max_seq=64, params=params, dwell_steps=4,
                                prefill_policy=pol)
        rng = np.random.default_rng(0)
        # engine 0 must be the BUSIER member so decide_merge makes it
        # the target and the mid-prefill engine the DONOR: 3x14 = 42
        # in-flight/queued tokens vs the donor's 40-token prompt (kv
        # accounting counts a prefilling slot's full prompt)
        shorts = [ServeRequest(rid=i, prompt=rng.integers(
                      0, cfg.vocab_size, size=14).tolist(),
                      max_new_tokens=8) for i in range(3)]
        e0, e1 = cluster.engines
        for s in shorts:
            e0.submit(s)
        # a 3-chunk prompt directly on engine 1 (the future donor)
        chunked = ServeRequest(rid=5, prompt=rng.integers(
            0, cfg.vocab_size, size=40).tolist(), max_new_tokens=6)
        e1.submit(chunked)
        cluster.step()
        assert any(p["req"].rid == 5 and 0 < p["done"] < 40
                   for p in e1._prefilling.values()), "not mid-prefill"
        assert e0.kv_used_fraction() > e1.kv_used_fraction()

        # the pool-sized long triggers the merge; donor must be e1
        long_r = ServeRequest(rid=9, prompt=rng.integers(
            0, cfg.vocab_size, size=80).tolist(), max_new_tokens=16)
        cluster.submit(long_r)
        merges = [a for a in cluster.actions
                  if isinstance(a, ScaleUp) and a.donor_iids]
        assert merges and merges[0].donor_iids == (e1.iid,), merges
        target = cluster._engine(merges[0].iid)
        # the donor's chunk progress moved to the target
        assert any(p["req"].rid == 5 and p["done"] == 16
                   for p in target._prefilling.values())
        cluster.run(max_steps=5000)
        assert all(r.finished for r in shorts + [chunked, long_r])

        ref = Engine(cfg, params=params, max_batch=8, max_seq=128,
                     devices=devs, plan=plan)
        for got in shorts + [chunked, long_r]:
            want = ServeRequest(rid=got.rid, prompt=list(got.prompt),
                                max_new_tokens=got.max_new_tokens)
            ref.submit(want)
            ref.run_until_done(2000)
            assert want.generated == got.generated, (
                got.rid, want.generated, got.generated)
        print("MIDPREFILL_MERGE_OK")
    """)
    assert "MIDPREFILL_MERGE_OK" in out
