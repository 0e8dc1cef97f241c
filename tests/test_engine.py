"""Serving engine: continuous batching correctness on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.padding import make_plan
from repro.models import model as M
from repro.serving import Engine, ServeRequest


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("llama3-8b").reduced()
    return Engine(cfg, max_batch=3, max_seq=128)


def _reference_greedy(engine, prompt, n):
    cfg, plan = engine.cfg, engine.plan
    caches = M.init_decode_caches(cfg, plan, 1, engine.max_seq_alloc,
                                  engine.page_tokens)
    lg, caches = M.prefill(engine.params, cfg, plan,
                           {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
                           caches)
    toks = [int(jnp.argmax(lg[0, -1]))]
    for i in range(n - 1):
        lg, caches = M.decode_step(engine.params, cfg, plan, caches,
                                   jnp.asarray([toks[-1]], jnp.int32),
                                   jnp.asarray([len(prompt) + i], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_continuous_batching_matches_reference(engine):
    prompts = [[1, 5, 9, 13], [2, 4, 6, 8, 10, 12], [3, 7], [11, 3, 5]]
    reqs = [ServeRequest(p, max_new_tokens=6) for p in prompts]
    for r in reqs:
        engine.submit(r)
    engine.run_until_done(500)
    for r, p in zip(reqs, prompts):
        assert r.generated == _reference_greedy(engine, p, 6)
        assert r.done and r.ttft is not None


def test_more_requests_than_slots(engine):
    reqs = [ServeRequest([i + 1, i + 2], max_new_tokens=3)
            for i in range(7)]  # 7 requests, 3 slots
    for r in reqs:
        engine.submit(r)
    engine.run_until_done(500)
    assert all(r.done for r in reqs)
    for r in reqs:
        assert r.generated == _reference_greedy(engine, r.prompt, 3)


def test_eos_stops_generation(engine):
    probe = ServeRequest([1, 2, 3], max_new_tokens=8)
    engine.submit(probe)
    engine.run_until_done(200)
    eos = probe.generated[2]
    r = ServeRequest([1, 2, 3], max_new_tokens=8, eos_id=eos)
    engine.submit(r)
    engine.run_until_done(200)
    assert r.generated[-1] == eos
    assert len(r.generated) == 3


def test_temperature_sampling_is_deterministic_per_request(engine):
    """Temperature sampling uses a per-(request, position) PRNG fold —
    resubmitting the same rid-free prompt twice gives valid tokens and
    the engine stays consistent."""
    r1 = ServeRequest([1, 2, 3], max_new_tokens=5, temperature=0.8)
    engine.submit(r1)
    engine.run_until_done(200)
    assert len(r1.generated) == 5
    assert all(0 <= t < engine.plan.vocab_padded for t in r1.generated)


def test_engine_respects_max_seq(engine):
    long_prompt = list(range(1, 100))  # near max_seq=128
    r = ServeRequest(long_prompt, max_new_tokens=64)
    engine.submit(r)
    engine.run_until_done(400)
    assert r.done
    assert len(long_prompt) + len(r.generated) <= engine.max_seq_alloc


# ---------------------------------------------------------------------------
# Decode attention through the paged-attention kernel (interpret mode here)
# ---------------------------------------------------------------------------

def _serve(cfg, params, pallas_kernels, submit):
    """Serve what ``submit(engine)`` submits through a fresh engine;
    returns (the requests, the engine, the decode spans'
    ``kv_read_tokens``, at each of them the decoded rows' contexts with
    the view's rows and capacity, and whether a slot was mid-prefill at
    any of them)."""
    from repro.core.scheduler import PrefillPolicy
    from repro.serving import tracing

    pol = PrefillPolicy(token_budget=8, mode="mixed", long_threshold=8,
                        order="fcfs")
    eng = Engine(cfg, params=params, max_batch=3, max_seq=32,
                 page_tokens=8, prefill_policy=pol,
                 pallas_kernels=pallas_kernels)
    seen, mid_prefill = [], []
    batch, spilled = eng._decode_batch, eng._decode_spilled

    def spy_batch(active):
        seen.append(([r.context_len for r in active], eng.max_batch,
                     eng.max_seq_alloc))
        mid_prefill.append(bool(eng._prefilling))
        return batch(active)

    def spy_spilled(r):
        seen.append(([r.context_len], 1, eng._spills[r.slot]["ext_tokens"]))
        return spilled(r)

    eng._decode_batch, eng._decode_spilled = spy_batch, spy_spilled
    tracing.RECORDER.clear()
    reqs = submit(eng)
    eng.run_until_done(500)
    reads = [s.attrs["kv_read_tokens"] for s in tracing.RECORDER.spans()
             if s.name == "engine.decode"]
    assert len(reads) == len(seen)
    return reqs, eng, reads, seen, any(mid_prefill)


@pytest.mark.parametrize("case", ["batched", "spilled"])
def test_decode_kernel_streams_match_jnp_path(case):
    """An engine whose decode attention takes the paged-attention kernel
    emits the greedy streams of one on the jnp path: rows beside an
    empty slot and a slot mid-prefill (MHA), and a slot spilled into a
    neighbour's pool (MQA), which also matches an engine big enough not
    to spill.  ``engine.decode`` counts what attention reads: whole
    reservations on the jnp path, the rows' live pages with the
    kernel."""
    import dataclasses
    name = "llama3-8b" if case == "batched" else "gemma-2b"
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32")
    plan = make_plan(cfg, 1)
    params = M.init_params(jax.random.PRNGKey(3), cfg, plan)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in ((5, 27) if case == "batched" else (28, 5))]
    news = (10, 4) if case == "batched" else (12, 8)

    def submit(eng):
        reqs = [ServeRequest(list(p), max_new_tokens=k)
                for p, k in zip(prompts, news)]
        if case == "spilled":
            host = Engine(cfg, params=params, max_batch=2, max_seq=32,
                          page_tokens=8, pallas_kernels=eng.pallas_kernels)
            eng.admit_spilled(reqs[0], host, host.host_spilled(2))
            eng.submit(reqs[1])
        else:
            for r in reqs:
                eng.submit(r)
        return reqs

    runs = {k: _serve(cfg, params, k, submit) for k in (False, True)}
    streams = {k: [r.generated for r in runs[k][0]] for k in runs}
    assert streams[True] == streams[False]
    if case == "batched":
        assert runs[True][4], "no decode step met a slot mid-prefill"
    else:
        big = Engine(cfg, params=params, max_batch=2, max_seq=64,
                     page_tokens=8)
        r = ServeRequest(list(prompts[0]), max_new_tokens=news[0])
        big.submit(r)
        big.run_until_done(200)
        assert streams[True][0] == r.generated
        assert any(rows == 1 for _, rows, _ in runs[True][3])
    for k, (_, eng, reads, seen, _) in runs.items():
        P = eng.page_tokens
        for read, (ctx, rows, cap) in zip(reads, seen):
            if not k:
                assert read == rows * cap
            else:
                pages = sum(-(-c // P) for c in ctx) + rows - len(ctx)
                assert read == pages * P, (read, ctx)


def test_decode_kernel_through_transform_session():
    """A TP1 engine over two devices (rows and their pages over ``rep``)
    transforms to TP2 (kv heads over ``tp``) mid-decode; through the
    session each layer's decode runs the kernel on that layer's mesh.
    The streams equal the jnp path's."""
    import os
    import subprocess
    import sys
    import textwrap
    body = textwrap.dedent("""
        import dataclasses
        import jax
        from repro.configs import get_config
        from repro.core.padding import make_plan
        from repro.models import model as M
        from repro.serving.engine import Engine
        from repro.serving.request import ServeRequest

        cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                                  dtype="float32")
        devs = jax.devices()[:2]
        params = M.init_params(jax.random.PRNGKey(11), cfg,
                               make_plan(cfg, 2, mode="page"))
        streams = {}
        for kernels in (False, True):
            eng = Engine(cfg, params=params, max_batch=2, max_seq=64,
                         page_tokens=16, devices=devs,
                         pallas_kernels=kernels)
            reqs = [ServeRequest(rid=i, prompt=list(range(5 + i, 21 + i)),
                                 max_new_tokens=12) for i in range(2)]
            for r in reqs:
                eng.submit(r)
            for _ in range(4):
                eng.step()
            n = eng.transform(2)
            mid = 0
            while eng.transforming:
                eng.step()
                mid += 1
            assert n > 0 and mid == n and eng.tp == 2
            eng.run_until_done()
            streams[kernels] = [r.generated for r in reqs]
        assert streams[True] == streams[False], streams
        print("KERNEL_SESSION_OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "KERNEL_SESSION_OK" in out.stdout
