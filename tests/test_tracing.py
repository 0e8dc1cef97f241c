"""The serving step's spans and counts (``repro.serving.tracing``), on a
tiny one-device ``ClusterEngine`` run: how the spans nest and tile each
step, the chunk and KV counts against a hand count, compiles attributed
to the chunk that compiled, the off switch, the ring's drop count, and
the spans' copies on the profiler's host plane."""
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.serving import tracing

PROMPTS = [(40, 4), (12, 3), (28, 5)]      # (prompt tokens, new tokens)
PARENTS = {
    "cluster.route": {None}, "cluster.plan": {None},
    "cluster.finalize": {None}, "engine.step": {None},
    "engine.session": {"engine.step"}, "engine.admit": {"engine.step"},
    "engine.chunk": {"engine.step"}, "engine.decode": {"engine.step"},
    "engine.chunk.view": {"engine.chunk"},
    "engine.chunk.run": {"engine.chunk"},
    "engine.chunk.adopt": {"engine.chunk"},
    "engine.sync": {"engine.decode", "engine.chunk"},
}


def _cluster():
    from repro.configs import get_config
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.cluster import ClusterEngine

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32")
    pol = PrefillPolicy(token_budget=16, mode="mixed", long_threshold=32)
    return cfg, ClusterEngine(cfg, jax.devices()[:1], n_instances=1,
                              max_batch=2, max_seq=64, page_tokens=8,
                              prefill_policy=pol)


def _requests(cfg, spec, rid0=0, seed=0):
    from repro.serving.request import ServeRequest
    rng = np.random.default_rng(seed)
    return [ServeRequest(rid=rid0 + i, prompt=rng.integers(
        0, cfg.vocab_size, size=n).tolist(), max_new_tokens=m)
        for i, (n, m) in enumerate(spec)]


def _serve(cluster, reqs):
    """Submit ``reqs`` and step until the cluster is idle; returns each
    step's (start, end) in ``perf_counter_ns``."""
    for r in reqs:
        cluster.submit(r)
    steps = []
    while not cluster.idle:
        t0 = time.perf_counter_ns()
        cluster.step()
        steps.append((t0, time.perf_counter_ns()))
    assert all(r.finished for r in reqs)
    return steps


@pytest.fixture(scope="module")
def served():
    cfg, cluster = _cluster()
    reqs = _requests(cfg, PROMPTS)
    tracing.RECORDER.clear()
    steps = _serve(cluster, reqs)
    spans = tracing.RECORDER.spans()
    # a warm second request of the first one's length
    warm = _requests(cfg, [PROMPTS[0]], rid0=100, seed=1)
    first = tracing.RECORDER.opened
    _serve(cluster, warm)
    warm_spans = [s for s in tracing.RECORDER.spans() if s.index >= first]
    return {"cluster": cluster, "reqs": reqs, "steps": steps,
            "spans": spans, "warm": warm, "warm_spans": warm_spans}


def test_spans_nest_under_their_parents(served):
    spans = served["spans"]
    by_index = {s.index: s for s in spans}
    assert set(PARENTS) - {"engine.session"} <= {s.name for s in spans}
    for s in spans:
        parent = by_index.get(s.parent)
        assert (parent.name if parent else None) in PARENTS[s.name], (
            s.name, s.attrs)
        assert s.t0 <= s.t1
        if parent is not None:
            assert parent.t0 <= s.t0 and s.t1 <= parent.t1
            assert parent.index < s.index


def test_chunk_sizes_sum_to_each_prompt(served):
    got = {}
    for s in served["spans"]:
        if s.name == "engine.chunk":
            got.setdefault(s.attrs["rid"], []).append(
                (s.attrs["start"], s.attrs["size"]))
    for r in served["reqs"]:
        chunks = got[r.rid]
        assert sum(n for _, n in chunks) == len(r.prompt)
        # each chunk starts where the one before it ended
        assert [a for a, _ in chunks] == list(
            np.cumsum([0] + [n for _, n in chunks[:-1]]))
    assert len(got[0]) == 3                    # 40 tokens at 16 a chunk


def test_spans_tile_each_step(served):
    """Top-level spans cover the steps but for the few microseconds
    between them: over the run, and in the median step (a step of a few
    milliseconds here, so one preemption between two spans can take a
    single step below 95% on a loaded machine)."""
    top = [s for s in served["spans"] if s.parent == -1]
    shares, covered, wall = [], 0, 0
    for t0, t1 in served["steps"]:
        c = sum(min(s.t1, t1) - max(s.t0, t0) for s in top
                if s.t0 < t1 and s.t1 > t0)
        shares.append(c / (t1 - t0))
        covered += c
        wall += t1 - t0
    assert covered >= 0.95 * wall
    assert np.median(shares) >= 0.95, shares


def test_decode_kv_counts_match_a_hand_count(served):
    eng = served["cluster"].engines[0]
    reserved = eng.max_batch * eng.max_seq_alloc
    prompt = {r.rid: len(r.prompt) for r in served["reqs"]}
    decoded = {rid: 0 for rid in prompt}
    for s in served["spans"]:
        if s.name != "engine.decode":
            continue
        assert s.attrs["kv_read_tokens"] == reserved == 2 * 64
        # a row's k-th decode step attends over its prompt, the token
        # its prefill emitted and the k - 1 it decoded since
        want = 0
        for rid in s.attrs["rids"]:
            decoded[rid] += 1
            want += prompt[rid] + decoded[rid]
        assert s.attrs["kv_live_tokens"] == want
    assert decoded == {r.rid: r.max_new_tokens - 1 for r in served["reqs"]}


def test_compiles_land_on_the_chunk_that_compiled(served):
    first = [n for s, n in tracing.rolled_up(
        served["spans"], "engine.chunk", "compiles")
        if s.attrs["rid"] == 0]
    assert first[0] > 0
    warm = tracing.rolled_up(served["warm_spans"], "engine.chunk",
                             "compiles")
    assert [s.attrs["size"] for s, _ in warm] == [16, 16, 8]
    assert [n for _, n in warm] == [0, 0, 0]
    # compile seconds ride along, on the innermost span open
    assert all(s.attrs.get("compile_s", 0) > 0 for s in served["spans"]
               if s.attrs.get("compiles"))


def test_totals_count_every_span(served):
    spans = served["spans"] + served["warm_spans"]
    totals = tracing.RECORDER.totals()
    decode = [s for s in spans if s.name == "engine.decode"]
    assert totals["engine.decode"]["count"] == len(decode)
    assert totals["engine.decode"]["kv_live_tokens"] == sum(
        s.attrs["kv_live_tokens"] for s in decode)


def test_off_switch_records_nothing(monkeypatch):
    cfg, cluster = _cluster()
    monkeypatch.setattr(tracing, "ENABLED", False)
    opened = tracing.RECORDER.opened
    _serve(cluster, _requests(cfg, [(20, 3)]))
    assert tracing.RECORDER.opened == opened


def test_ring_counts_what_it_dropped(monkeypatch):
    rec = tracing.Recorder(size=4)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    with tracing.span("outer") as outer:
        for i in range(4):          # the fourth pushes ``outer`` out
            with tracing.span("inner", i=i) as sp:
                pass
    inner_end = sp.t1
    for i in range(4, 8):
        with tracing.span("inner", i=i):
            pass
    assert rec.opened == 9 and rec.dropped == 5
    assert [s.attrs["i"] for s in rec.spans()] == [4, 5, 6, 7]
    # ``outer`` left the ring while it was open: its end counts as lost
    assert inner_end < outer.t1 == rec.lost_ns
    assert not rec.holds_since(outer.t1)
    assert rec.holds_since(outer.t1 + 1)
    # totals count the dropped spans too
    totals = rec.totals()
    assert totals["inner"]["count"] == 8 and totals["outer"]["count"] == 1
    assert totals["inner"]["i"] == sum(range(8))


def test_spans_land_on_their_trace_annotations(monkeypatch, tmp_path):
    from jax.profiler import ProfileData

    monkeypatch.setattr(tracing, "RECORDER", tracing.Recorder())
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(30):
            with tracing.span("probe.outer"):
                with tracing.span("probe.inner"):
                    time.sleep(0.0002)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    marks = sorted((e.start_ns, e.end_ns, e.name)
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("probe."))
    spans = sorted(tracing.RECORDER.spans(), key=lambda s: s.t0)
    assert [n for _, _, n in marks] == [s.name for s in spans]
    # one offset between the two clocks, fitted on the ends
    offset = float(np.median([b - s.t1 for (_, b, _), s
                              in zip(marks, spans)]))
    for (a, b, _), s in zip(marks, spans):
        assert abs(a - (s.t0 + offset)) < 100_000
        assert abs(b - (s.t1 + offset)) < 100_000
