"""The readers of the program's own spans: ``control_plane_ms_per_step``
and ``decode_kv_live_pct`` on a window served on the CPU at a reduced
size, ``host_idle_pct`` on a synthetic trace with a known answer, and
each one's ``None`` where it has nothing to read."""
import sys

import pytest

from chipbench import harness, spec, traffic
from chipbench import trace as tracemod
from conftest import tiny_cell, tiny_model

CELL = "phi3-mini.longdoc"
MS = 1_000_000                       # ns


@pytest.fixture(scope="module")
def window():
    """A 2 s window of the cell at the reduced size, and the engine."""
    import jax
    from repro.serving import tracing

    cell = tiny_cell(CELL)
    model = tiny_model(cell["model"])
    seed = 2 ** 33 + 7
    devices = jax.devices("cpu")[:cell["chips"]]
    clk = harness.CompileClock()
    cluster, _, _ = harness.build(cell, model, seed, devices, clk)
    probe = harness.Probe(annotate=False)
    probe.install_all(cluster)
    harness.warm_up(cluster, cell["mix"], model["vocab_size"], seed, probe)
    arrivals = traffic.schedule(cell["mix"], 2.0, seed)
    prompts = traffic.prompt_ids(arrivals, model["vocab_size"], seed)
    tracing.RECORDER.clear()
    run = harness.window(cluster, arrivals, prompts, 2.0, probe, None, None)
    return {"run": run, "trace": None, "engine": cluster.engines[0]}


def test_control_plane_ms_per_step(window):
    from repro.serving import tracing

    v = spec.reader("control_plane_ms_per_step")(window)
    run = window["run"]
    steps = run["steps"]
    assert steps
    wall_ms = 1e3 * sum(s["t1"] - s["t0"] for s in steps) / len(steps)
    assert 0 < v < wall_ms
    # each control-plane span of the window counted once, as self time
    spans = [s for s in tracing.RECORDER.spans()
             if s.name in ("cluster.route", "cluster.plan",
                           "cluster.finalize")]
    assert len(spans) >= 3 * len(steps)
    assert v <= 1e-6 * sum(s.t1 - s.t0 for s in spans) / len(steps)


def test_decode_kv_live_pct_is_the_hand_count(window):
    v = spec.reader("decode_kv_live_pct")(window)
    eng, reqs = window["engine"], window["run"]["reqs"]
    # one slot, so one row a decode step; a request's k-th decode step
    # attends over its prompt, its prefill's token and the k - 1 since
    assert eng.max_batch == 1
    live = sum(len(q.prompt) + k for q in reqs
               for k in range(1, len(q.generated)))
    decode_steps = sum(max(len(q.generated) - 1, 0) for q in reqs)
    assert decode_steps
    reserved = decode_steps * eng.max_batch * eng.max_seq_alloc
    assert v == pytest.approx(100.0 * live / reserved)
    assert 0 < v < 100


def test_no_steps_nothing_to_read(window):
    run = dict(window["run"], steps=[])
    assert spec.reader("control_plane_ms_per_step")(
        dict(window, run=run)) is None


@pytest.mark.parametrize("name", ["control_plane_ms_per_step",
                                  "decode_kv_live_pct"])
def test_dropped_spans_nothing_to_read(window, name, monkeypatch):
    from repro.serving import tracing
    monkeypatch.setattr(tracing.RECORDER, "lost_ns",
                        window["run"]["t0"] * 1e9 + 1)
    assert spec.reader(name)(window) is None


@pytest.mark.parametrize("name", ["control_plane_ms_per_step",
                                  "decode_kv_live_pct", "host_idle_pct"])
def test_program_without_recorder_nothing_to_read(name, monkeypatch):
    import repro.serving
    ctx = synthetic(monkeypatch, 25)
    assert spec.reader(name)(ctx) is not None
    # the parent of the change that added the recorder has no such module
    monkeypatch.delattr(repro.serving, "tracing")
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    assert spec.reader(name)(ctx) is None


def synthetic(monkeypatch, n_steps, jitter_ns=0):
    """A recorder holding ``n_steps`` steps of 10 ms on the program's
    clock: 8 ms of ``cluster.step``, then 2 ms outside it.  In each,
    ``cluster.route`` for 1 ms, then ``engine.step`` holding
    ``engine.decode`` (2-7 ms, 1000 of 4096 KV tokens live) holding
    ``engine.sync`` (3-6 ms).  The device idles from 0.5 to 4 ms and
    from 8 to 10 ms of each step: the host works through 2.5 ms of that
    (0.5-1 route, 1-2 engine.step, 2-3 decode), the rest is sync or
    outside the program's spans.  The profiler's clock runs 1 s ahead
    of the program's."""
    from repro.serving import tracing

    rec = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", rec)
    off = 1_000_000_000
    base = 1_000_000_000_000            # the program's clock, ns
    steps, anchors, gaps = [], [], []

    def add(name, a, b, parent, **attrs):
        sp = tracing.Span(name, attrs)
        sp.t0, sp.t1, sp.parent, sp.index = a, b, parent, rec.opened
        rec.opened += 1
        rec.ring.append(sp)
        return sp.index

    for k in range(n_steps):
        t0 = base + 10 * MS * k
        add("cluster.route", t0, t0 + 1 * MS, -1)
        i = add("engine.step", t0 + 1 * MS, t0 + 8 * MS, -1)
        j = add("engine.decode", t0 + 2 * MS, t0 + 7 * MS, i,
                kv_live_tokens=1000, kv_read_tokens=4096)
        add("engine.sync", t0 + 3 * MS, t0 + 6 * MS, j)
        steps.append({"t0": t0 * 1e-9, "t1": (t0 + 8 * MS) * 1e-9})
        anchors.append((t0 + off, t0 + 8 * MS + off + jitter_ns * (k % 2),
                        "cluster.step"))
        gaps += [(t0 + off + MS // 2, t0 + off + 4 * MS),
                 (t0 + off + 8 * MS, t0 + off + 10 * MS)]
    window_s = n_steps * 10 * MS * 1e-9
    dev = tracemod.Device("/device:TPU:0", window_s - 5.5e-3 * n_steps,
                          gaps)
    tr = tracemod.Trace(window_s, [dev], anchors)
    t_a, t_b = base * 1e-9, (base + 10 * MS * n_steps) * 1e-9
    run = {"t0": t_a, "t1": t_b, "steps": steps, "trace_t": (t_a, t_b)}
    return {"run": run, "trace": tr}


def test_host_idle_pct_on_a_known_trace(monkeypatch):
    ctx = synthetic(monkeypatch, 25, jitter_ns=150_000)
    v = spec.reader("host_idle_pct")(ctx)
    assert v == pytest.approx(25.0, abs=0.2)      # 2.5 ms of every 10


def test_host_idle_pct_too_few_anchors(monkeypatch):
    ctx = synthetic(monkeypatch, 19)
    assert spec.reader("host_idle_pct")(ctx) is None


def test_host_idle_pct_anchors_spread_too_wide(monkeypatch):
    ctx = synthetic(monkeypatch, 25, jitter_ns=250_000)
    assert spec.reader("host_idle_pct")(ctx) is None


def test_host_idle_pct_dropped_spans(monkeypatch):
    from repro.serving import tracing
    ctx = synthetic(monkeypatch, 25)
    tracing.RECORDER.lost_ns = ctx["run"]["trace_t"][0] * 1e9 + MS
    assert spec.reader("host_idle_pct")(ctx) is None


def test_host_idle_pct_without_a_trace(monkeypatch):
    ctx = synthetic(monkeypatch, 25)
    assert spec.reader("host_idle_pct")(dict(ctx, trace=None)) is None
