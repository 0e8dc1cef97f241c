"""One run of one cell: weights from the seed, the program's serving path
built and warmed up, an open-loop window on the wall clock, the metrics,
and the comparison with the plain reference that decides ``correct``.

The program is driven through its own entry points only:
``serving.cluster.ClusterEngine`` (built as ``launch.serve.build_cluster``
builds it, with the benchmark's weights) and its ``submit`` / ``step``.
Every request is timed from its due time on the generator's schedule;
each token is stamped when the cluster step that emitted it returns.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from typing import Dict, List, Optional

import numpy as np

from chipbench import e2e, spec, trace as tracemod, traffic, weights

clock = time.perf_counter
WARM_RID = 1 << 30      # warm-up request ids, clear of the window's
WARMUP_OUTPUT = 4       # tokens each warm-up request decodes
TRACE_SECONDS = 6       # ``--trace 1`` profiles the window's last seconds
# the name the chunk-prefill kernel's call takes in the compiled program,
# and so in the trace (the name scope the probe opens around it)
CHUNK_KERNEL = "chipbench_chunk_kernel"
SPANS = ("cluster.step", "bench.submit", "bench.idle", "bench.stamp",
         "engine.prefill_chunk", "engine.decode_dispatch")


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Programs JAX obtained (compiled or read from the persistent
    cache) and seconds spent tracing, lowering and compiling, from its
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.BACKEND:
            self.programs += 1

    def mark(self):
        return (self.programs, self.seconds)


def name_chunk_kernel() -> None:
    """Trace the program's chunk-prefill kernel call inside a name scope,
    so its Mosaic call is named ``CHUNK_KERNEL`` in the compiled program
    and in the trace.  A scope names ops and changes no computation; it
    has to be in place before the chunk programs are traced."""
    import jax
    from repro.kernels import chunk_prefill as CP

    call = CP.chunk_prefill_sharded
    if getattr(call, "chipbench_scoped", False):
        return

    def scoped(*a, **k):
        with jax.named_scope(CHUNK_KERNEL):
            return call(*a, **k)

    scoped.chipbench_scoped = True
    CP.chunk_prefill_sharded = scoped


class Probe:
    """Harness-side spans and counts around the program's calls: each
    prefill chunk (its start and length), and host spans for the trace."""

    def __init__(self, annotate: bool):
        self.chunks: List[tuple] = []       # (iid, start, size, t_end)
        self.annotate = annotate

    def span(self, name: str):
        if not self.annotate:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def install(self, eng) -> None:
        run_chunk, dispatch = eng._run_chunk, eng._decode_dispatch

        def chunk(slot, _eng=eng, _run=run_chunk):
            prog = _eng._prefilling[slot]
            start, size = prog["done"], prog["chunks"][prog["ci"]]
            with self.span("engine.prefill_chunk"):
                out = _run(slot)
            self.chunks.append((_eng.iid, start, size, clock()))
            return out

        def decode(*a, _run=dispatch):
            with self.span("engine.decode_dispatch"):
                return _run(*a)

        eng._run_chunk = chunk
        eng._decode_dispatch = decode

    def install_all(self, cluster) -> None:
        for eng in cluster.engines:
            self.install(eng)


def _device_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def _on_device(tree) -> int:
    """Bytes ``tree`` takes on its devices (tiled layouts included)."""
    import jax
    return sum(x.on_device_size_in_bytes() for x in jax.tree.leaves(tree)
               if hasattr(x, "on_device_size_in_bytes"))


def _barrier(cluster) -> None:
    import jax
    for e in cluster.engines:
        if not e.parked:
            jax.block_until_ready((e.params, e.caches))


def build(cell: Dict, model: Dict, seed: int, devices, clk: CompileClock):
    """Weights on the device, then the cluster over them."""
    import jax
    from repro.core.padding import make_plan
    from repro.core.scheduler import PrefillPolicy
    from repro.serving.cluster import ClusterEngine

    eng = cell["engine"]
    fam = spec.family(model)
    cfg = fam.program_config(model)
    n_inst = eng["instances"]
    w = len(devices) // n_inst
    plan = make_plan(cfg, n_inst * w, mode="page")
    t0, c0 = clock(), clk.mark()
    params = fam.for_program(seed, model, cfg, plan, device=devices[0])
    jax.block_until_ready(params)
    fp = fam.fingerprint_program(params, model)
    t1, c1 = clock(), clk.mark()
    # launch.serve.build_cluster's cluster, given the benchmark's weights
    policy = PrefillPolicy(token_budget=eng["chunk_budget"],
                           mode=eng["prefill_mode"],
                           long_threshold=eng["max_seq"] // w or 1,
                           order="sjf")
    cluster = ClusterEngine(
        cfg, devices, n_instances=n_inst, max_batch=eng["slots"],
        max_seq=eng["max_seq"], page_tokens=eng["page_tokens"],
        prefill_policy=policy, rng=jax.random.PRNGKey(seed & 0x7FFFFFFF),
        params=params)
    del params
    if cluster.plan != plan:
        raise RuntimeError("the cluster's padding plan differs from the "
                           "one the weights were laid out for")
    _barrier(cluster)
    t2, c2 = clock(), clk.mark()
    e0 = cluster.engines[0]
    log(f"built: params {_on_device(e0.params)} B, KV pool "
        f"{_on_device(e0.caches)} B on the device; bytes_in_use "
        f"{_in_use(devices)}, limit "
        f"{(devices[0].memory_stats() or {}).get('bytes_limit')}")
    split = {"weights_s": t1 - t0, "weights_compile_s": c1[1] - c0[1],
             "cluster_s": t2 - t1, "cluster_compile_s": c2[1] - c1[1],
             "bytes_in_use_built": _in_use(devices)}
    return cluster, fp, split


def warm_up(cluster, mix: Dict, vocab: int, seed: int,
            probe: Probe) -> int:
    """Serve one request of every prompt length the mix can send, all
    submitted at once (so prefills overlap as they will in the window),
    until the cluster drains and any transformation it ordered has
    returned.  Returns the number of warm-up requests."""
    from repro.serving.request import ServeRequest

    rng = np.random.default_rng([seed, 2])
    reqs = [ServeRequest(rid=WARM_RID + i,
                         prompt=rng.integers(0, vocab, size=n).tolist(),
                         max_new_tokens=WARMUP_OUTPUT)
            for i, n in enumerate(traffic.prompt_grid(mix))]
    for r in reqs:
        cluster.submit(r)
    cluster.run(max_steps=100_000)
    probe.chunks.clear()
    return len(reqs)


def window(cluster, arrivals, prompts, seconds: float, probe: Probe,
           trace_at: Optional[tuple], trace_dir: Optional[str]) -> Dict:
    """The open-loop window: each request submitted when it is due, the
    cluster stepped whenever it has work."""
    import jax
    from repro.serving.request import ServeRequest

    reqs = [ServeRequest(rid=i, prompt=p.tolist(),
                         max_new_tokens=a.output_len)
            for i, (a, p) in enumerate(zip(arrivals, prompts))]
    rec = [{"rid": i, "due": 0.0,
            "prompt_len": a.prompt_len, "output_len": a.output_len,
            "tokens": [], "prefill_start": None, "submitted": None}
           for i, a in enumerate(arrivals)]
    steps: List[Dict] = []
    live: List[int] = []
    late: List[float] = []
    failed = 0
    nxt = 0
    tracing = False
    t0 = clock()
    for r, a in zip(rec, arrivals):
        r["due"] = t0 + a.due_s
    t_end = t0 + seconds
    wspan = None

    def stamp(now):
        keep = []
        for i in live:
            r, q = rec[i], reqs[i]
            while len(r["tokens"]) < len(q.generated):
                r["tokens"].append(now)
            if r["prefill_start"] is None and q.t_prefill_start is not None:
                r["prefill_start"] = now
            if not q.finished:
                keep.append(i)
        live[:] = keep

    def step(now):
        n_chunks = len(probe.chunks)
        gen0 = {i: len(reqs[i].generated) for i in live}
        with probe.span("cluster.step"):
            cluster.step()
        t = clock()
        chunks = probe.chunks[n_chunks:]
        ctx = [len(reqs[i].prompt) + len(reqs[i].generated) - 1
               for i in live if len(reqs[i].generated) > gen0[i]
               and gen0[i] > 0]
        session = any(e.transforming for e in cluster.engines)
        steps.append({"t0": now, "t1": t, "chunks": [c[1:3] for c in chunks],
                      "decode_contexts": ctx, "session": session,
                      "decode_only": bool(ctx) and not chunks
                      and not session})
        with probe.span("bench.stamp"):
            stamp(t)
        return t

    now = t0
    while True:
        if trace_at is not None and not tracing and now >= t0 + trace_at[0]:
            _barrier(cluster)
            jax.profiler.start_trace(trace_dir)
            tracing = True
            wspan = jax.profiler.TraceAnnotation(tracemod.WINDOW)
            wspan.__enter__()
            trace_t = [clock(), None]
        if now >= t_end:
            break
        with probe.span("bench.submit"):
            while nxt < len(reqs) and rec[nxt]["due"] <= now:
                try:
                    cluster.submit(reqs[nxt])
                    live.append(nxt)
                    rec[nxt]["submitted"] = now
                    late.append(now - rec[nxt]["due"])
                except ValueError as err:
                    log(f"request {nxt} refused: {err}")
                    failed += 1
                nxt += 1
        if cluster.idle and not live:
            wake = min(rec[nxt]["due"] if nxt < len(reqs) else t_end, t_end)
            with probe.span("bench.idle"):
                while clock() < wake:
                    time.sleep(min(0.0005, max(0.0, wake - clock())))
            now = clock()
            continue
        now = step(now)
    t1 = clock()
    if tracing and trace_t[1] is None:
        _barrier(cluster)
        trace_t[1] = clock()
        wspan.__exit__(None, None, None)
        jax.profiler.stop_trace()
    prefill_tokens = sum(size for _, _, size, t in probe.chunks
                         if t0 <= t <= t1)
    return {"t0": t0, "t1": t1, "requests": rec[:nxt], "reqs": reqs[:nxt],
            "steps": steps, "late": late,
            "failed": failed, "prefill_tokens": prefill_tokens,
            "not_submitted": len(reqs) - nxt,
            "trace_t": tuple(trace_t) if trace_at is not None else None}


def sample_for_check(run: Dict, seed: int, want_tokens: int,
                     max_requests: int) -> List[int]:
    """Finished requests to compare, drawn from the seed: the longest
    first, then others until ``want_tokens`` served tokens."""
    done = [i for i, q in enumerate(run["reqs"])
            if q.finished and len(q.generated) == q.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(run["reqs"][i].prompt)
                                       + len(run["reqs"][i].generated), i))
    rng = np.random.default_rng([seed, 3])
    rest = [i for i in rng.permutation(done).tolist() if i != longest]
    pick, n = [longest], len(run["reqs"][longest].generated)
    for i in rest:
        if n >= want_tokens or len(pick) >= max_requests:
            break
        pick.append(i)
        n += len(run["reqs"][i].generated)
    return pick


def check(run: Dict, picks: List[int], model: Dict, seed: int, fp: Dict,
          device, control: bool, shape=(0, 0)) -> Dict:
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over the sampled requests.  With
    ``control`` the token compared at each position is the one the fp8
    control puts first, given the same prompt and served tokens."""
    fam = spec.family(model)
    w = fam.canonical(seed, model, device=device)
    same = weights.same_fingerprint(weights.fingerprint(w), fp)
    widest, n_tok = 0.0, 0
    for i in picks:
        q = run["reqs"][i]
        toks = list(q.prompt) + list(q.generated[:-1])
        rows = list(range(len(q.prompt) - 1, len(toks)))
        ref = fam.logits(w, model, toks, rows, shape=shape)
        if control:
            tok = fam.logits(w, model, toks, rows, quant=True,
                             shape=shape).argmax(axis=1)
        else:
            tok = np.asarray(q.generated)
        gap = ref.max(axis=1) - ref[np.arange(len(rows)), tok]
        widest = max(widest, float(np.max(gap)))
        n_tok += len(rows)
    return {"widest_gap": widest, "weights_match": same, "tokens": n_tok,
            "requests": len(picks)}


def run(cell: Dict, seed: int, seconds: float, trace: bool, devices,
        t_start: float, model: Optional[Dict] = None,
        control: bool = False) -> Dict:
    """One run; returns the result line and the lines to print.  With
    ``control`` the fp8 control's first choices stand in for the served
    tokens in the comparison."""
    import jax

    clk = CompileClock()
    model = model or cell["model"]
    mix = cell["mix"]
    chips = cell["chips"]
    devices = list(devices)[:chips]
    probe = Probe(annotate=trace)
    name_chunk_kernel()

    cluster, fp, split = build(cell, model, seed, devices, clk)
    probe.install_all(cluster)
    t_w, c_w = clock(), clk.mark()
    n_warm = warm_up(cluster, mix, model["vocab_size"], seed, probe)
    _barrier(cluster)
    t_r, c_r = clock(), clk.mark()
    log(f"warmed up in {t_r - t_w:.1f} s; peak_bytes_in_use "
        f"{_device_peak(devices)}")
    split.update(warmup_s=t_r - t_w, warmup_compile_s=c_r[1] - c_w[1],
                 warmup_requests=n_warm, peak_after_warmup=_device_peak(
                     devices))

    arrivals = traffic.schedule(mix, seconds, seed)
    prompts = traffic.prompt_ids(arrivals, model["vocab_size"], seed)
    trace_dir = None
    trace_at = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        # the last part of the window: the trace is written out after
        # the window closes, not inside it
        trace_at = (max(0.0, seconds - TRACE_SECONDS), seconds)
    setup_s = clock() - t_start
    c_win = clk.mark()
    result_run = window(cluster, arrivals, prompts, seconds, probe,
                        trace_at, trace_dir)
    c_end = clk.mark()
    result_run["setup_s"] = setup_s
    peak = _device_peak(devices)
    in_window = (c_end[0] - c_win[0], c_end[1] - c_win[1])

    lines = [
        f"device {devices[0].device_kind} x {len(devices)} "
        f"({devices[0].platform})",
        "setup " + ", ".join(f"{k} {v:.3f}" if isinstance(v, float)
                             else f"{k} {v}" for k, v in split.items())
        + f"; setup_s {setup_s:.3f}",
        f"window {result_run['t1'] - result_run['t0']:.3f} s, "
        f"{len(result_run['requests'])} requests submitted, "
        f"{result_run['not_submitted']} not yet due, "
        f"{len(result_run['steps'])} steps, "
        f"{result_run['prefill_tokens']} prefill tokens",
        "generator lateness s: p50 {:.6f} p99 {:.6f} max {:.6f}".format(
            e2e.percentile(result_run["late"], 50),
            e2e.percentile(result_run["late"], 99),
            max(result_run["late"], default=float("nan"))),
        "ttft s: p50 {:.4f} p75 {:.4f} p90 {:.4f} over {} requests; "
        "gaps ms: p50 {:.3f} p95 {:.3f} p99 {:.3f} over {}".format(
            *(e2e.percentile(e2e.ttfts(result_run["requests"]), q)
              for q in (50, 75, 90)), len(result_run["requests"]),
            *(1e3 * e2e.percentile(e2e.gaps(result_run["requests"],
                                            result_run["t0"],
                                            result_run["t1"]), q)
              for q in (50, 95, 99)),
            len(e2e.gaps(result_run["requests"], result_run["t0"],
                         result_run["t1"]))),
        f"compiles inside the window: {in_window[0]} programs, "
        f"{in_window[1]:.3f} s",
        f"peak_bytes_in_use {peak}",
    ]
    ctx = {"run": result_run, "model": model, "chips": chips,
           "peak": spec.peaks(devices[0].device_kind)
           if devices[0].platform == "tpu" else None,
           "trace": None, "cell": cell}
    breakdown = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if trace:
        tr = tracemod.read(tracemod.find(trace_dir), SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = tr
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_by_span(10)}
        lines.append(f"trace: window {tr.window_s:.6f} s, busy "
                     f"{tr.busy_s:.6f} s on {len(tr.devices)} devices")
        kernels = sorted({tracemod.op_name(n) for d in tr.devices
                          for n in d.op_events if tracemod.is_kernel(n)})
        lines.append(f"trace: Mosaic kernel ops {kernels}")
        names = [m["name"] for m in cell["per_layer"]]
        metrics = {}
        for m in cell["per_layer"]:
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lines.append("per-layer metrics not found: "
                     f"{[n for n in names if n not in metrics]}")
    else:
        vals = e2e.compute([m["name"] for m in cell["end_to_end"]],
                           result_run)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    for k, v in metrics.items():
        lines.append(f"metric {k} {v['value']!r} {v['unit']}")

    # the program's state goes before the reference runs, so the
    # reference neither sets the peak nor lacks the memory
    picks = sample_for_check(result_run, seed,
                             cell["check"]["tokens"],
                             cell["check"]["max_requests"])
    del cluster
    gc.collect()
    lines.append(f"bytes_in_use before the reference {_in_use(devices)}")
    t_c = clock()
    # one padded shape for every request of the cell: the longest
    # context it can hold, the longest output it can ask for
    out_max = max(c["output"]["max"] for c in mix["classes"])
    chk = check(result_run, picks, model, seed, fp, devices[0], control,
                shape=(cell["engine"]["max_seq"], out_max))
    limit = cell["check"]["limit"]
    correct = bool(picks) and chk["weights_match"] and \
        chk["widest_gap"] <= limit
    lines.append(f"reference check {clock() - t_c:.3f} s over "
                 f"{chk['requests']} requests, {chk['tokens']} tokens"
                 + (" (fp8 control in the program's place)" if control
                    else ""))
    check_out = {"widest_gap": {"value": chk["widest_gap"],
                                "limit": limit},
                 "weights_match": {"value": chk["weights_match"],
                                   "limit": True},
                 "requests_compared": {"value": chk["requests"],
                                       "limit": 1}}
    out = {"correct": correct,
           "attempted": len(result_run["requests"]),
           "failed": result_run["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check_out
    check_lines = [f"check {k} {v['value']!r} limit {v['limit']!r}"
                   for k, v in check_out.items()]
    return {"result": out, "lines": lines, "check_lines": check_lines}
