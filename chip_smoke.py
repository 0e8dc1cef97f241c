#!/usr/bin/env python3
"""Chip smoke test: the serving main path at published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # live TP1x4 -> TP4 merge, four chips

One chip: gemma-2b (18 layers, d_model 2048, vocab 256000, bf16) with
seeded random weights serves a few seeded requests through the
``ClusterEngine`` -> ``Engine`` -> model -> Pallas kernel path that
``repro.launch.serve`` builds.  A 512-token chunk budget sends the long
prompt through chunked prefill and the fused chunk-prefill kernel.  The
next-token logits the engine sampled from are checked against the same
model's plain jnp whole-prompt path run with float32 activations, and
the compiled kernel alone against its dense float32 oracle on one
continuation chunk, beside a control with one prefix page dropped.

Four chips (``--four-chips``, and nothing else): four gemma-2b TP1
engines; a request over the TP1 ceiling makes the scheduler merge them
into one TP4 engine (a live ``Engine.transform`` session with the
page-migration kernels), and split back once it drains.  One short
request finishes inside the merge session, so the long prompt's chunks
run the per-layer session path.  The token streams are compared with an
engine started at TP4 on the same requests.

Everything runs in this one process.  The script exits non-zero, and
prints no result line, when JAX finds no TPU, when any phase fails, or
when it outlives ``--time-limit`` (it then dumps every thread's stack).
The last line of a passing run is ``{"ok": true, "device": {...}}``.
The compile cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "gemma-2b"
SEED = 0
PAGE = 64            # tokens per KV page
CHUNK = 512          # prefill tokens per engine step
GEN = 16             # new tokens per request
# bf16 serving vs float32 reference: the largest absolute logit error
# must stay under this fraction of the reference's largest |logit|.
# Sound runs read 0.013-0.015 (gemma-2b, TPU v5e) and 0.015-0.018 (18
# layers at d_model 512, CPU); the same CPU run with the kernel dropping
# the prefix page before each chunk reads 0.21.
REL_TOL = 0.05
# largest bf16-vs-float32 logit error of a sound run, rounded up: two
# bf16 runs may pick different tokens only where the picks' logits are
# closer than twice it
BF16_ERR = 0.02
TIE_TOL = 2 * BF16_ERR
# compiled chunk kernel vs its dense float32 oracle, bf16 inputs and
# outputs, as a fraction of the oracle's largest |output|: the CPU
# interpreter reads 0.003 sound and 0.31 with one prefix page dropped
KERNEL_TOL = 0.02


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def record_prefill_logits(engine) -> dict:
    """rid -> the float32 next-token logits the engine sampled the
    request's first token from (whole-prompt or last chunk)."""
    import numpy as np

    seen = {}
    finish = engine._finish_prefill

    def record(req, slot, logits):
        seen[req.rid] = np.asarray(logits[0, -1], np.float32)
        finish(req, slot, logits)

    engine._finish_prefill = record
    return seen


def reference_logits(engine, prompt) -> "np.ndarray":
    """The same weights through the plain jnp whole-prompt path
    (``models.model.prefill``) in float32: the embedding is upcast, so
    every matmul promotes its bf16 weight to float32, and runs at full
    float32 precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    cfg = dataclasses.replace(engine.cfg, dtype="float32")
    params = dict(engine.params,
                  embed=engine.params["embed"].astype(jnp.float32))
    cache = M.init_decode_caches(cfg, engine.plan, 1, engine.max_seq_alloc,
                                 engine.page_tokens, engine.layout)
    fn = jax.jit(lambda p, t, c: M.prefill(p, cfg, engine.plan,
                                           {"tokens": t}, c,
                                           engine.layout)[0])
    # a TPU runs float32 matmuls as one bf16 pass unless told otherwise
    with jax.default_matmul_precision("highest"):
        out = fn(params, jnp.asarray(prompt, jnp.int32)[None], cache)
    return np.asarray(out[0, -1], np.float32)


def logit_error(served, ref, vocab: int):
    """(max abs error, that error over max |ref logit|), real vocab."""
    import numpy as np
    s, r = served[:vocab], ref[:vocab]
    err = float(np.max(np.abs(s - r)))
    return err, err / float(np.max(np.abs(r)))


def chunk_kernel_compiled(engine, first_chunk: bool) -> bool:
    """Whether the engine's chunk-prefill program, as lowered for the
    current mesh, calls the compiled Mosaic kernel."""
    import jax.numpy as jnp

    tokens = jnp.zeros((1, CHUNK), jnp.int32)
    start = jnp.full((1,), 0 if first_chunk else CHUNK, jnp.int32)
    txt = engine._prefill_chunk_jit.lower(
        engine.params, tokens, start, engine._extract_slot_cache(0),
        first_chunk=first_chunk, sp=engine.par_layout.sp,
        mesh=engine.mesh).as_text()
    return "tpu_custom_call" in txt


def kernel_check(interpret: bool = False) -> None:
    """The chunk kernel against its dense float32 oracle
    (``kernels.ref.chunk_prefill_ref``) at gemma-2b attention widths: two
    batch rows, a 512-token continuation chunk after a 2560-token cached
    prefix.  The control runs the oracle with the prefix's last page
    dropped; the tolerance must sit between the two readings, and the
    scattered pool must equal the oracle's byte for byte."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import chunk_prefill as CP
    from repro.kernels import ref as R

    B, HQ, KV, DH, SLOT = 2, 8, 1, 256, 4096
    prefix, mps = 5 * CHUNK, SLOT // PAGE
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q = jax.random.normal(ks[0], (B, CHUNK, HQ, DH), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, CHUNK, KV, DH), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, CHUNK, KV, DH), jnp.bfloat16)
    pool = jax.random.normal(ks[3], (B * mps, KV, 2, PAGE, DH),
                             jnp.bfloat16)
    table = jnp.arange(B * mps, dtype=jnp.int32).reshape(B, mps)
    t = jnp.arange(SLOT, dtype=jnp.int32)
    kvpos = jnp.broadcast_to(jnp.where(t < prefix, t, -1), (B, SLOT))
    qpos = jnp.broadcast_to(prefix + jnp.arange(CHUNK, dtype=jnp.int32),
                            (B, CHUNK))
    kern = jax.jit(functools.partial(CP.chunk_prefill_attention,
                                     interpret=interpret))
    out_k, pool_k = kern(q, k, v, pool, table, kvpos, qpos)
    oracle = jax.jit(R.chunk_prefill_ref)
    with jax.default_matmul_precision("highest"):
        out_r, pool_r = oracle(q, k, v, pool, table, kvpos, qpos)
        dropped = kvpos.at[:, prefix - PAGE:prefix].set(-1)
        out_f, _ = oracle(q, k, v, pool, table, dropped, qpos)
    out_k, out_r, out_f = (np.asarray(o, np.float32)
                           for o in (out_k, out_r, out_f))
    scale = float(np.max(np.abs(out_r)))
    err = float(np.max(np.abs(out_k - out_r))) / scale
    fault = float(np.max(np.abs(out_f - out_r))) / scale
    same = bool(jnp.array_equal(pool_k, pool_r))
    print(f"[one-chip] chunk kernel vs dense oracle: rel err {err:.5f} "
          f"(tol {KERNEL_TOL}); last prefix page dropped: {fault:.5f}; "
          f"scattered pool identical {same}")
    assert same, "the kernel's pool scatter differs from the oracle's"
    assert err <= KERNEL_TOL < fault, (err, KERNEL_TOL, fault)


def tree_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


def requests(spec):
    from repro.serving.request import ServeRequest
    return [ServeRequest(rid=rid, prompt=list(p), max_new_tokens=n)
            for rid, p, n in spec]


def serve_one_chip(cfg, devices, *, max_seq: int, max_batch: int,
                   long_len: int, short_lens, n_short: int,
                   clock: CompileClock) -> None:
    """ClusterEngine over one device: seeded short prompts plus one long
    prompt, all served to completion; reference check on two prompts."""
    import jax
    import numpy as np

    from repro.launch.serve import build_cluster
    from repro.serving import tracing

    t0 = time.perf_counter()
    cluster = build_cluster(cfg, devices, instances=1, max_seq=max_seq,
                            max_batch=max_batch, page_tokens=PAGE,
                            prefill_budget=CHUNK,
                            rng=jax.random.PRNGKey(SEED))
    eng = cluster.engines[0]
    assert eng.pallas_kernels, "chunk prefill and decode must take the kernels"
    jax.block_until_ready(eng.params)
    print(f"[one-chip] setup {time.perf_counter() - t0:.1f} s: "
          f"params {tree_bytes(eng.params)} B, "
          f"KV pool {tree_bytes(eng.caches)} B "
          f"({max_batch} slots x {max_seq} tokens, page {PAGE})")

    rng = np.random.default_rng(SEED)
    spec = [(i, rng.integers(0, cfg.vocab_size, size=int(
        rng.choice(short_lens))), GEN) for i in range(n_short)]
    spec.insert(n_short // 2,
                (n_short, rng.integers(0, cfg.vocab_size, size=long_len),
                 GEN))
    reqs = requests(spec)
    served = record_prefill_logits(eng)

    c0, t0 = clock.seconds, time.perf_counter()
    first_span = tracing.RECORDER.opened
    m = cluster.run(reqs, max_steps=2_000)
    wall = time.perf_counter() - t0
    chunks = tracing.rolled_up(
        [s for s in tracing.RECORDER.spans() if s.index >= first_span],
        "engine.chunk", "compiles")
    compiled = clock.seconds - c0
    assert all(r.finished and len(r.generated) == GEN for r in reqs), (
        [(r.rid, r.state, len(r.generated)) for r in reqs])
    n_prompt = sum(len(r.prompt) for r in reqs)
    n_gen = sum(len(r.generated) for r in reqs)
    print(f"[one-chip] served {int(m['finished'])}/{len(reqs)} requests: "
          f"{n_prompt} prompt + {n_gen} generated tokens in "
          f"{cluster.steps} steps; prompt lengths "
          f"{[len(r.prompt) for r in reqs]}")
    print(f"[one-chip] wall {wall:.2f} s = compile {compiled:.2f} s "
          f"+ serve {wall - compiled:.2f} s; chunk compiles "
          f"{sum(n for _, n in chunks)}, chunk calls {len(chunks)}")
    stats = devices[0].memory_stats() or {}
    print(f"[one-chip] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")

    kern = {first: chunk_kernel_compiled(eng, first)
            for first in (True, False)}
    print(f"[one-chip] chunk jit HLO has tpu_custom_call: "
          f"first chunk {kern[True]}, continuation {kern[False]}")
    assert all(kern.values()), "chunked prefill is not the compiled kernel"
    kernel_check()

    long_r = next(r for r in reqs if len(r.prompt) == long_len)
    short_r = next(r for r in reqs if len(r.prompt) != long_len)
    for name, r in (("long (chunked, kernel)", long_r),
                    ("short (whole prompt)", short_r)):
        ref = reference_logits(eng, r.prompt)
        err, rel = logit_error(served[r.rid], ref, cfg.vocab_size)
        top = (int(np.argmax(served[r.rid][:cfg.vocab_size]))
               == int(np.argmax(ref[:cfg.vocab_size])))
        print(f"[one-chip] reference {name}, {len(r.prompt)} tokens: "
              f"max abs err {err:.5f}, rel {rel:.5f} (tol {REL_TOL}), "
              f"top-1 agrees {top}")
        assert rel <= REL_TOL, f"{name}: relative error {rel} > {REL_TOL}"


def first_divergence(a, b):
    n = min(len(a), len(b))
    return next((i for i in range(n) if a[i] != b[i]),
                None if len(a) == len(b) else n)


def merge_four_chips(cfg, devices, *, quantum: int, max_batch: int,
                     short_len: int, long_len: int,
                     clock: CompileClock) -> None:
    """Four TP1 engines; a request over the TP1 ceiling merges them into
    TP4 and the drain splits them back.  Streams vs a TP4-started
    engine."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.scheduler import PrefillPolicy, ScaleDown, ScaleUp
    from repro.launch.serve import build_cluster
    from repro.models import model as M
    from repro.serving.engine import Engine

    W = len(devices)
    t0 = time.perf_counter()
    cluster = build_cluster(cfg, devices, instances=W, max_seq=quantum,
                            max_batch=max_batch, page_tokens=PAGE,
                            prefill_budget=CHUNK,
                            rng=jax.random.PRNGKey(SEED))
    print(f"[four-chip] setup {time.perf_counter() - t0:.1f} s: {W} TP1 "
          f"engines, TP1 ceiling {cluster.engines[0].max_seq_at(1)} tok, "
          f"TP{W} ceiling {cluster.engines[0].max_seq_at(W)} tok")
    rng = np.random.default_rng(SEED)
    # request 0 finishes inside the merge session (a session step per
    # layer) and frees the slot the long prompt waits for, whose chunks
    # then run the per-layer session path; the rest decode across its end
    spec = [(i, rng.integers(0, cfg.vocab_size, size=short_len),
             GEN // 2 if i == 0 else 2 * GEN) for i in range(W)]
    spec.append((W, rng.integers(0, cfg.vocab_size, size=long_len), GEN))
    live = requests(spec)
    session_chunks = []
    for e in cluster.engines:
        def counted(*a, _run=e._run_chunk_layers):
            session_chunks.append(a[0])
            return _run(*a)
        e._run_chunk_layers = counted

    c0, t0 = clock.seconds, time.perf_counter()
    for r in live[:W]:
        cluster.submit(r)
    for _ in range(3):       # every engine decoding before the merge
        cluster.step()
    cluster.submit(live[W])  # over the TP1 ceiling: the merge trigger
    merges = [a for a in cluster.actions
              if isinstance(a, ScaleUp) and a.donor_iids]
    assert merges and merges[0].tp_to == W, cluster.actions
    target = cluster._engine(merges[0].iid)

    def progress(what: str) -> None:
        print(f"[four-chip] {what}: step {cluster.steps}, "
              f"{time.perf_counter() - t0:.1f} s, TPs "
              f"{[e.tp for e in cluster.engines]}, transforming "
              f"{[e.iid for e in cluster.engines if e.transforming]}, "
              f"finished {sum(r.finished for r in live)}/{len(live)}")

    progress(f"merge issued {merges[0]}")
    while not cluster.idle and cluster.steps < 2_000:
        cluster.step()
        progress("serving")
    progress("drained")
    m = cluster.run(max_steps=5_000)     # quiet window: Alg 2 splits
    wall = time.perf_counter() - t0
    compiled = clock.seconds - c0
    downs = [a for a in cluster.actions if isinstance(a, ScaleDown)]
    assert downs, "the merged engine never split back"
    assert all(e.tp == 1 and not e.parked for e in cluster.engines)
    assert all(r.finished for r in live)
    assert any(r.kernel_plane for r in target.transform_reports), (
        "the merge did not take the page-migration kernels")
    assert session_chunks, "no prefill chunk ran inside the merge session"
    print(f"[four-chip] split {downs[0]}")
    print(f"[four-chip] wall {wall:.2f} s = compile {compiled:.2f} s + "
          f"serve {wall - compiled:.2f} s; merge_wall_s "
          f"{m['merge_wall_s']:.3f}; stall_steps {cluster.stall_steps}; "
          f"tokens_during_session {cluster.tokens_during_session}; "
          f"session_steps {cluster.session_steps}; prefill chunks "
          f"inside a session {len(session_chunks)}")
    for e in cluster.engines:
        for rec in e.transform_log:
            print(f"[four-chip] transform_log engine {e.iid}: " + json.dumps(
                {k: v for k, v in rec.items() if k != "step_drifts"}))
    assert cluster.stall_steps == 0, cluster.stall_steps

    params, plan = cluster._params_src, cluster.plan
    streams = {r.rid: list(r.generated) for r in live}
    del cluster, target, merges, downs, live
    gc.collect()

    ref = Engine(cfg, params=params, max_batch=max_batch,
                 max_seq=quantum * W, page_tokens=PAGE, devices=devices,
                 plan=plan, prefill_policy=PrefillPolicy(
                     token_budget=CHUNK, mode="mixed",
                     long_threshold=quantum * W, order="sjf"))
    print(f"[four-chip] reference engine built, "
          f"{time.perf_counter() - t0:.1f} s")
    ref.transform(W)
    while ref.transforming:
        ref.step()
    assert ref.tp == W
    print(f"[four-chip] reference engine at TP{W}, "
          f"{time.perf_counter() - t0:.1f} s")
    kern = chunk_kernel_compiled(ref, first_chunk=False)
    print(f"[four-chip] TP{W} chunk jit HLO has tpu_custom_call: {kern}")
    assert kern, "chunked prefill at TP4 is not the compiled kernel"
    want = requests(spec)
    for r in want:
        ref.submit(r)
    ref.run_until_done(5_000)
    for r in want:
        got = streams[r.rid]
        i = first_divergence(r.generated, got)
        if i is None:
            print(f"[four-chip] request {r.rid} ({len(r.prompt)} prompt "
                  f"tokens): {len(got)} tokens identical to TP{W}")
            continue
        ctx = list(r.prompt) + r.generated[:i]
        sub = M.init_decode_caches(cfg, plan, 1, ref.max_seq_alloc,
                                   PAGE, ref.layout)
        logits = np.asarray(ref._prefill_whole_jit(
            ref.params, jnp.asarray(ctx, jnp.int32)[None], sub)[0][0, -1],
            np.float32)[:cfg.vocab_size]
        top2 = np.sort(logits)[-2:]
        scale = float(np.max(np.abs(logits)))
        gap = abs(float(logits[r.generated[i]] - logits[got[i]]))
        print(f"[four-chip] request {r.rid}: first difference at "
              f"generated token {i} ({r.generated[i]} at TP{W} vs "
              f"{got[i]} merged); top-2 logit gap "
              f"{float(top2[1] - top2[0]):.5f}, gap between the two "
              f"picks {gap:.5f} = {gap / scale:.5f} of max |logit| "
              f"(tie tol {TIE_TOL})")
        assert gap <= TIE_TOL * scale, f"request {r.rid}: not a near tie"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip merge phase")
    ap.add_argument("--time-limit", type=float, default=1100.0,
                    help="seconds before the script dumps its stacks "
                         "and exits with an error")
    args = ap.parse_args()
    sys.stdout.reconfigure(line_buffering=True)
    faulthandler.dump_traceback_later(args.time_limit, exit=True)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devs)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    print(f"[setup] compile cache {use_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    print(f"[setup] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} q heads / {cfg.num_kv_heads} kv "
          f"heads x {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}; device {devs[0].device_kind} "
          f"x {len(devs)}")
    if args.four_chips:
        merge_four_chips(cfg, devs[:4], quantum=1024, max_batch=4,
                         short_len=128, long_len=7 * CHUNK, clock=clock)
    else:
        serve_one_chip(cfg, devs[:1], max_seq=4096, max_batch=8,
                       long_len=6 * CHUNK, short_lens=(128, 384),
                       n_short=7, clock=clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
