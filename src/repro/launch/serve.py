"""Serving launcher: a thin CLI over the ``ClusterEngine`` control plane.

The §5 scheduler (``GygesScheduler`` by default) routes every request and
decides every transformation; this module only parses arguments, builds
the trace, and prints what the control plane did.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
        [--smoke] [--instances N] [--requests 16] [--long-every 5]

It serves on whatever devices JAX finds.  One device gives one TP1
instance; with several (a TPU host, or fake CPU devices from
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) it demonstrates
the full dynamic: short requests spread over TP1 instances, a long
request triggers a scheduler-issued live scale-up (``Engine.transform``,
one §4.3 schedule step per decode iteration), and the Alg-2 scan
decomposes the instance once the long request drains.  ``--smoke`` swaps
in the reduced test-size config.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.scheduler import SCHEDULERS, PrefillPolicy, ScaleUp
from repro.launch.compile_cache import use_compile_cache
from repro.serving.cluster import ClusterEngine
from repro.serving.request import ServeRequest


def build_trace(n: int, long_every: int, cluster: ClusterEngine,
                gen_tokens: int, seed: int = 0) -> list:
    """Mixed short/long ServeRequests sized against the cluster's
    admission ceilings: shorts fit a TP1 instance, longs need max TP."""
    rng = np.random.default_rng(seed)
    base = cluster.engines[0].max_seq_at(1)
    full = cluster.engines[0].max_seq_at(cluster.engines[0].max_tp)
    vocab = cluster.cfg.vocab_size
    reqs = []
    for i in range(n):
        if long_every and (i + 1) % long_every == 0:
            plen = max(1, full - gen_tokens - 1)
        else:
            plen = int(rng.integers(2, max(3, base - gen_tokens)))
        prompt = rng.integers(0, vocab, size=plen).tolist()
        reqs.append(ServeRequest(rid=i, prompt=prompt,
                                 max_new_tokens=gen_tokens))
    return reqs


def build_cluster(cfg, devices, *, instances: int, max_seq: int,
                  max_batch: int = 0, page_tokens: int = 16,
                  prefill_budget: int = 0, prefill_mode: str = "mixed",
                  scheduler: str = "gyges",
                  rng: Optional[jax.Array] = None) -> ClusterEngine:
    """The serving control plane over ``devices``: ``instances`` TP1
    engines of ``len(devices) // instances`` devices each, ``max_batch``
    slots apiece (0 = one per device), and a chunked-prefill policy when
    ``prefill_budget`` is set (0 = whole-prompt prefill)."""
    w = len(devices) // instances
    policy = (PrefillPolicy(token_budget=prefill_budget, mode=prefill_mode,
                            long_threshold=max_seq // w or 1, order="sjf")
              if prefill_budget else None)
    return ClusterEngine(
        cfg, devices, n_instances=instances,
        max_batch=max_batch or w, max_seq=max_seq,
        page_tokens=page_tokens,
        scheduler=None if scheduler == "gyges"
        else SCHEDULERS[scheduler](),
        prefill_policy=policy, rng=rng)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ASSIGNED_ARCHS)
    ap.add_argument("--instances", type=int, default=0,
                    help="TP1 instances (0 = two when the devices allow, "
                         "else one)")
    ap.add_argument("--scheduler", default="gyges",
                    choices=sorted(SCHEDULERS))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--long-every", type=int, default=5,
                    help="every Nth request is long-context (0 = none)")
    ap.add_argument("--gen-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=0,
                    help="slots per instance (0 = one per device)")
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="chunked-prefill token budget per engine step "
                         "(0 = whole-prompt prefill)")
    ap.add_argument("--prefill-mode", default="mixed",
                    choices=("prefill", "decode", "mixed"),
                    help="prefill/decode priority when budgeted")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced test-size model config")
    ap.add_argument("--dtype", default=None,
                    help="override the config's dtype (e.g. float32)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    devs = jax.devices()
    instances = args.instances or min(2, len(devs))
    w = len(devs) // instances
    cluster = build_cluster(
        cfg, devs, instances=instances, max_seq=args.max_seq,
        max_batch=args.max_batch,
        prefill_budget=args.prefill_budget, prefill_mode=args.prefill_mode,
        scheduler=args.scheduler)
    print(f"[serve] {cfg.name}: {instances} instances x {w} devices, "
          f"scheduler={cluster.scheduler.name}, "
          f"TP1 ceiling {cluster.engines[0].max_seq_at(1)} tok, "
          f"TP{w} ceiling {cluster.engines[0].max_seq_at(w)} tok")

    trace = build_trace(args.requests, args.long_every, cluster,
                        args.gen_tokens)
    n_long = sum(1 for r in trace
                 if cluster.scheduler.is_long(r.total_tokens))
    print(f"[serve] trace: {len(trace)} requests ({n_long} long)")
    seen = 0
    for r in trace:
        cluster.submit(r)
        cluster.step()
        for act in cluster.actions[seen:]:
            kind = "scale-up" if isinstance(act, ScaleUp) else "scale-down"
            print(f"[serve] step {cluster.steps}: {kind} instance "
                  f"{act.iid} -> TP{act.tp_to} ({act.reason})")
        seen = len(cluster.actions)
    m = cluster.run()   # drain + Alg-2 quiet window
    for act in cluster.actions[seen:]:
        kind = "scale-up" if isinstance(act, ScaleUp) else "scale-down"
        print(f"[serve] drain: {kind} instance {act.iid} -> TP{act.tp_to} "
              f"({act.reason})")
    print(f"[serve] final TPs: {[e.tp for e in cluster.engines]}")
    print("[serve] " + ", ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in m.items()))


if __name__ == "__main__":
    main()
