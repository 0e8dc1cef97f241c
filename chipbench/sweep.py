#!/usr/bin/env python3
"""Find a cell's knee: the highest Poisson rate its engine sustains.

    python3 chipbench/sweep.py --workload <cell> --rates 0.5,1,1.5 \
        --seconds 20 [--seed n]

One process builds the cell once, warms it up, then runs one open-loop
window per rate (every class of the mix at that absolute rate), each
followed by a drain to idle.  For each
rate it prints the requests due, how many had not finished when the
window closed, the TTFT and queue-wait tails, the inter-token p95 and
the tokens per second.  The knee is the highest rate whose backlog does
not grow through the window; a cell's traffic file records it.  This is
a sizing tool: the benchmark's runs never call it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax

    from chipbench import e2e, harness, spec, traffic
    from chipbench.run import use_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    use_cache()
    cell = spec.cell(args.workload)
    model = cell["model"]
    devs = devs[:cell["chips"]]
    clk = harness.CompileClock()
    probe = harness.Probe(annotate=False)
    cluster, _, split = harness.build(cell, model, args.seed, devs, clk)
    probe.install_all(cluster)
    harness.warm_up(cluster, cell["mix"], model["vocab_size"],
                    args.seed, probe)
    harness.log(f"setup {time.perf_counter() - T_START:.1f} s {split}; "
                f"peak_bytes_in_use {harness._device_peak(devs)}")
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell["mix"])
        for c in mix["classes"]:
            c["arrival"]["rate"] = rate
        arr = traffic.schedule(mix, args.seconds, args.seed)
        ids = traffic.prompt_ids(arr, model["vocab_size"], args.seed)
        c0 = clk.mark()
        run = harness.window(cluster, arr, ids, args.seconds, probe, None,
                             None)
        c1 = clk.mark()
        backlog = sum(1 for r in run["requests"]
                      if not any(t <= run["t1"] for t in r["tokens"][-1:])
                      or len(r["tokens"]) < r["output_len"])
        waits = [(r["prefill_start"] or float("inf")) - r["due"]
                 for r in run["requests"]]
        t = time.perf_counter()
        while not cluster.idle and time.perf_counter() - t < 120:
            cluster.step()
        row = {"rate": rate, "due": len(run["requests"]),
               "unfinished_at_close": backlog,
               "ttft_p50_s": e2e.percentile(e2e.ttfts(run["requests"]), 50),
               "ttft_p90_s": e2e.percentile(e2e.ttfts(run["requests"]), 90),
               "queue_wait_p90_s": e2e.percentile(waits, 90),
               "itl_p95_ms": e2e.METRICS["itl_p95_ms"](run),
               "tok_s": e2e.METRICS["tok_s"](run),
               "steps": len(run["steps"]),
               "compiles_in_window": c1[0] - c0[0],
               "drain_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
    harness.log(f"peak_bytes_in_use {harness._device_peak(devs)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
