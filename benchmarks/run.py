"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,...]

Prints ``name,us_per_call,derived`` CSV rows (per the harness contract).
The roofline (§Roofline) runs in a separate process because it needs 512
placeholder devices: ``python -m benchmarks.roofline``.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_ablation, bench_calibrate, bench_e2e,
                        bench_kv_transform, bench_overall_cost,
                        bench_scheduler, bench_tp_tradeoff,
                        bench_weights)
from repro.launch.compile_cache import use_compile_cache

MODULES = {
    "table1": bench_tp_tradeoff,
    "fig9": bench_kv_transform,
    "fig10_table3": bench_weights,
    "fig11": bench_overall_cost,
    "fig12": bench_scheduler,
    "fig14": bench_e2e,
    "ablation": bench_ablation,
    "calibration": bench_calibrate,
}


def emit_trajectory(out: str | None) -> str:
    """Write the schema-versioned perf-trajectory JSON (the CI artifact
    ``tools/check_bench_regression.py`` gates against the committed
    ``benchmarks/BENCH_baseline.json``).  Returns the path written."""
    import datetime
    import json

    payload = bench_e2e.trajectory_payload()
    payload["generated"] = datetime.date.today().isoformat()
    path = out or f"BENCH_{payload['generated']}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: import every benchmark module (done "
                         "at import time above) and run the fast KV-"
                         "transform accounting + data-plane benchmark")
    ap.add_argument("--trajectory", action="store_true",
                    help="emit the schema-versioned BENCH_<date>.json "
                         "perf trajectory (deterministic replay "
                         "scenarios with regression gates)")
    ap.add_argument("--out", default=None,
                    help="output path for --trajectory (default "
                         "BENCH_<date>.json in the working directory)")
    args = ap.parse_args()
    use_compile_cache()
    if args.trajectory:
        print(f"trajectory,{emit_trajectory(args.out)}")
        return
    if args.smoke and not args.only:
        names = ["fig9"]
    else:
        names = args.only.split(",") if args.only else list(MODULES)

    failures = 0
    for name in names:
        mod = MODULES[name]
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},FAIL,{type(e).__name__}: {e}")
            continue
        us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        for r in rows:
            head, rest = r.split(",", 1)
            print(f"{head},{us:.1f},{rest}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
