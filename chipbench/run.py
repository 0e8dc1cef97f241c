#!/usr/bin/env python3
"""The on-chip serving benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the cell's chips.  It draws the weights from the seed
on the device, builds the program's serving cluster over them, warms up
every shape the cell's traffic can use, runs an open-loop window of
``--seconds`` on the wall clock, and compares what the window served
with the plain float32 reference.  Progress goes to standard error; the
last lines there are each number compared, beside its limit.  The last
line of standard output is the result: the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics from a profiled run
(``--trace 1``).

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.  JAX's persistent compilation cache lives
in ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".jax_cache")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the fp8 control's first choices in place "
                    "of the served tokens (such a run is not correct)")
    return ap.parse_args(argv)


def use_cache() -> None:
    import jax
    os.makedirs(CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the program reads this when it places its own cache: it is given
    # the benchmark's
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    from chipbench import harness, spec

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devs[0].platform}); nothing "
              "was run", file=sys.stderr)
        return 2
    cell = spec.cell(args.workload)
    if len(devs) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2
    use_cache()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devs, T_START, control=bool(args.control))
    for line in out["lines"] + out["check_lines"]:
        harness.log(line)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
