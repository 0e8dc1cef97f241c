#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace.py`` reads.

    python3 chipbench/tests/record_trace.py <out_dir>

Inside a ``bench.window`` span: a ``cluster.step`` span that runs a few
matmuls on the device, then a ``bench.idle`` span in which the device
sits idle for 20 ms.  Needs a TPU; the ``.xplane.pb`` it writes under
``<out_dir>`` is what ``tests/data/`` keeps.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("cluster.step"):
            for _ in range(4):
                x = f(x)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("cluster.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
