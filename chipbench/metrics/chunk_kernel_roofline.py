"""The fused chunk-prefill kernel's share of its roofline: for the
chunks run inside the traced part of the window, the summed least time
(the larger of the kernel's operations over the bf16 peak and its bytes
over HBM bandwidth, the family's ``chunk_kernel``) over the kernel's
device time, summed from its events in the trace.  Nothing to read where
the trace holds no such event.

The kernel is known by name: the harness traces the program's call of
it inside a name scope, so its Mosaic call, and nothing else, is named
``harness.CHUNK_KERNEL`` in the compiled program and in the trace."""
from chipbench import flops, spec
from chipbench.harness import CHUNK_KERNEL
from chipbench.trace import is_kernel, op_name


def is_chunk_kernel(text):
    return is_kernel(text) and op_name(text) == CHUNK_KERNEL


def read(ctx):
    tr, run, m, peak = ctx["trace"], ctx["run"], ctx["model"], ctx["peak"]
    if tr is None or run["trace_t"] is None:
        return None
    secs, count = tr.op_seconds(is_chunk_kernel)
    if not count or secs <= 0:
        return None
    fam = spec.family(m)
    a, b = run["trace_t"]
    least = 0.0
    for s in run["steps"]:
        if a <= s["t0"] and s["t1"] <= b:
            for start, n in s["chunks"]:
                k = fam.chunk_kernel(m, start, n)
                least += flops.least_time(k["flops"], k["bytes"], peak)
    if not least:
        return None
    return 100.0 * least / secs
