"""Median host wall time of the cluster steps in the window that only
decoded: no prefill chunk ran and no transformation session was open.
Host clock; the engine layer."""
from chipbench.e2e import percentile
from chipbench.metrics_common import decode_only_steps


def read(ctx):
    xs = [1e3 * (s["t1"] - s["t0"]) for s in decode_only_steps(ctx)]
    return percentile(xs, 50) if xs else None
