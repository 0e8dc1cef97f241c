"""Decode steps' share of the roofline: over the window's decode-only
cluster steps, the summed least time (the larger of their model FLOPs
over the bf16 peak and their bytes over HBM bandwidth; bytes are the
weights once plus the keys and values of the batch's live contexts,
not its reservation) over their summed measured host wall time."""
from chipbench import flops, spec
from chipbench.metrics_common import decode_only_steps


def read(ctx):
    m, peak = ctx["model"], ctx["peak"]
    steps = decode_only_steps(ctx)
    if not steps:
        return None
    fam = spec.family(m)
    least = sum(flops.least_time(
        sum(fam.decode_flops(m, c) for c in s["decode_contexts"]),
        fam.decode_step_bytes(m, s["decode_contexts"]), peak)
        for s in steps)
    return 100.0 * least / sum(s["t1"] - s["t0"] for s in steps)
