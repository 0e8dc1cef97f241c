"""Model FLOP utilization of the whole window: the model operations of
every prompt token prefilled and every token decoded in the window
(the family's counts, published widths) over window length x chips x
the chip's bf16 peak."""
from chipbench import spec


def read(ctx):
    run, m, peak = ctx["run"], ctx["model"], ctx["peak"]
    fam = spec.family(m)
    t0, t1 = run["t0"], run["t1"]
    total = 0
    for s in run["steps"]:
        total += sum(fam.prefill_flops(m, a, n) for a, n in s["chunks"])
        total += sum(fam.decode_flops(m, c) for c in s["decode_contexts"])
    if not total:
        return None
    return 100.0 * total / ((t1 - t0) * ctx["chips"]
                            * peak["bf16_flops_per_s"])
