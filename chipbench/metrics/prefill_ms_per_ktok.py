"""Host wall time of the window's cluster steps that ran a prefill
chunk, per 1000 prompt tokens those chunks carried.  Host clock; the
engine layer."""


def read(ctx):
    run = ctx["run"]
    steps = [s for s in run["steps"] if s["chunks"]]
    tokens = sum(size for s in steps for _, size in s["chunks"])
    if not tokens:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in steps) / (tokens / 1e3)
