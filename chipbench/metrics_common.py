"""Step selections that several per-layer readers share."""


def decode_only_steps(ctx):
    run = ctx["run"]
    return [s for s in run["steps"]
            if s["decode_only"]]
