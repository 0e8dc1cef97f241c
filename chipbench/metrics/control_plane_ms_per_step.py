"""Control-plane host time per cluster step: the summed self time of
the program's ``cluster.route``, ``cluster.plan`` and
``cluster.finalize`` spans in the window (routing and the ``decide_*``
ladder, the Alg 2 and layout scans, loan and spill finalizing, the
measured-cost feed) over the window's steps.  Program spans; nothing to
read where the program records none."""
from chipbench import program_spans as PS


def read(ctx):
    run = ctx["run"]
    spans = PS.window_spans(run["t0"], run["t1"])
    if not spans or not run["steps"]:
        return None
    own = PS.self_ns(spans)
    cp = [own[s.index] for s in spans if s.name in PS.CONTROL_PLANE]
    if not cp:
        return None
    return 1e-6 * sum(cp) / len(run["steps"])
