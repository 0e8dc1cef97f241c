"""Share of the traced window in which the device was idle while the
program did host work: the device's idle gaps (``chipbench/trace.py``)
intersected with the time in which the innermost program span was any
but ``engine.sync`` (the host waiting on the device), averaged over the
chips.  The program's spans come from its recorder, on
``perf_counter``'s clock, moved onto the profiler's by one offset
fitted on the harness's ``cluster.step`` spans
(``program_spans.offset_ns``).  Nothing to read without a trace,
without program spans, or where the fit fails."""
from chipbench import program_spans as PS


def read(ctx):
    tr, run = ctx["trace"], ctx["run"]
    if tr is None or run["trace_t"] is None or tr.window_s <= 0:
        return None
    off = PS.offset_ns(run, tr)
    spans = PS.window_spans(*run["trace_t"])
    if off is None or not spans:
        return None
    host = PS.merged((a + off, b + off) for a, b, name
                     in PS.innermost(spans) if name != PS.SYNC)
    idle = sum(PS.overlap_ns(d.gaps, host) for d in tr.devices)
    return 100.0 * idle * 1e-9 / len(tr.devices) / tr.window_s
