"""Share of the KV that decode attention reads which belongs to live
context: over the window's ``engine.decode`` spans, the summed
``kv_live_tokens`` (the decoded rows' context lengths) over the summed
``kv_read_tokens`` (what decode attention reads per layer: through the
paged-attention kernel each decoding row's context rounded up to whole
pages, every page once a ring wraps, and one page of every other row;
on the program's jnp paths every row's whole reservation).  Program
counters; nothing to read where the program records none."""
from chipbench import program_spans as PS


def read(ctx):
    run = ctx["run"]
    spans = PS.window_spans(run["t0"], run["t1"])
    if not spans:
        return None
    dec = [s.attrs for s in spans if s.name == "engine.decode"]
    reserved = sum(a.get("kv_read_tokens", 0) for a in dec)
    if not reserved:
        return None
    return 100.0 * sum(a.get("kv_live_tokens", 0) for a in dec) / reserved
