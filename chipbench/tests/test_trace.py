"""trace.py on a small trace recorded on a TPU v5e by
``record_trace.py``: a 24 ms ``bench.window`` holding a ``cluster.step``
of matmuls, a 20 ms ``bench.idle`` sleep, and one more step."""
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_small.xplane.pb")
SPANS = ("cluster.step", "bench.idle")


@pytest.fixture(scope="module")
def tr():
    return trace.read(DATA, SPANS)


def test_window_and_devices(tr):
    assert len(tr.devices) == 1
    assert tr.devices[0].name == "/device:TPU:0"
    assert abs(tr.window_s - 0.023995918) < 1e-9
    assert 0 < tr.busy_s < tr.window_s


def test_idle_is_labelled_by_host_span(tr):
    idle = dict(tr.idle_by_span())
    # the sleep is idle; the device is never busy there
    assert max(idle, key=idle.get) == "bench.idle"
    assert 0.019 < idle["bench.idle"] < 0.0225
    assert abs(sum(idle.values()) - (tr.window_s - tr.busy_s)) < 1e-9


def test_ops_by_short_name(tr):
    ops = dict(tr.top_ops())
    assert {"fusion", "convolution_tanh_fusion"} <= set(ops)
    assert abs(sum(ops.values()) - tr.busy_s) < 1e-6
    secs, n = tr.op_seconds(lambda name: "fusion" in name)
    assert n >= 2 and 0 < secs <= tr.busy_s


def test_union_and_names():
    covered, gaps = trace._union([(0, 4), (2, 6), (8, 9)], 1, 10)
    assert covered == 6 and gaps == [(6, 8), (9, 10)]
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion"
    assert trace.op_name("%copy-start = (bf16[2]) copy-start(x)") == \
        "copy-start"


def test_no_window_span_is_an_error(monkeypatch):
    monkeypatch.setattr(trace, "WINDOW", "no.such.span")
    with pytest.raises(ValueError):
        trace.read(DATA, SPANS)


def test_chunk_kernel_is_named_in_the_compiled_program():
    """The harness's name scope names the chunk kernel's Mosaic call in
    the program compiled for a (described) v5e, which is the name the
    trace shows and ``chunk_kernel_roofline`` matches."""
    import importlib.util

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, spec
    from repro.kernels import chunk_prefill as CP

    harness.name_chunk_kernel()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    B, S, H, D, P, cap = 1, 64, 2, 128, 64, 256
    shapes = [((B, S, H, D), jnp.bfloat16), ((B, S, H, D), jnp.bfloat16),
              ((B, S, H, D), jnp.bfloat16),
              ((B * cap // P, H, 2, P, D), jnp.bfloat16),
              ((B, cap // P), jnp.int32), ((B, cap), jnp.int32),
              ((B, S), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]

    def step(q, *rest):
        # the kernel inside a larger program, as the engine calls it
        out, pool = CP.chunk_prefill_sharded(None, q * 2, *rest,
                                             interpret=False)
        return out + 1, pool

    text = jax.jit(step).lower(*args).compile().as_text()
    path = f"{spec.HERE}/metrics/chunk_kernel_roofline.py"
    mod_spec = importlib.util.spec_from_file_location("ckr", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    ops = [line.strip() for line in text.splitlines() if " = " in line]
    kernels = [op for op in ops if trace.is_kernel(op)]
    assert len(kernels) == 1
    assert trace.op_name(kernels[0]) == harness.CHUNK_KERNEL
    assert [op for op in ops if mod.is_chunk_kernel(op)] == kernels
