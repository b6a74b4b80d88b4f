"""The trace reduction: busy union, kernel time, roofline bound, idle
gaps by host span, on hand-built traces and a recorded one."""
import json
import os

import pytest

from bench import trace
from bench.metrics import device_idle_share, paged_attn_roofline

HERE = os.path.dirname(os.path.abspath(__file__))


def small():
    ops = [("fusion.1", 0.0, 1.0), ("paged_chunk_attention", 0.5, 2.0),
           ("fusion.2", 3.0, 4.0), ("paged_chunk_attention", 6.0, 7.0)]
    host = [("bench/window_open", 0.0, 0.0),
            ("bench/control_tick", 2.0, 2.6),
            ("bench/run_step", 4.2, 6.0),
            ("bench/window_close", 8.0, 8.0)]
    return trace.Trace({"/device:TPU:0": ops}, host)


def test_union_and_busy():
    t = small()
    assert trace.union(t.device["/device:TPU:0"], 0.0, 8.0) == \
        [(0.0, 2.0), (3.0, 4.0), (6.0, 7.0)]
    assert trace.busy_s(t, 0.0, 8.0) == pytest.approx(4.0)
    assert trace.busy_s(t, 0.5, 3.5) == pytest.approx(2.0)


def test_kernel_time_and_top_ops():
    t = small()
    assert trace.kernel_s(t, "paged_chunk", 0.0, 8.0) == (2.5, 2)
    assert trace.top_ops(t, 0.0, 8.0)[0] == ["paged_chunk_attention", 2.5]


def test_idle_gaps_by_host_span():
    gaps = dict(map(tuple, trace.idle_gaps(small(), 0.0, 8.0)))
    # 2-3 s: the tick covers 0.6 s; 4-6 s: run_step covers 1.8 s;
    # 7-8 s: no span
    assert gaps == pytest.approx({"bench/control_tick": 0.6,
                                  "bench/run_step": 1.8,
                                  "host/other": 1.6})


class Ctx:
    pass


def test_readers_on_small_trace():
    from bench.reference.ardit import dims_of
    with open(os.path.join(HERE, "..", "configs",
                           "ardit-self-forcing.json")) as f:
        d = dims_of(json.load(f))
    ctx = Ctx()
    ctx.trace, ctx.trace_window = small(), (0.0, 8.0)
    ctx.dims, ctx.peak = d, {"bf16_flops": 197e12,
                             "hbm_bytes_per_s": 819e9}
    ctx.launch_rows = [77 + 4680] * 4
    assert device_idle_share.read(ctx) == pytest.approx(50.0)
    f = 4.0 * 4680 * (77 + 4680) * 1536 * 15 * 4
    assert paged_attn_roofline.read(ctx) == pytest.approx(
        100.0 * (f / 197e12) / 2.5)
    ctx.trace = trace.Trace({}, [])
    assert device_idle_share.read(ctx) is None


def test_load_finds_markers_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window_open"):
        pass
    with jax.profiler.TraceAnnotation("bench/run_step"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench/window_close"):
        pass
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path))
    lo, hi = t.window()
    assert lo < hi
    assert [n for n, _, _ in t.host] == ["bench/window_open",
                                         "bench/run_step",
                                         "bench/window_close"]


def test_recorded_v5e_slice():
    """Half a second of a v5e trace of the benchmark's window: the
    scan's ``while`` holds the layer ops, the paged kernel is found by
    name, and the busy union never exceeds the window."""
    with open(os.path.join(HERE, "data", "trace_v5e.json")) as f:
        raw = json.load(f)
    t = trace.Trace({p: [tuple(e) for e in ops]
                     for p, ops in raw["device"].items()},
                    [tuple(e) for e in raw["host"]])
    lo, hi = t.window()
    busy = trace.busy_s(t, lo, hi)
    assert 0.0 < busy <= hi - lo
    seconds, calls = trace.kernel_s(t, "paged_chunk_attention", lo, hi)
    assert calls > 0 and 0.0 < seconds < busy
    names = [n for n, _ in trace.top_ops(t, lo, hi)]
    assert not any(n.startswith("while") for n in names)
    assert any(n.startswith("paged_chunk_attention") for n in names)
    gaps = trace.idle_gaps(t, lo, hi)
    assert sum(s for _, s in gaps) == pytest.approx(hi - lo - busy)
