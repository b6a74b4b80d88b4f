"""The program's spans and device scopes as the four readers see them:
hand-built slices with known answers, the readers' silence on a run
that has no spans (untraced, or the parent's program), and a slice of a
v5e trace of the benchmark's window recorded on the chip."""
import json
import os

import pytest

from bench import scopes, trace
from bench.metrics import (bmpr_recovery_share, launch_prep_ms,
                           mlp_roofline, segment_attn_roofline)
from bench.reference.ardit import dims_of

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
PLANE = "/device:TPU:0"
READERS = (launch_prep_ms, bmpr_recovery_share, segment_attn_roofline,
           mlp_roofline)


def dims():
    with open(os.path.join(HERE, "..", "configs",
                           "ardit-self-forcing.json")) as f:
        return dims_of(json.load(f))


class Ctx:
    def __init__(self, window, launch_rows):
        self.trace = trace.Trace({}, [])      # a traced run
        self.trace_window = window
        self.launch_rows = launch_rows
        self.dims, self.peak = dims(), PEAK


def small():
    """Two launches of one row on a 10 s window: prep 2 and 4 ms, three
    BMPR decisions (two in speed recovery), and the device ops of two
    steps, each under its scope."""
    spans = [
        ("session/tick", 0.5, 0.6, {}),
        ("bmpr/select", 1.0, 1.0, {"sid": 0, "chunk": 0, "mode": "quality"}),
        ("batcher/launch", 1.0, 1.1, {"rows": 1, "quant": "fp8", "clean": 0}),
        ("batcher/prep", 1.0, 1.002, {}),
        ("bmpr/select", 2.0, 2.0, {"sid": 1, "chunk": 3,
                                   "mode": "speed-recovery"}),
        ("batcher/launch", 3.0, 6.5, {"rows": 1, "quant": "fp8", "clean": 1}),
        ("batcher/prep", 3.0, 3.004, {}),
        ("batcher/sync", 3.1, 6.4, {"sid": 0, "chunk": 0}),
        ("bmpr/select", 7.0, 7.0, {"sid": 0, "chunk": 1,
                                   "mode": "speed-recovery"}),
        ("bmpr/select", 11.0, 11.0, {"sid": 0, "chunk": 2,
                                     "mode": "quality"}),   # after close
    ]
    ops = [("while.1", 1.2, 3.0, ""),                # the loop: not a leaf
           ("fusion.1", 1.2, 1.5, "qkv"),
           ("paged_chunk_attention_pallas.7", 1.5, 2.0, "paged_attn"),
           ("bitcast_reduce_fusion.2", 2.0, 2.4, "segment_attn"),
           ("fusion.2", 2.4, 2.5, "out_proj"),
           ("fusion.3", 2.5, 3.0, "mlp"),
           ("copy.4", 3.5, 4.0, ""),
           ("fusion.3", 4.0, 6.0, "mlp")]
    modules = [("jit_denoise_step_paged(1)", 1.2, 3.0),
               ("jit_denoise_step_paged(1)", 3.5, 6.0)]
    return scopes.Scopes(spans, {PLANE: trace.leaves(ops)},
                         {PLANE: modules})


@pytest.fixture
def use(monkeypatch):
    def put(sc):
        monkeypatch.setattr(scopes, "of", lambda ctx: sc)
    return put


def test_scope_seconds_and_leaves():
    got = small().scope_s(0.0, 10.0)
    assert got == pytest.approx({"qkv": 0.3, "paged_attn": 0.5,
                                 "segment_attn": 0.4, "out_proj": 0.1,
                                 "mlp": 2.5, "": 0.5})
    # clipped to the window
    assert small().scope_s(5.0, 10.0)["mlp"] == pytest.approx(1.0)


def test_readers_on_a_hand_built_slice(use):
    use(small())
    ctx = Ctx((0.0, 10.0), [77 + 4680] * 2)
    assert launch_prep_ms.read(ctx) == pytest.approx(3.0)
    assert bmpr_recovery_share.read(ctx) == pytest.approx(2 / 3)
    d = ctx.dims
    seg = 4.0 * 4680 ** 2 * 1536 * d.n_layers * 2
    mlp = 4.0 * 4680 * 1536 * 8960 * d.n_layers * 2
    assert segment_attn_roofline.read(ctx) == pytest.approx(
        100.0 * seg / 197e12 / 0.4)
    assert mlp_roofline.read(ctx) == pytest.approx(
        100.0 * mlp / 197e12 / 2.5)


def test_hand_counts():
    d = dims()
    # 2.02 and 3.86 TFLOP a row-pass at 4,680 tokens on 15 layers
    assert scopes.segment_attn_flops(d) == pytest.approx(2.018e12, rel=1e-3)
    assert scopes.mlp_flops(d) == pytest.approx(3.864e12, rel=1e-3)


def test_readers_are_silent_without_spans_or_scopes(use):
    ctx = Ctx((0.0, 10.0), [4757])
    ctx.trace = None                                  # untraced run
    assert all(r.read(ctx) is None for r in READERS)
    ctx.trace = trace.Trace({}, [])
    # the parent's program: a trace with device ops but no spans, no scopes
    bare = small()
    use(scopes.Scopes([], {PLANE: [(n, s, e, "")
                                   for n, s, e, _ in bare.ops[PLANE]]},
                      bare.modules))
    assert all(r.read(ctx) is None for r in READERS)


def test_idle_by_innermost_span():
    sc = small()
    tr = trace.Trace({PLANE: [(n, s, e) for n, s, e, _ in sc.ops[PLANE]]},
                     [])
    got = scopes.idle_by_span(sc, tr, 0.0, 10.0)
    # idle: 0-1.2 (tick 0.1; launch 0.1, of which prep 0.002; no span
    # 1.0), 3.0-3.5 (prep 0.004, then launch 0.096, then sync 0.4),
    # 6.0-10.0 (sync 0.4, launch 0.1, no span 3.5)
    assert got == pytest.approx({"session/tick": 0.1,
                                 "batcher/prep": 0.002 + 0.004,
                                 "batcher/launch": 0.098 + 0.096 + 0.1,
                                 "batcher/sync": 0.4 + 0.4,
                                 "host/other": 1.0 + 3.5})
    assert sum(got.values()) == pytest.approx(
        10.0 - trace.busy_s(tr, 0.0, 10.0))


def test_sync_lags_pair_launches_with_steps():
    # the sync of the second launch (its clean pass) waits for the first
    # launch's step, which made the chunk: it ended at 3.0, the sync at 6.4
    sc = small()
    assert scopes.sync_lags(sc, PLANE) == pytest.approx([3.4])
    sc.modules[PLANE].append(("jit_denoise_step_paged(1)", 8.0, 9.0))
    with pytest.raises(ValueError):
        scopes.sync_lags(sc, PLANE)


def test_pick_slice_falls_back_to_the_last_step():
    raw = scopes.pick_slice(small(), 0.0, 10.0, 1.0)
    assert raw["window"] == [3.5, 6.0] and raw["launch_rows"] == 1


def test_fixture_round_trip():
    sc = small()
    raw = json.loads(json.dumps(scopes.fixture(sc, 3.2, 6.2)))
    assert raw["launch_rows"] == 1
    back = scopes.from_json(raw)
    assert [s[0] for s in back.spans if s[0].startswith("batcher/")] == \
        ["batcher/launch", "batcher/prep", "batcher/sync"]
    # the launch that made the synced chunk lies outside the slice
    assert scopes.sync_lags(back, PLANE) == []


def test_recorded_v5e_slice(use):
    """A slice of a traced ``sf-steady`` window on one v5e chip
    (``python3 bench/scopes.py --fixture``): the four readers read it,
    every roofline share stays at or under 100%, the five scopes hold
    time, the paged kernel sits under ``ardit/paged_attn``, and every
    ``batcher/sync`` ends after the step it waits for."""
    with open(os.path.join(HERE, "data", "scopes_v5e.json")) as f:
        raw = json.load(f)
    sc = scopes.from_json(raw)
    use(sc)
    # the slice's steps were launched (and prepared) by the host a little
    # before the device ran them: the readers' window opens at the first
    # span the slice holds
    hi = raw["window"][1]
    lo = min(s[1] for s in sc.spans)
    ctx = Ctx((lo, hi), [0] * raw["launch_rows"])
    got = {r.__name__.rsplit(".", 1)[1]: r.read(ctx) for r in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["launch_prep_ms"] > 0.0
    assert 0.0 <= got["bmpr_recovery_share"] <= 1.0
    assert 0.0 < got["segment_attn_roofline"] <= 100.0
    assert 0.0 < got["mlp_roofline"] <= 100.0
    seconds = sc.scope_s(lo, hi)
    assert all(seconds[s] > 0.0 for s in scopes.SCOPES)
    pallas = [o for o in sc.ops[PLANE] if "paged_chunk_attention" in o[0]]
    assert pallas and all(o[3] == "paged_attn" for o in pallas)
    lags = scopes.sync_lags(sc, PLANE)
    assert lags and min(lags) >= 0.0


def test_scopes_from_the_programs_hlo_in_a_recorded_trace(tmp_path):
    """The trace's ``/host:metadata`` plane holds each program's HLO; an
    instruction's scope is read from its ``op_name`` there."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("ardit/mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("ardit/qkv"):
            return jnp.exp(y).sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    by_program, _ = scopes._op_scopes(scopes._newest(str(tmp_path)))
    programs = [v for k, v in by_program.items() if k.startswith("jit_f(")]
    assert programs
    assert {"mlp", "qkv"} <= set(programs[0].values())
