"""The whole run at reduced widths on the CPU: the timed path against the
float32 reference, its fp8 control, a planted fault, the refusal of a
machine without a TPU and of a configuration file whose condition or
latent sizes are not the program's, and what the run reads of a
configuration's reference module."""
import glob
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "sf-steady"


def reduced_conf():
    with open(os.path.join(ROOT, "bench", "configs",
                           "ardit-self-forcing.json")) as f:
        conf = json.load(f)
    conf["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                         d_head=16, d_ff=128, ardit_frame_tokens=16,
                         param_dtype="float32", kv_dtype="float32")
    conf["pool_streams"] = 2
    conf["check"]["reference_passes"] = 30
    return conf


def run(seed, conf=None, **kw):
    from bench import run as bench_run
    traffic = bench_run.cell_files(WORKLOAD)[3]
    # the reduced model serves a stream in well under a second: arrive
    # often enough that the 6 s window holds several
    return bench_run.run_cell(WORKLOAD, seed, 6.0, False, require_chip=False,
                              conf_override=conf or reduced_conf(),
                              traffic_override=dict(traffic, rate_per_s=1.0),
                              **kw)


# what run_cell may read of a configuration's reference module
REFERENCE_CONTRACT = {"dims_of", "make_params", "parse_fidelity",
                      "visible_window_tokens", "passes_of", "replay_stream",
                      "chunk_noise", "rel_err"}


class Recording(types.ModuleType):
    """A module that records the names read of it."""

    def __init__(self, module):
        super().__init__(module.__name__)
        self._module, self.read = module, set()

    def __getattr__(self, name):
        self.read.add(name)
        return getattr(self._module, name)


@pytest.fixture(scope="module")
def sound():
    """A sound run, with the reference module it imports recording what
    the run reads of it."""
    name = "bench.reference.ardit"
    recording = Recording(importlib.import_module(name))
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, name, recording)
        out = run(2 ** 33 + 5)
    out["reference_read"] = recording.read
    return out


@pytest.fixture(scope="module")
def control():
    return run(2 ** 33 + 6, control=True)


def test_sound_run_is_correct(sound):
    out, info = sound["result"], sound["info"]
    assert out["correct"] is True, info
    assert info["compared_chunks"] >= 3, info
    # float32 weights and KV at reduced widths: the program and the
    # reference differ by rounding alone
    assert max(info["chunk_rel_err"]) < 1e-5
    assert list(out)[-1] == "compared"
    # behind the gate at the pool's size nothing spills
    assert info["residency"]["evictions"] == 0
    assert info["pool_streams"] == 2
    assert set(out["metrics"]) == {"chunks_per_s", "setup_s"}


def test_run_reads_the_documented_reference_functions(sound):
    """A new configuration's reference module provides these and no more."""
    assert sound["reference_read"] == REFERENCE_CONTRACT


def test_compared_chunks_are_made_in_the_window(sound):
    info = sound["info"]
    assert info["compared_index"]
    assert set(info["coverage"]) <= set(info["window_features"])


def test_fp8_control_is_not_correct(control):
    """The reference computed with fp8 matmul operands, put in the
    program's place, makes the run not correct, on every compared
    chunk, while the program's own chunks of that run pass."""
    out, info = control["result"], control["info"]
    limit = out["compared"]["chunk_rel_err"]["limit"]
    assert out["correct"] is False
    assert min(info["control_rel_err"]) > limit
    assert max(info["chunk_rel_err"]) <= limit


def test_altered_chunk_is_not_correct(monkeypatch):
    """A chunk altered where it is produced makes the run not correct."""
    from repro.serve.batcher import BatchedChunkExecutor
    inner = BatchedChunkExecutor.run_step

    def altered(self, sids, sp_serve=False):
        done, dt = inner(self, sids, sp_serve)
        for sid in done:
            if sid < 1_000_000:            # served traffic, not warm-up
                self.chunks[sid][-1] = self.chunks[sid][-1] * 1.05
        return done, dt

    monkeypatch.setattr(BatchedChunkExecutor, "run_step", altered)
    out = run(11)["result"]
    assert out["correct"] is False
    assert out["compared"]["chunk_rel_err"]["value"] > \
        out["compared"]["chunk_rel_err"]["limit"]


def test_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        WORKLOAD, "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_sample_prefers_uncovered_features_within_budget():
    """The comparison's sample takes, while the pass budget lasts, the
    chunk that adds the most features not yet covered, then the
    cheapest; a stream's earlier chunks cost one clean pass each."""
    from bench import run as bench_run
    from bench.reference import ardit as ref
    keys = {1: ["S2_r0.0_W7_bf16"] * 6,
            2: ["S2_r0.0_W7_bf16", "S2_r0.0_W7_bf16", "S3_r0.6_W7_fp8"]}
    cands = {(1, 4): {"in_chunk", "ring_ge_2"},
             (1, 5): {"in_chunk", "ring_ge_2", "batch2_mixed"},
             (2, 2): {"in_chunk", "ring_ge_2", "rho", "fp8"}}
    picks = bench_run.sample_chunks(cands, keys, seed=3, budget=100,
                                    reference=ref)
    assert picks == {1: [4, 5], 2: [2]}
    # (2, 2) covers the most and costs 2 + 3 passes; (1, 5) then adds
    # batch2_mixed at 5 + 2; 13 passes leave (1, 4) out
    picks = bench_run.sample_chunks(cands, keys, seed=3, budget=13,
                                    reference=ref)
    assert picks == {1: [5], 2: [2]}
    assert ref.passes_of(keys[1], [4, 5]) == 5 + 2 + 2


@pytest.mark.parametrize("key,value", [("text_tokens", 512),
                                       ("cond_tokens", 78),
                                       ("latent_channels", 8)])
def test_refuses_sizes_the_program_does_not_state(monkeypatch, key, value):
    """A configuration file whose condition or latent sizes differ from
    the program's ends the run, naming the key, before any weights."""
    from bench.reference import ardit as ref

    def no_weights(*a, **k):
        raise AssertionError("make_params was called")

    monkeypatch.setattr(ref, "make_params", no_weights)
    conf = dict(reduced_conf(), **{key: value})
    with pytest.raises(SystemExit, match=f"^{key}: .*states {value}, "):
        run(3, conf)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(ROOT, "bench", "configs", "*.json"))),
    ids=os.path.basename)
def test_configuration_states_the_programs_sizes(path):
    from bench import run as bench_run
    from repro.models import ardit as A
    with open(path) as f:
        conf = json.load(f)
    bench_run.check_layout(conf, A, bench_run.model_config(conf))


def test_layout_comes_from_the_program_where_it_states_one():
    """A program with ``io_layout`` is asked for each configuration's
    sizes: a block reading 512 text tokens through a cross-attention over
    an empty sink passes a file that states so, and no other."""
    from bench import run as bench_run
    program = types.SimpleNamespace(io_layout=lambda cfg: {
        "cond_tokens": 0, "text_tokens": 512, "latent_channels": 16})
    conf = dict(reduced_conf(), cond_tokens=0, text_tokens=512)
    bench_run.check_layout(conf, program, None)
    with pytest.raises(SystemExit, match="^cond_tokens: "):
        bench_run.check_layout(reduced_conf(), program, None)
