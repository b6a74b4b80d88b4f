"""The operation and byte counts against a hand count at published
widths."""
import json
import os

import pytest

from bench import flops
from bench.reference.ardit import dims_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dims(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return dims_of(json.load(f))


def test_pass_at_published_widths_hand_count():
    d = dims("ardit-self-forcing")
    t, dm, ff, L = 4680, 1536, 8960, 15
    proj = 2 * t * dm * dm * 4            # q, k, v, o: 12 x 128 = 1536
    mlp = 2 * t * dm * ff * 2
    own = 2 * 2 * t * t * dm              # q.k and p.v over the chunk
    ctx = 2 * 2 * t * 77 * dm             # over the sink
    mod = 2 * dm * 6 * dm
    want = L * (proj + mlp + own + ctx + mod) + 4 * t * 16 * dm \
        + 2 * 256 * dm + 6 * dm * dm
    assert flops.pass_flops(d, 77) == pytest.approx(want, rel=1e-12)
    # ~5.2 TFLOP of projections and MLP, ~2.0 TFLOP per chunk attended
    assert L * (proj + mlp) == pytest.approx(5.19e12, rel=0.01)
    assert L * own == pytest.approx(2.02e12, rel=0.01)


def test_chunk_counts_denoise_and_clean_passes():
    d = dims("ardit-self-forcing")
    full = d.cond_tokens + 7 * d.chunk_tokens
    top = flops.chunk_flops(d, "S4_r0.0_W7_bf16", 9)
    assert top == pytest.approx(5 * flops.pass_flops(d, full))
    # the window is clipped by fill, and rho thins only denoise passes
    assert flops.visible_context(d, 2, 7, 0.0) == 77 + 2 * 4680
    sp = flops.chunk_flops(d, "S2_r0.6_W3_bf16", 5)
    vis_dn = flops.visible_context(d, 5, 3, 0.6)
    assert 77 + 3 * 4680 * 0.4 < vis_dn < 77 + 3 * 4680 * 0.5
    assert sp == pytest.approx(2 * flops.pass_flops(d, vis_dn)
                               + flops.pass_flops(d, 77 + 3 * 4680))


def test_paged_kernel_cost_hand_count():
    d = dims("ardit-causal-forcing")       # 16 heads of 96
    f, b = flops.paged_kernel_cost(d, 77 + 2640)
    assert f == 4 * 2640 * (77 + 2640) * 16 * 96
    assert b == (2640 * 1536 * 2 + 2 * (77 + 2640) * 16 * 96 * 2
                 + 2640 * 16 * 98 * 4)
    # compute bounds it on v5e: ~0.19 ms of math against ~0.03 ms of HBM
    assert f / 197e12 > 4 * b / 819e9
