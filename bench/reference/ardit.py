"""Plain float32 reference of one AR-DiT stream, and the benchmark's weights.

Written from the model's description, importing nothing of the program:
a chunk of ``chunk_tokens`` latent tokens is denoised over S rectified-flow
Euler steps (sigma grid 1 -> 0); every pass runs ``n_layers`` adaLN DiT
blocks (RMSNorm, shift/scale modulation from the time embedding, gated
residuals, RoPE self-attention, GELU MLP).  Attention covers the sink (the
condition tokens' K/V), the visible window of earlier chunks' K/V, and the
chunk's own tokens.  A clean pass at t=0 over the finished chunk gives the
K/V that later chunks attend to.  Fidelity knobs: S steps, W window chunks,
rho block sparsity over the window (denoise passes only), Q = fp8 rounding
of the chunk's stored K/V.

No pool, no batching, no kernel: one stream, float32 everywhere, matmuls at
``Precision.HIGHEST``.  The window is a ring of 7 chunk slots; a pass reads
only the slots its mask makes live, in blocks of queries, so the reference
fits beside nothing else on one 16 GB chip.

``matmul="fp8"`` is the control: the same reference with the operands of
every projection and MLP matmul rounded to float8_e4m3 (per-tensor scale),
the step down from the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                  # largest finite float8_e4m3fn


class Dims(NamedTuple):
    """The sizes the forward needs, read from a configuration file."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    chunk_tokens: int
    window: int                  # ring slots (W max)
    cond_tokens: int
    latent: int
    rope_theta: float
    norm_eps: float


def dims_of(conf: dict) -> Dims:
    m = conf["model"]
    return Dims(m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
                m["d_head"], m["d_ff"],
                m["ardit_frame_tokens"] * m["ardit_chunk_frames"],
                m["ardit_window_chunks"], conf["cond_tokens"],
                conf["latent_channels"], float(m["rope_theta"]),
                float(m["norm_eps"]))


# ---------------------------------------------------------------------------
# weights: made on the device from the seed, in the type they are served in
# ---------------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make_params(d: Dims, key: jax.Array, dtype: str):
    dt = jnp.dtype(dtype)

    def dense(k, shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    L, dm = d.n_layers, d.d_model
    hq, hkv = d.n_heads * d.d_head, d.n_kv_heads * d.d_head
    ks = jax.random.split(key, 16)
    return {
        "in_proj": dense(ks[0], (d.latent, dm)),
        "cond_proj": dense(ks[1], (dm, dm)),
        "t_mlp1": dense(ks[2], (256, dm)),
        "t_mlp2": dense(ks[3], (dm, dm)),
        "layers": {
            "attn": {"wq": dense(ks[4], (L, dm, hq)),
                     "wk": dense(ks[5], (L, dm, hkv)),
                     "wv": dense(ks[6], (L, dm, hkv)),
                     "wo": dense(ks[7], (L, hq, dm))},
            "mlp": {"wi": dense(ks[8], (L, dm, d.d_ff)),
                    "wo": dense(ks[9], (L, d.d_ff, dm))},
            # open adaLN gates: every residual branch, attention over the
            # cached context among them, reaches the output
            "mod": dense(ks[10], (L, dm, 6 * dm)),
            "mod_b": jnp.zeros((L, 6 * dm), dt),
        },
        "final_norm": jnp.ones((dm,), dt),
        "final_mod": dense(ks[11], (dm, 2 * dm)),
        "out_proj": dense(ks[12], (dm, d.latent), 0.02),
    }


def make_params(d: Dims, seed: int, dtype: str = "bfloat16"):
    """Random weights from ``seed``, made on the default device in one
    jitted call."""
    return _make_params(d, seed_key(seed), dtype)


# ---------------------------------------------------------------------------
# the served inputs, regenerated from the seeding rule the server documents
# ---------------------------------------------------------------------------

def chunk_noise(d: Dims, sid: int, index: int) -> jax.Array:
    """Initial noise of chunk ``index`` of stream ``sid`` [T, latent]."""
    key = jax.random.PRNGKey(index * 7919 + sid)
    return jax.random.normal(key, (1, d.chunk_tokens, d.latent))[0]


def stream_condition(d: Dims, sid: int) -> jax.Array:
    """Stub text-encoder output of stream ``sid`` [cond_tokens, d_model]."""
    key = jax.random.PRNGKey(1000 + sid)
    return jax.random.normal(key, (1, d.cond_tokens, d.d_model))[0] * 0.02


# ---------------------------------------------------------------------------
# fidelity -> visibility
# ---------------------------------------------------------------------------

class Fidelity(NamedTuple):
    steps: int
    sparsity: float
    window: int
    quant: str


def parse_fidelity(key: str) -> Fidelity:
    """``S3_r0.6_W3_fp8`` -> Fidelity(3, 0.6, 3, "fp8")."""
    s, r, w, q = key.split("_")[:4]
    return Fidelity(int(s[1:]), float(r[1:]), int(w[1:]), q)


def keep_blocks(n_blocks: int, sparsity: float) -> List[int]:
    """Block-sparse keep list over ``n_blocks`` 128-token blocks of the
    window: the first and last block always, and an evenly strided
    ~(1 - rho) share of the others."""
    frac = max(1e-6, 1.0 - sparsity)
    forced = {0, n_blocks - 1}
    middle = [j for j in range(n_blocks) if j not in forced]
    n_keep = int(round(len(middle) * frac))
    if n_keep >= len(middle):
        chosen = middle
    elif n_keep <= 0:
        chosen = []
    else:
        idx = np.linspace(0, len(middle) - 1, n_keep).round().astype(int)
        chosen = [middle[j] for j in sorted(set(idx.tolist()))]
    return sorted(forced | set(chosen))


def visible_window_tokens(d: Dims, n: int, window: int,
                          sparsity: float) -> np.ndarray:
    """Chronological indices of the visible window tokens (after the
    sink) for a chunk at fill ``n``: the last ``min(W, n, W_max)``
    chunks, thinned by rho in 128-token blocks."""
    w = min(window, n, d.window)
    body = w * d.chunk_tokens
    idx = np.arange(body)
    if sparsity <= 0.0 or body == 0:
        return idx
    n_blocks = max(1, body // 128)
    keep = [np.arange(j * 128, min((j + 1) * 128, body))
            for j in keep_blocks(n_blocks, sparsity)]
    keep.append(np.arange(n_blocks * 128, body))
    return np.unique(np.concatenate(keep))


def ring_mask(d: Dims, n: int, window: int, sparsity: float) -> np.ndarray:
    """[W_max, T] visibility of the ring slots for a chunk at fill ``n``
    (chunk c lives in slot c % W_max)."""
    w = min(window, n, d.window)
    body = visible_window_tokens(d, n, window, sparsity)
    mask = np.zeros((d.window, d.chunk_tokens), bool)
    c_abs = (n - w) + body // d.chunk_tokens
    mask[c_abs % d.window, body % d.chunk_tokens] = True
    return mask


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def _quantize(a: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, matmul: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if matmul == "fp8":
        a, b = _quantize(a), _quantize(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, pos, theta):
    """x [T, H, D] at absolute positions pos [T]; rotate-half layout."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _time_embed(p, t, matmul):
    half = 128
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t * freqs * 1000.0
    emb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])[None]
    h = jax.nn.silu(_mm(emb, p["t_mlp1"], matmul))
    return _mm(h, p["t_mlp2"], matmul)[0]


def _qkv(d: Dims, lp, x, pos, matmul):
    t = x.shape[0]
    q = _mm(x, lp["wq"], matmul).reshape(t, d.n_heads, d.d_head)
    k = _mm(x, lp["wk"], matmul).reshape(t, d.n_kv_heads, d.d_head)
    v = _mm(x, lp["wv"], matmul).reshape(t, d.n_kv_heads, d.d_head)
    return (_rope(q, pos, d.rope_theta), _rope(k, pos, d.rope_theta), v)


def _partial(qg, k, v, mask, scale):
    """Softmax partials of query block qg [bq, Hkv, G, D] over keys
    k, v [S, Hkv, D] with key visibility mask [S] (or None)."""
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return (m_safe, jnp.sum(p, -1),
            jnp.einsum("hgqk,khd->hgqd", p, v, precision=HIGHEST))


def _merge(a, b):
    m = jnp.maximum(a[0], b[0])
    ca, cb = jnp.exp(a[0] - m), jnp.exp(b[0] - m)
    return m, a[1] * ca + b[1] * cb, a[2] * ca[..., None] + b[2] * cb[..., None]


def _attend(d: Dims, q, k, v, sink_k, sink_v, ring_k, ring_v, rmask, live):
    """Attention of the chunk's queries over sink + live ring slots + the
    chunk itself, one query block at a time."""
    t = q.shape[0]
    g = d.n_heads // d.n_kv_heads
    scale = 1.0 / math.sqrt(d.d_head)
    # query blocks whose per-slot scores stay near 128 MB
    n_blocks = next(nb for nb in range(1, t + 1) if t % nb == 0 and
                    d.n_heads * (t // nb) * t * 4 <= 2 ** 27)
    qb = q.reshape(n_blocks, t // n_blocks, d.n_kv_heads, g, d.d_head)

    def block(qg):
        acc = _partial(qg, sink_k, sink_v, None, scale)
        acc = _merge(acc, _partial(qg, k, v, None, scale))
        for r in range(d.window):
            acc = jax.lax.cond(
                live[r],
                lambda a, r=r: _merge(a, _partial(qg, ring_k[r], ring_v[r],
                                                  rmask[r], scale)),
                lambda a: a, acc)
        _, l_, o = acc
        return o / l_[..., None]                       # [Hkv, G, bq, D]

    out = jax.lax.map(block, qb)            # [nb, Hkv, G, bq, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(t, d.n_heads * d.d_head)


@functools.partial(jax.jit, static_argnums=(0, 1))
def chunk_pass(d: Dims, matmul: str, p, x, t, index, sink_k, sink_v,
               ring_k, ring_v, rmask, live):
    """One pass over a chunk: x [T, latent] at denoise time t, chunk
    ``index``; sink_k/v [L, cond, Hkv, D]; ring_k/v [L, W, T, Hkv, D];
    rmask [W, T], live [W].  Returns (prediction [T, latent], this
    chunk's K, V [L, T, Hkv, D])."""
    f32 = jnp.float32
    x = x.astype(f32)
    pos = d.cond_tokens + index * d.chunk_tokens + jnp.arange(d.chunk_tokens)
    temb = _time_embed(p, t.astype(f32), matmul)
    h = _mm(x, p["in_proj"], matmul)

    def layer(h, xs):
        lp, sk, sv, rk, rv = xs
        mod = _mm(jax.nn.silu(temb)[None], lp["mod"], matmul)[0] \
            + lp["mod_b"].astype(f32)
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6)
        a = _rmsnorm(h, 1.0, d.norm_eps) * (1.0 + sc1) + sh1
        q, k, v = _qkv(d, lp["attn"], a, pos, matmul)
        o = _attend(d, q, k, v, sk, sv, rk, rv, rmask, live)
        h = h + g1 * _mm(o, lp["attn"]["wo"], matmul)
        f = _rmsnorm(h, 1.0, d.norm_eps) * (1.0 + sc2) + sh2
        f = jax.nn.gelu(_mm(f, lp["mlp"]["wi"], matmul))
        h = h + g2 * _mm(f, lp["mlp"]["wo"], matmul)
        return h, (k, v)

    h, (ks, vs) = jax.lax.scan(layer, h, (p["layers"], sink_k, sink_v,
                                          ring_k, ring_v))
    mod = _mm(jax.nn.silu(temb)[None], p["final_mod"], matmul)[0]
    sh, sc = jnp.split(mod, 2)
    h = _rmsnorm(h, p["final_norm"].astype(f32), d.norm_eps) * (1.0 + sc) + sh
    return _mm(h, p["out_proj"], matmul), ks, vs


@functools.partial(jax.jit, static_argnums=(0, 1))
def sink_kv(d: Dims, matmul: str, p, cond):
    """The condition tokens' K/V in every layer [L, cond, Hkv, D]."""
    c = _mm(cond, p["cond_proj"], matmul)
    pos = jnp.arange(d.cond_tokens)

    def one(lp):
        _, k, v = _qkv(d, lp, c, pos, matmul)
        return k, v

    return jax.lax.map(one, p["layers"]["attn"])


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(5,))
def _ring_put(ring_k, ring_v, k, v, slot, quant: str):
    if quant == "fp8":
        k = k.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        v = v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (ring_k.at[:, slot].set(k), ring_v.at[:, slot].set(v))


def rel_err(got, want, base) -> float:
    """||got - want|| / ||want - base||: the gap against the reference,
    over what the denoise steps moved the chunk away from its noise."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    base = np.asarray(base, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want - base), 1e-30))


def replay_stream(d: Dims, params, sid: int,
                  served: List[np.ndarray], fids: List[str],
                  compare: Sequence[int], matmul: str = "f32",
                  ring: Optional[Tuple[jax.Array, jax.Array]] = None,
                  ) -> Tuple[Dict[int, np.ndarray],
                             Tuple[jax.Array, jax.Array]]:
    """Teacher-forced replay of one served stream: each chunk in
    ``compare`` is denoised from its seeded noise with its logged
    fidelity over the reference's own K/V of the SERVED earlier chunks,
    as the server builds its own; every earlier chunk gets only the
    clean pass that gives its K/V.  Returns the reference's chunk per
    compared index.  ``ring`` is a reusable f32 buffer pair (donated
    through)."""
    shape = (d.n_layers, d.window, d.chunk_tokens, d.n_kv_heads, d.d_head)
    if ring is None:
        ring = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    ring_k, ring_v = ring
    want = set(compare)
    last = max(want)
    outs: Dict[int, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        sk, sv = sink_kv(d, matmul, params, stream_condition(d, sid))
        for i in range(last + 1):
            fid = parse_fidelity(fids[i])
            if i in want:
                grid = np.linspace(1.0, 0.0, fid.steps + 1)
                dn = ring_mask(d, i, fid.window, fid.sparsity)
                x = chunk_noise(d, sid, i)
                for s in range(fid.steps):
                    v_pred, _, _ = chunk_pass(
                        d, matmul, params, x, jnp.float32(grid[s]), i, sk,
                        sv, ring_k, ring_v, jnp.asarray(dn),
                        jnp.asarray(dn.any(1)))
                    x = x - jnp.float32(grid[s] - grid[s + 1]) * v_pred
                outs[i] = np.asarray(x)
            if i < last:
                cl = ring_mask(d, i, fid.window, 0.0)
                _, k, v = chunk_pass(
                    d, matmul, params,
                    jnp.asarray(served[i]).reshape(d.chunk_tokens, d.latent),
                    jnp.float32(0.0), i, sk, sv, ring_k, ring_v,
                    jnp.asarray(cl), jnp.asarray(cl.any(1)))
                ring_k, ring_v = _ring_put(ring_k, ring_v, k, v,
                                           i % d.window, fid.quant)
    return outs, (ring_k, ring_v)


def passes_of(fids: List[str], compare: Iterable[int]) -> int:
    """Forward passes ``replay_stream`` runs for the chunks ``compare``:
    the denoise steps of each, and a clean pass of every chunk before
    the last."""
    want = set(compare)
    if not want:
        return 0
    return max(want) + sum(parse_fidelity(fids[i]).steps for i in want)
