#!/usr/bin/env python3
"""The program's own spans and device scopes, read from the traced run.

The serving path annotates itself (``repro/tracing.py``): host spans and
marks named ``session/...``, ``batcher/...`` and ``bmpr/...`` with their
stats (``sid``, ``chunk``, ``rows``, ``mode`` ...), and ``ardit/...``
``jax.named_scope`` scopes on the model step's device operations.  This
module reads both from the ``.xplane.pb`` that ``bench/trace.py`` loads
(the newest under ``.bench_trace/``), once per run, on the profiler's one
clock, so ``ctx.trace_window`` bounds them as it bounds the device ops.

A device operation's scope is the ``ardit/<part>`` in its HLO ``op_name``
metadata, read from the HLO of its program that the trace's
``/host:metadata`` plane holds (``ProfileData`` shows neither that nor the
operations' own metadata, so both are parsed from the file here); where
XLA fused operations of two scopes, the fusion's own ``op_name`` (that of
the operation it was rooted at) decides.  Operations with no such scope
are ``""``.

    python3 bench/scopes.py                  # listing of the last trace
    python3 bench/scopes.py --fixture OUT.json --slice-s 1.5

prints, after a ``--trace 1`` run, the device seconds per scope and per
operation in the window, the scopes' cover of the busy time, the idle
gaps by the program's innermost span, and the check that each
``batcher/sync`` ends after the step it waits for; ``--fixture`` writes a
slice of the window for ``bench/tests/test_scopes.py``.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")     # bench/run.py's trace
LAYERS = ("session/", "batcher/", "bmpr/")
SCOPES = ("qkv", "paged_attn", "segment_attn", "out_proj", "mlp")
SCOPE_RE = re.compile(r"ardit/(" + "|".join(SCOPES) + r")\b")
STEP_MODULE = "denoise_step"         # the jitted step's program name

Span = Tuple[str, float, float, Dict[str, Any]]     # name, start, end, stats
Op = Tuple[str, float, float, str]                  # name, start, end, scope


@dataclasses.dataclass
class Scopes:
    spans: List[Span]                  # the program's host spans, by start
    ops: Dict[str, List[Op]]           # device plane -> innermost ops
    modules: Dict[str, List[trace.Event]]   # device plane -> programs run

    def spans_in(self, name: str, lo: float, hi: float) -> List[Span]:
        """Spans (or marks) called ``name`` that start in [lo, hi)."""
        return [s for s in self.spans if s[0] == name and lo <= s[1] < hi]

    def scope_s(self, lo: float, hi: float) -> Dict[str, float]:
        """Device seconds per scope inside [lo, hi], summed over the
        chips; ``""`` holds the operations outside every scope."""
        out = {s: 0.0 for s in SCOPES + ("",)}
        for ops in self.ops.values():
            for _, s, e, scope in ops:
                if e > lo and s < hi:
                    out[scope] += min(e, hi) - max(s, lo)
        return out

    def steps(self, plane: str) -> List[trace.Event]:
        """Executions of the jitted step on one device, by start."""
        return [m for m in self.modules.get(plane, ())
                if STEP_MODULE in m[0]]


# ---------------------------------------------------------------------------
# operations the scoped layers need (a multiply-add counts 2)
# ---------------------------------------------------------------------------

def segment_attn_flops(d) -> float:
    """One pass of one row: the chunk's T queries over its own T tokens,
    q.k and p.v, in every layer."""
    return 4.0 * d.chunk_tokens ** 2 * d.n_heads * d.d_head * d.n_layers


def mlp_flops(d) -> float:
    """One pass of one row: the MLP's in and out projections, every
    layer."""
    return 4.0 * d.chunk_tokens * d.d_model * d.d_ff * d.n_layers


def roofline(ctx, scope: str, flops_per_row: float) -> Optional[float]:
    """Share of its compute roofline one scope reaches, in %: the
    operations of every row of every launch in the window over the bf16
    peak, over the scope's device time in the window.  Both scoped
    layers are compute-bound at the served shapes (a row's MLP reads
    55 MB of weights per layer for 0.26 TFLOP; the in-chunk attention
    reads 57 MB of q, k, v per layer for 0.13 TFLOP)."""
    sc = of(ctx)
    if sc is None or not ctx.launch_rows:
        return None
    lo, hi = ctx.trace_window
    seconds = sc.scope_s(lo, hi)[scope]
    if seconds <= 0.0:
        return None
    ops = flops_per_row * len(ctx.launch_rows)
    return 100.0 * ops / ctx.peak["bf16_flops"] / seconds


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _schema():
    """Message classes for the parts of an ``.xplane.pb`` that
    ``jax.profiler.ProfileData`` does not show: the planes' event and
    stat metadata, and the HLO modules the ``/host:metadata`` plane
    holds (its "Hlo Proto" stats), down to each instruction's
    ``op_name``.  Field numbers are those of ``tsl/profiler/protobuf/
    xplane.proto`` and ``xla/service/hlo.proto``; every other field is
    skipped."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    fp = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_scopes.proto",
                                            package="bench_scopes")

    def add(where, name, fields):
        m = where.add(name=name)
        for fname, num, typ, many in fields:
            f = m.field.add(name=fname, number=num, label=(
                fp.LABEL_REPEATED if many else fp.LABEL_OPTIONAL))
            if typ.startswith("."):
                f.type, f.type_name = fp.TYPE_MESSAGE, ".bench_scopes" + typ
            else:
                f.type = getattr(fp, "TYPE_" + typ)
        return m

    add(fd.message_type, "XStat", [("metadata_id", 1, "INT64", False),
                                   ("str_value", 5, "STRING", False),
                                   ("bytes_value", 6, "BYTES", False),
                                   ("ref_value", 7, "UINT64", False)])
    add(fd.message_type, "XEventMetadata", [("name", 2, "STRING", False),
                                            ("stats", 5, ".XStat", True)])
    add(fd.message_type, "XStatMetadata", [("name", 2, "STRING", False)])
    plane = add(fd.message_type, "XPlane", [
        ("name", 2, "STRING", False),
        ("event_metadata", 4, ".XPlane.EventEntry", True),
        ("stat_metadata", 5, ".XPlane.StatEntry", True)])
    for entry, value in (("EventEntry", ".XEventMetadata"),
                         ("StatEntry", ".XStatMetadata")):
        e = add(plane.nested_type, entry, [("key", 1, "INT64", False),
                                           ("value", 2, value, False)])
        e.options.map_entry = True
    add(fd.message_type, "XSpace", [("planes", 1, ".XPlane", True)])
    add(fd.message_type, "OpMetadata", [("op_name", 2, "STRING", False)])
    add(fd.message_type, "Instruction", [("name", 1, "STRING", False),
                                         ("metadata", 7, ".OpMetadata",
                                          False)])
    add(fd.message_type, "Computation", [("instructions", 2,
                                          ".Instruction", True)])
    add(fd.message_type, "Module", [("computations", 3, ".Computation",
                                     True)])
    add(fd.message_type, "Hlo", [("hlo_module", 1, ".Module", False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return tuple(message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_scopes." + n))
        for n in ("XSpace", "Hlo"))


def _scope(op_name: str) -> str:
    found = SCOPE_RE.findall(op_name)
    return found[-1] if found else ""       # an inner scope wins


def _op_scopes(path: str):
    """({program: {instruction: scope}} from the HLO of every program
    the trace holds, {operation event name: scope} from the string
    stats of the device planes' operation metadata)."""
    xspace, hlo = _schema()
    with open(path, "rb") as f:
        space = xspace.FromString(f.read())
    by_program: Dict[str, Dict[str, str]] = {}
    by_event: Dict[str, str] = {}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for em in plane.event_metadata.values():
            for st in em.stats:
                if plane.name == "/host:metadata" and st.bytes_value:
                    module = hlo.FromString(st.bytes_value).hlo_module
                    by_program[em.name] = {
                        i.name: _scope(i.metadata.op_name)
                        for c in module.computations
                        for i in c.instructions}
                elif plane.name.startswith("/device:TPU:"):
                    value = st.str_value or names.get(st.ref_value, "")
                    if _scope(value):
                        by_event[em.name] = _scope(value)
    return by_program, by_event


def _program(by_program: Dict[str, Dict[str, str]],
             module: str) -> Dict[str, str]:
    """A program's instructions by the ``XLA Modules`` event's name
    ("jit_denoise_step_paged(<id>)"); where no HLO carries that exact
    name, those of every program of the same name."""
    if module in by_program:
        return by_program[module]
    base = module.split("(")[0]
    merged: Dict[str, str] = {}
    for name, instructions in by_program.items():
        if name.split("(")[0] == base:
            for k, v in instructions.items():
                merged.setdefault(k, v)
    by_program[module] = merged
    return merged


def _short(name: str) -> str:
    # "%fusion.12 = bf16[...] fusion(...)" -> "fusion.12", as trace.py
    return name.split(" = ")[0].lstrip("%")


def load(directory: str) -> Scopes:
    return _read(_newest(directory))


def _read(path: str) -> Scopes:
    """A device operation's scope comes from its own metadata where the
    trace gives the ``op_name`` there, else from the HLO of the program
    it ran in (the ``XLA Modules`` event that holds it)."""
    from jax.profiler import ProfileData
    by_program, by_event = _op_scopes(path)
    data = ProfileData.from_file(path)
    spans: List[Span] = []
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[trace.Event]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            raw, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    mods.extend((e.name, e.start_ns * 1e-9,
                                 e.end_ns * 1e-9) for e in line.events)
            mods.sort(key=lambda m: m[1])
            starts = [m[1] for m in mods]
            scoped = []
            for name, s, e in raw:
                j = bisect.bisect_right(starts, s) - 1
                program = _program(by_program, mods[j][0]) if j >= 0 else {}
                scope = by_event.get(name) or program.get(_short(name), "")
                scoped.append((_short(name), s, e, scope))
            # innermost ops: a loop's op holds its body's ops
            ops[plane.name] = trace.leaves(scoped)
            modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                          dict(e.stats))
                         for line in plane.lines for e in line.events
                         if e.name.startswith(LAYERS))
    return Scopes(sorted(spans, key=lambda s: s[1]), ops, modules)


def _newest(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


@functools.lru_cache(maxsize=1)
def _load_cached(path: str, mtime: float) -> Scopes:
    return _read(path)


def of(ctx) -> Optional[Scopes]:
    """The traced run's spans and scopes (read once per trace file), or
    None in an untraced run."""
    if ctx.trace is None:
        return None
    try:
        path = _newest(TRACE_DIR)
    except FileNotFoundError:
        return None
    return _load_cached(path, os.path.getmtime(path))


def from_json(raw: Dict[str, Any]) -> Scopes:
    """A slice written by ``--fixture``."""
    return Scopes([(n, s, e, dict(st)) for n, s, e, st in raw["spans"]],
                  {p: [tuple(o) for o in v] for p, v in raw["ops"].items()},
                  {p: [tuple(m) for m in v]
                   for p, v in raw["modules"].items()})


# ---------------------------------------------------------------------------
# checks and the listing
# ---------------------------------------------------------------------------

def sync_lags(sc: Scopes, plane: str) -> List[float]:
    """For each ``batcher/sync``, its end less the end of the step it
    waits for (negative: the host stopped waiting before the device
    finished).  The k-th ``batcher/launch`` ran the k-th step: every
    launch the benchmark serves dispatches one (``dispatch_count``).  A
    sync waits for the chunk's output, which its last denoise step made:
    the launch before it of the same KV dtype (a stream's rows keep their
    dtype through a chunk, and the fused groups split by dtype alone);
    the clean pass that appends the chunk's K/V runs on after the sync.
    A sync with no such launch in ``sc`` is left out."""
    launches = [s for s in sc.spans if s[0] == "batcher/launch"]
    steps = sc.steps(plane)
    if len(launches) != len(steps):
        raise ValueError(f"{len(launches)} launches, {len(steps)} steps")
    syncs = [s for s in sc.spans if s[0] == "batcher/sync"]
    out = []
    last: Dict[str, int] = {}            # dtype -> its latest launch
    for k, (_, ls, le, meta) in enumerate(launches):
        j = last.get(meta["quant"])
        if j is not None:
            out.extend(s[2] - steps[j][2] for s in syncs
                       if ls <= s[1] and s[2] <= le)
        last[meta["quant"]] = k
    return out


def idle_by_span(sc: Scopes, tr: trace.Trace, lo: float,
                 hi: float) -> Dict[str, float]:
    """Device idle time (first chip) by the innermost program span that
    the host was in; ``host/other`` where it was in none."""
    busy = trace.union(next(iter(tr.device.values())), lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    out: Dict[str, float] = {}
    spans = [s for s in sc.spans if s[2] > s[1]]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        inside = [s for s in spans if s[1] < ge and s[2] > gs]
        cuts = sorted({gs, ge} | {t for s in inside for t in s[1:3]
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in inside if s[1] <= a and s[2] >= b]
            # innermost: the latest start, then the earliest end
            name = (max(cover, key=lambda s: (s[1], -s[2]))[0] if cover
                    else "host/other")
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def listing(sc: Scopes, tr: trace.Trace, lo: float,
            hi: float) -> Dict[str, Any]:
    plane = next(iter(sc.ops))
    mods = sc.modules.get(plane, [])
    starts = [m[1] for m in mods]
    by_op: Dict[Tuple[str, str, str], float] = {}
    for n, s, e, scope in sc.ops[plane]:
        if e > lo and s < hi:
            j = bisect.bisect_right(starts, s) - 1
            mod = mods[j][0] if j >= 0 and mods[j][2] >= e else "?"
            key = (mod, re.sub(r"\.\d+$", "", n) if n.startswith("copy")
                   else n, scope)
            by_op[key] = by_op.get(key, 0.0) + min(e, hi) - max(s, lo)
    try:
        lags = sync_lags(sc, plane)
    except ValueError as e:
        lags, lag_error = [], str(e)
    else:
        lag_error = None
    prep = [s[2] - s[1] for s in sc.spans_in("batcher/prep", lo, hi)]
    marks = sc.spans_in("bmpr/select", lo, hi)
    pallas, _ = trace.kernel_s(tr, "paged_chunk_attention", lo, hi)
    return {
        "window_s": hi - lo, "busy_s": trace.busy_s(tr, lo, hi),
        "scope_s": sc.scope_s(lo, hi), "pallas_s": pallas,
        "ops": [[m, n, scope, t] for (m, n, scope), t in
                sorted(by_op.items(), key=lambda x: -x[1])],
        "sync_lag_s": {"n": len(lags), "min": min(lags, default=None),
                       "error": lag_error},
        "prep_ms_median": statistics.median(prep) * 1e3 if prep else None,
        "bmpr_modes": {m: sum(s[3].get("mode") == m for s in marks)
                       for m in {s[3].get("mode") for s in marks}},
        "spans": {n: len(sc.spans_in(n, lo, hi))
                  for n in sorted({s[0] for s in sc.spans})},
        "idle_by_span": idle_by_span(sc, tr, lo, hi),
    }


def fixture(sc: Scopes, lo: float, hi: float) -> Dict[str, Any]:
    """A slice [lo, hi] of one chip's trace: the device ops and programs
    in it; the steps that ran wholly inside it, with their launches (and
    the spans inside those, wherever the host ran them) and the launches'
    rows; the program's other spans that overlap it."""
    plane = next(iter(sc.ops))
    launches = [s for s in sc.spans if s[0] == "batcher/launch"]
    steps = sc.steps(plane)
    kept = [(ls, m) for ls, m in zip(launches, steps)
            if lo <= m[1] and m[2] <= hi]

    def in_kept(s):
        return any(ls[1] <= s[1] and s[2] <= ls[2] for ls, _ in kept)

    spans = [s for s in sc.spans
             if (s[0].startswith("batcher/") and in_kept(s))
             or (not s[0].startswith("batcher/")
                 and s[2] >= lo and s[1] <= hi)]
    mods = [m for m in sc.modules[plane] if m[2] > lo and m[1] < hi
            and (STEP_MODULE not in m[0] or any(m == k for _, k in kept))]
    return {
        "window": [lo, hi],
        "launch_rows": sum(ls[3]["rows"] for ls, _ in kept),
        "spans": [list(s) for s in spans],
        "ops": {plane: [list(o) for o in sc.ops[plane]
                        if o[2] > lo and o[1] < hi]},
        "modules": {plane: [list(m) for m in mods]},
    }


def pick_slice(sc: Scopes, lo: float, hi: float,
               seconds: float) -> Dict[str, Any]:
    """The fixture of a slice from the start of a step in mid-window to
    the end of a later one, at least ``seconds`` long and holding a
    chunk's begin (a ``bmpr/select`` mark) and a completion whose chunk
    it made (or, failing that, up to the window's last step)."""
    plane = next(iter(sc.ops))
    steps = [m for m in sc.steps(plane) if lo <= m[1] and m[2] <= hi]
    start = steps[len(steps) // 2][1]
    for _, _, end in steps[len(steps) // 2:]:
        raw = fixture(sc, start, end)
        if (end - start >= seconds
                and any(sp[0] == "bmpr/select" for sp in raw["spans"])
                and sync_lags(from_json(raw), plane)):
            break
    return raw


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dir", default=TRACE_DIR)
    ap.add_argument("--fixture", default="")
    ap.add_argument("--slice-s", type=float, default=1.5)
    args = ap.parse_args(argv)
    tr = trace.load(args.dir)
    sc = load(args.dir)
    lo, hi = tr.window()
    print(json.dumps(listing(sc, tr, lo, hi)))
    if args.fixture:
        with open(args.fixture, "w") as f:
            json.dump(pick_slice(sc, lo, hi, args.slice_s), f)


if __name__ == "__main__":
    main()
