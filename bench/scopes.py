"""Split a profiler trace of one call by the engine's own scopes.

``trace_reduce`` sees the engine program as one block. This module reads
the same trace again, with the program's map from HLO instruction to
engine scope (``repro.core.sweep.compiled_scope_map``, built on
``engine.scope_map``), and the program's own host spans
(``eudoxia/run/*``, ``eudoxia/fleet/*``, ``eudoxia/workload/*``), and
gives for the traced call:

- the device seconds of the engine program's leaf ops, by scope
  (control-flow ops span the ops they run, so they are left out and
  nested loops count once);
- the engine program's in-loop gap: its execution intervals less the
  union of its leaf ops, time in which the device ran none of them;
- the device's idle time outside any engine program, by the innermost
  host span it falls in, the program's spans inside the benchmark's.

It raises when an op inside an engine program is missing from the map
(the map is not the program that ran), and when the clocks disagree: an
engine program must start after a ``.../dispatch`` span has started and
lie inside the benchmark's ``engine_call`` span.

    python3 bench/scopes.py --workload single_run --seeds 7,8,9

runs one cell's set-up on the chip, then per seed its call 0 untraced
and again under the profiler, and prints one JSON line per seed: both
wall times, the trace's per-layer numbers and the split above.
"""
from __future__ import annotations

import glob
import re

import trace_reduce

PROGRAM = "eudoxia/"
# the program's entry points: idle inside them, outside an engine
# program, is what ``entry_idle_ms`` reads
ENTRY = ("eudoxia/run/", "eudoxia/fleet/")
TOP = 10
_INSTR = re.compile(r"^%?([^\s=%]+) =")


def load_program_spans(path: str) -> list:
    """The program's own host spans in an xplane, as reduction records."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(("span", ev.name, ev.start_ns, ev.duration_ns, -1)
                           for ev in line.events
                           if ev.name.startswith(PROGRAM))
    return out


def instruction(text: str) -> str:
    """``%fusion.556 = f32[131072]{0} fusion(...)`` -> ``fusion.556``."""
    m = _INSTR.match(text)
    return m.group(1) if m else text


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _check_clocks(engine, spans):
    """Each engine program starts after a dispatch span of the program
    has started, and lies inside one of the benchmark's ``engine_call``
    spans, the dispatch span too."""
    calls = [(s, e) for n, s, e in spans if n == "engine_call"]
    dispatch = [s for n, s, _ in spans
                if n.startswith(PROGRAM) and n.endswith("/dispatch")]
    for a, b in engine:
        call = [(s, e) for s, e in calls if s <= a and b <= e]
        if not call or not any(call[0][0] <= d <= a for d in dispatch):
            raise ValueError(
                f"clocks disagree: the engine program at [{a}, {b}) ns is "
                "not inside an engine_call span after a dispatch span")


def _innermost(spans, t):
    """The name of the innermost span holding ``t``: the latest to start."""
    inside = [(s, n) for n, s, e in spans if s <= t < e]
    return max(inside)[1] if inside else "between spans"


def reduce_events(records: list, scopes: dict) -> dict | None:
    """The split of one call on one device; None when the trace holds
    no engine program. ``records`` are ``trace_reduce``'s records plus
    the program's spans; ``scopes`` maps instruction to scope."""
    spans = [(n, s, s + d) for k, n, s, d, _ in records if k == "span"]
    ops = [(t, s, s + d) for k, t, s, d, _ in records if k == "op"]
    engine = trace_reduce._union(
        [(s, s + d) for k, n, s, d, _ in records
         if k == "module" and trace_reduce.ENGINE_MARK in n])
    if not engine:
        return None
    _check_clocks(engine, spans)

    by_scope: dict = {}
    leaves = []
    outside = []
    for text, s, e in ops:
        if not any(a <= s < b for a, b in engine):
            outside.append((s, e))
            continue
        name = instruction(text)
        if name not in scopes:
            raise KeyError(f"op {name!r} of the engine program is not in the "
                           "scope map: the map is of another program")
        if trace_reduce.short_name(text).rsplit(" ", 1)[-1] in (
                trace_reduce.CONTAINERS):
            continue
        by_scope[scopes[name]] = by_scope.get(scopes[name], 0) + (e - s)
        leaves.append((s, e))
    engine_ns = _length(engine)
    gap_ns = engine_ns - _length(trace_reduce._union(
        [iv for a, b in engine for iv in trace_reduce._clip(leaves, a, b)]))

    # device idle outside the engine programs, inside the call's window,
    # cut at every span edge and named by the innermost span
    bench = [sp for sp in spans if sp[0] in trace_reduce.SPAN_NAMES]
    lo = min(s for _, s, _ in bench)
    hi = max(e for _, _, e in bench)
    busy = trace_reduce._union(trace_reduce._clip(outside + engine, lo, hi))
    edges = sorted({lo, hi} | {x for iv in busy for x in iv}
                   | {x for _, s, e in spans for x in (s, e) if lo < x < hi})
    gaps = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if not any(s <= mid < e for s, e in busy):
            gaps.append((_innermost(spans, mid), b - a))
    idle: dict = {}
    for where, g in gaps:
        idle[where] = idle.get(where, 0) + g
    gaps.sort(key=lambda g: -g[1])
    return {
        "engine_s": engine_ns / 1e9,
        "scope_s": {k: v / 1e9 for k, v in by_scope.items()},
        "loop_gap_s": gap_ns / 1e9,
        "idle_by_span_s": {k: v / 1e9 for k, v in idle.items()},
        "entry_idle_s": sum(v for k, v in idle.items()
                            if k.startswith(ENTRY)) / 1e9,
        "breakdown": {
            "scopes": [[k, v / 1e9] for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])],
            "idle_gaps_in_program": [[w, g / 1e9] for w, g in gaps[:TOP]],
        },
    }


def reduce_dir(trace_dir: str, scopes: dict | None,
               chips: int = 1) -> dict | None:
    """:func:`reduce_events` of the one trace under ``trace_dir``, its
    seconds averaged over the cell's chips (the lists are chip 0's);
    None without a scope map."""
    if scopes is None:
        return None
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane under {trace_dir}, got {paths}")
    records = trace_reduce.load_xplane(paths[0])
    spans = [r for r in records if r[0] == "span"]
    spans += load_program_spans(paths[0])
    per_chip = [reduce_events(spans + [r for r in records if r[4] == c],
                              scopes) for c in range(chips)]
    if any(r is None for r in per_chip):
        return None
    out = dict(per_chip[0])
    for key in ("engine_s", "loop_gap_s", "entry_idle_s"):
        out[key] = sum(r[key] for r in per_chip) / chips
    for key in ("scope_s", "idle_by_span_s"):
        names = {k for r in per_chip for k in r[key]}
        out[key] = {k: sum(r[key].get(k, 0.0) for r in per_chip) / chips
                    for k in names}
    return out


def per_iter_us(ctx, scope: str | None = None):
    """Microseconds per ``while_loop`` trip of the traced call: under
    engine scope ``scope``, or of the in-loop gap when it is None; None
    where the trace has no engine program or no trip."""
    split = getattr(ctx, "scopes", None)
    trips = ctx.trace["kernel_launches"] if ctx.trace else 0
    if not split or not trips:
        return None
    s = split["loop_gap_s"] if scope is None else split["scope_s"].get(
        scope, 0.0)
    return s / trips * 1e6


# ---------------------------------------------------------------------------
# A cell's traced call on the chip, split by scope.
# ---------------------------------------------------------------------------
READ = ("iters_per_call", "device_us_per_iter", "idle_share",
        "phase1_us_per_iter", "scheduler_us_per_iter", "apply_us_per_iter",
        "advance_us_per_iter", "loop_us_per_iter", "loop_gap_us_per_iter",
        "entry_idle_ms")


def call_scope_map(traffic, call: int) -> dict | None:
    """The scope map of the program that ``traffic.call(call)`` runs;
    None for a program that cannot make one."""
    try:
        from repro.core.sweep import compiled_scope_map
    except ImportError:
        return None
    wls = traffic.build(traffic.seeds(call))
    return compiled_scope_map(traffic.params, wls,
                              shard=getattr(traffic, "shard", None))


def span_cost_us(n: int = 100_000) -> float:
    """Host microseconds of one program span with no profiler running."""
    import time

    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("eudoxia/run/dispatch"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import tempfile
    import time
    import types

    import generator
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.cells.load_cell(args.workload, run.ROOT)
    run.device_check(cell.chips)
    run.enable_cache()

    from repro.core import SimParams

    params = SimParams.from_dict(cell.params)
    readers = {m: run.cells.load_reader(run.BENCH / "layers" / f"{m}.py")
               for m in READ}
    print(json.dumps({"span_cost_us": span_cost_us()}), flush=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    spans = run.Spans()
    traffic = cell.make_traffic(params, seeds[0])
    traffic.call(generator.WARMUP, spans)  # the cell's shapes, as set-up
    scopes = call_scope_map(traffic, 0)
    for seed in seeds:
        traffic = cell.make_traffic(params, seed)
        t0 = time.perf_counter()
        traffic.call(0, spans)
        untraced = time.perf_counter() - t0
        trace_dir = tempfile.mkdtemp(prefix="bench_scopes_")
        try:
            t0 = time.perf_counter()
            run.trace_call(traffic, spans, trace_dir)
            traced = time.perf_counter() - t0
            reduced = trace_reduce.reduce_dir(trace_dir, cell.chips)
            split = reduce_dir(trace_dir, scopes, cell.chips)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(trace=reduced, scopes=split)
        leaf = sum(split["scope_s"].values())
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "untraced_call_s": untraced,
            # the call under the profiler, from its first host span to
            # its last, and the whole session with the trace's writing
            "traced_call_s": reduced["window_s"], "trace_session_s": traced,
            "metrics": {m: readers[m](ctx) for m in READ},
            "engine_busy_s": reduced["engine_busy_s"],
            "engine_s": split["engine_s"],
            "scopes_plus_gap_s": leaf + split["loop_gap_s"],
            "unscoped_share": split["scope_s"].get("unscoped", 0.0)
            / (leaf or 1.0),
            "scope_s": split["scope_s"],
            "idle_by_span_s": split["idle_by_span_s"],
            "breakdown": split["breakdown"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
