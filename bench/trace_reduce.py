"""Reduce a profiler trace of one call to the numbers the per-layer
readers take: device busy time and idle share, the engine program's
busy time, launches and time of the ``sim_tick`` kernel, the device
ops that took most time, and where the device sat idle.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain records; ``reduce_events`` works on those records alone, so
a test can hand it a small synthetic trace.

A record is ``(kind, name, start_ns, dur_ns, chip)``: ``kind`` is
``"op"`` for an operation on a TPU core (its name is the HLO
instruction's text), ``"module"`` for a program's execution on it,
``"span"`` for one of the benchmark's host spans (``chip`` -1).

On a TPU v5e with jax 0.9.0 an op's trace event carries its HLO text
and times only, not the ``jax.named_scope`` path of its metadata, so
device time is not split by the engine's scopes here.
"""
from __future__ import annotations

import glob
import re

SPAN_NAMES = ("workload_build", "engine_call", "reduce")
# the sim_tick Pallas kernel's instruction (kernels/sim_tick/kernel.py)
KERNEL_MARK = "%fleet_tick_kernel"
ENGINE_MARK = "_fleet_"       # _fleet_compiled / _fleet_sharded programs
# control-flow ops span the ops they run; they count for busy time, but
# not again in the list of ops that took most time
CONTAINERS = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


def load_xplane(path: str) -> list:
    """Records of the TPU cores' ops and programs and of the host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    names: dict = {}  # one string per distinct op text, not per event
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in (OP_LINE, MODULE_LINE):
                    kind = "op" if line.name == OP_LINE else "module"
                    out.extend((kind, names.setdefault(ev.name, ev.name),
                                ev.start_ns, ev.duration_ns, chip)
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(("span", ev.name, ev.start_ns, ev.duration_ns, -1)
                           for ev in line.events if ev.name in SPAN_NAMES)
    return out


def short_name(text: str) -> str:
    """``%fusion.556 = f32[131072]{...} fusion(...), kind=kCustom, ...``
    -> ``%fusion.556 f32[131072] fusion``."""
    m = re.match(r"(%\S+) = (\(?[\w\[\],]*)", text)
    if not m:
        return text[:80]
    name, shape = m.groups()
    op = re.search(r"\} ([\w-]+)\(|\) ([\w-]+)\(", text)
    opcode = next((g for g in op.groups() if g), "") if op else ""
    return f"{name} {shape.rstrip(',(')} {opcode}".strip()


def _opcode(text: str) -> str:
    return short_name(text).rsplit(" ", 1)[-1]


def _union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_events(records: list) -> dict:
    """The reduced trace of one call on one device.

    The window runs from the first to the last host span of the call;
    busy time is the union of op intervals inside it."""
    spans = [(n, s, s + d) for k, n, s, d, _ in records if k == "span"]
    ops = [r for r in records if r[0] == "op"]
    if not spans or not ops:
        raise ValueError("the trace holds no host span or no device op")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy = _union(_clip([(s, s + d) for _, _, s, d, _ in ops], lo, hi))
    busy_ns = sum(e - s for s, e in busy)

    by_text: dict = {}
    kernel_n = 0
    for _, text, s, d, _ in ops:
        d = min(s + d, hi) - max(s, lo)
        if d > 0:
            by_text[text] = by_text.get(text, 0.0) + d
            kernel_n += text.startswith(KERNEL_MARK)
    kernel_ns = sum(v for t, v in by_text.items() if t.startswith(KERNEL_MARK))
    by_name: dict = {}
    for text, v in by_text.items():
        if _opcode(text) not in CONTAINERS:
            name = short_name(text)
            by_name[name] = by_name.get(name, 0.0) + v

    engine = _union(_clip([(s, s + d) for k, n, s, d, _ in records
                           if k == "module" and ENGINE_MARK in n], lo, hi))
    engine_busy_ns = sum(min(e, b) - max(s, a) for s, e in busy
                         for a, b in engine if s < b and e > a)

    # idle gaps between busy intervals, by the host span they fall in
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            where = [n for n, s, e in spans if s <= mid < e]
            gaps.append((where[-1] if where else "between spans", b - a))
    gaps.sort(key=lambda g: -g[1])
    by_where: dict = {}
    for where, g in gaps:
        by_where[where] = by_where.get(where, 0.0) + g

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / (hi - lo),
        "kernel_launches": kernel_n,
        "kernel_s": kernel_ns / 1e9,
        "engine_busy_s": engine_busy_ns / 1e9,
        "idle_by_span_s": {k: v / 1e9 for k, v in by_where.items()},
        "breakdown": {
            "device_ops": [[n, v / 1e9] for n, v in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[w, g / 1e9] for w, g in gaps[:TOP]],
        },
    }


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    """Reduce the one trace ``jax.profiler`` wrote under ``trace_dir``:
    busy, kernel and engine times are averaged over the cell's chips,
    and ``imbalance`` is the busiest chip's engine time over the mean."""
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane under {trace_dir}, got {paths}")
    records = load_xplane(paths[0])
    spans = [r for r in records if r[0] == "span"]
    per_chip = [reduce_events(spans + [r for r in records if r[4] == c])
                for c in range(chips)]
    out = dict(per_chip[0])
    for key in ("busy_s", "kernel_s", "engine_busy_s"):
        out[key] = sum(r[key] for r in per_chip) / len(per_chip)
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    mean = out["engine_busy_s"]
    out["imbalance"] = (max(r["engine_busy_s"] for r in per_chip) / mean
                        if mean else None)
    return out
