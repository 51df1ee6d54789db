"""Profiling the program: every instruction of the engine's loop names
its engine scope (``engine.scope_map``), and a ``jax.profiler`` trace of
``run()`` / ``fleet_run()`` holds the program's host spans, nested and
in order."""
from __future__ import annotations

import glob
import re

import jax
import pytest

from repro.core import SimParams
from repro.core.engine import ENGINE_SCOPES, UNSCOPED, _fleet_compiled, scope_map
from repro.core.sweep import (
    _fleet_sharded,
    compiled_scope_map,
    fleet_run,
    make_workload_batch,
)

TINY = dict(duration=0.05, max_pipelines=16, max_containers=8,
            max_ops_per_pipeline=4)
MODES = {
    "open_loop": ({}, 0, set()),
    "faults_traced": (
        dict(crash_mtbf_ticks=500.0, outage_mtbf_ticks=800.0,
             outage_duration_ticks=100.0), 64, {"faults", "telemetry"}),
    "closed_loop": (
        dict(client_max_inflight=4, client_think_ticks=50,
             admission_policy="queue_threshold", admit_queue_limit=4), 0,
        {"closed_loop"}),
}
_HEADER = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+) = .*?\s([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|branch_computations|to_apply)="
                     r"\{?%?([^\s,}]+)")


def instructions(hlo: str) -> dict:
    """``{computation: [(name, opcode, op_name or None, line)]}``."""
    comps: dict = {}
    comp = None
    for line in hlo.splitlines():
        h = _HEADER.match(line)
        if h:
            comp = comps.setdefault(h.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            op = _OP_NAME.search(line)
            comp.append((m.group(1), m.group(2), op and op.group(1), line))
    return comps


def launched_in_loop(comps: dict) -> list:
    """The instructions the engine's while_loop launches: those of its
    body and cond and of every loop, branch or call inside them."""
    entry_while = [ins for c in comps.values() for ins in c
                   if ins[1] == "while" and ins[2] is not None
                   and ins[2].endswith("jit(_fleet_compiled)/while")]
    assert entry_while, "no engine while_loop in the HLO"
    todo = [n for ins in entry_while for n in re.findall(
        r"(?:body|condition)=%?([^\s,}]+)", ins[3])]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ins in comps[c]:
            out.append(ins)
            if ins[1] in ("while", "conditional", "call"):
                for names in _CALLED.findall(ins[3]):
                    todo.extend(n.lstrip("%") for n in names.split(","))
    return out


def _tiny(mode: str, lanes: int = 2):
    kw, cap, _ = MODES[mode]
    p = SimParams(**TINY, **kw)
    wls = make_workload_batch(p, list(range(lanes)))
    if p.fault_trace_active:
        from repro.core.faults import attach_fault_traces

        wls = attach_fault_traces(wls, p)
    return p, wls, cap


def _check_loop(hlo: str, smap: dict, want: set):
    comps = instructions(hlo)
    loop = launched_in_loop(comps)
    launched = [i for i in loop if i[1] in ("fusion", "custom-call")]
    assert launched
    for name, opcode, op_name, _ in launched:
        # every one traced from the loop's code names an engine scope;
        # XLA's own (copies, hoisted broadcasts) carry no metadata, or
        # only the name of the jit they were made in
        if op_name is not None and "/while/" in op_name:
            assert smap[name] in ENGINE_SCOPES, (name, op_name)
        else:
            assert op_name is None or op_name.endswith(
                "jit(_fleet_compiled)"), (name, op_name)
    seen = {smap[i[0]] for i in launched}
    assert {"phase1", "scheduler", "apply", "advance", "loop"} | want <= seen
    for c in comps.values():
        for name, _, op_name, _ in c:
            if op_name and "fleet_tick" in op_name:
                assert smap[name] == "phase1", (name, op_name)
            if op_name and "/while/" in op_name and "select_n" in op_name:
                assert smap[name] != UNSCOPED, (name, op_name)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_scope_map_covers_the_loop(mode):
    p, wls, cap = _tiny(mode)
    hlo = _fleet_compiled.lower(
        p, wls, p.scheduling_algo, "auto", trace_capacity=cap
    ).compile().as_text()
    smap = scope_map(hlo)
    _check_loop(hlo, smap, MODES[mode][2])
    # the helper maps the program fleet_run runs for these lanes
    assert compiled_scope_map(p, wls, trace_capacity=cap) == smap


@pytest.mark.skipif(jax.local_device_count() < 2,
                    reason="needs more than one device")
def test_sharded_scope_map_covers_the_loop():
    p, wls, _ = _tiny("open_loop", lanes=3)
    n = jax.local_device_count()
    smap = compiled_scope_map(p, wls, shard="auto")
    padded = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape[1:], x.dtype), wls)
    hlo = _fleet_sharded.lower(
        p, padded, p.scheduling_algo, "auto", n).compile().as_text()
    assert scope_map(hlo) == smap
    _check_loop(hlo, smap, set())


def test_scope_of_an_op_name():
    from repro.core.engine import _scope_of

    assert _scope_of("jit(_fleet_sharded)/shard_map/jit(_fleet_compiled)/"
                     "while/body/vmap(apply)/jit(_where)/select_n") == "apply"
    assert _scope_of("jit(_fleet_compiled)/while/cond/loop/reduce_or") == (
        "loop")
    assert _scope_of("jit(_fleet_compiled)/jit(sort)/sort") == UNSCOPED


def _program_spans(fn, tmp_path):
    from jax.profiler import ProfileData

    jax.block_until_ready(fn())  # compile off the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("eudoxia/")]
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.mark.parametrize("call,want", [
    ("run", ["eudoxia/run/prepare", "eudoxia/workload/generate",
             "eudoxia/run/dispatch", "eudoxia/run/unpack"]),
    ("fleet_seeds", ["eudoxia/fleet/prepare", "eudoxia/workload/batch",
                     "eudoxia/workload/generate", "eudoxia/fleet/dispatch"]),
    ("fleet_sharded_traced", [
        "eudoxia/fleet/prepare", "eudoxia/workload/batch",
        "eudoxia/workload/generate", "eudoxia/fleet/bin",
        "eudoxia/fleet/dispatch", "eudoxia/fleet/unbin",
        "eudoxia/fleet/decode"]),
])
def test_program_spans_nest_in_order(call, want, tmp_path):
    from repro.core import run

    p = SimParams(**TINY)
    calls = {
        "run": lambda: run(p).state,
        "fleet_seeds": lambda: fleet_run(p, seeds=[0, 1]),
        "fleet_sharded_traced": lambda: fleet_run(
            p, seeds=[0, 1, 2], shard="auto", trace=True,
            trace_capacity=64)[0],
    }
    if call == "fleet_sharded_traced" and jax.local_device_count() < 2:
        pytest.skip("needs more than one device")
    spans = _program_spans(calls[call], tmp_path)
    assert [n for _, _, n in spans] == want
    by = {n: (s, e) for s, e, n in spans}
    top = [n for n in want if "/workload/" not in n]
    for a, b in zip(top, top[1:]):
        assert by[a][1] <= by[b][0], (a, b)  # one after the other
    # the workload is built inside the entry point's prepare span
    prepare = by[top[0]]
    assert _inside(by["eudoxia/workload/generate"], prepare)
    if "eudoxia/workload/batch" in by:
        assert _inside(by["eudoxia/workload/batch"], prepare)
        assert _inside(by["eudoxia/workload/generate"],
                       by["eudoxia/workload/batch"])


def test_policy_search_spans_and_states(tmp_path):
    """``evaluate_policies`` marks its grid and its objectives with host
    spans around ``fleet_run``'s, and returns the grid's final states
    bitwise as ``fleet_run`` gives them on the same grid."""
    import numpy as np

    from repro.core.policy import DEFAULT_POINTS
    from repro.core.sweep import policy_grid_workloads
    from repro.search.grid import evaluate_policies

    p = SimParams(**TINY, scheduling_algo="policy")
    pols = np.stack([DEFAULT_POINTS[n].to_vector()
                     for n in ("naive", "priority", "sjf")])

    def make():
        return make_workload_batch(p, [0, 1]), p

    res = {}

    def search():
        res["r"] = evaluate_policies(make, pols)
        return res["r"]["states"]

    spans = _program_spans(search, tmp_path)
    names = [n for _, _, n in spans if n.startswith("eudoxia/")
             and not n.startswith("eudoxia/workload/")]
    assert names == ["eudoxia/search/grid", "eudoxia/fleet/prepare",
                     "eudoxia/fleet/dispatch", "eudoxia/search/objectives"]
    by = {n: (s, e) for s, e, n in spans}
    for a, b in zip(names, names[1:]):
        assert by[a][1] <= by[b][0], (a, b)  # one after the other

    grid, C, S = policy_grid_workloads(make_workload_batch(p, [0, 1]), pols)
    want = fleet_run(p, workloads=grid)
    got = res["r"]["states"]
    assert got.done_count.shape == (C * S,)
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f)
