"""The split of a traced call by engine scope, on a small synthetic trace
of one ``run()`` call: leaf-op time per scope with control-flow ops left
out, the in-loop gap, device idle by the innermost program span, the
refusals (an op missing from the map, clocks that disagree), and the
readers of the per-layer metrics built on it."""
from __future__ import annotations

import json
import pathlib
import types

import pytest

import cell as cells
import scopes

DATA = pathlib.Path(__file__).resolve().parent / "data" / "synthetic_scopes.json"
LAYERS = pathlib.Path(scopes.__file__).resolve().parent / "layers"


@pytest.fixture(scope="module")
def trace():
    d = json.loads(DATA.read_text())
    return [tuple(r) for r in d["records"]], d["scopes"]


@pytest.fixture(scope="module")
def split(trace):
    records, smap = trace
    return scopes.reduce_events([r for r in records if r[4] in (-1, 0)], smap)


def test_leaf_time_by_scope(split):
    # the while ops (600 ns and the nested 70 ns) are containers: their
    # leaf ops count, they do not; the nested loop's op counts once
    assert split["scope_s"] == pytest.approx({
        "phase1": 20e-9, "scheduler": 160e-9, "apply": 210e-9,
        "advance": 30e-9, "loop": 40e-9, "unscoped": 20e-9})
    assert split["breakdown"]["scopes"][0] == ["apply", pytest.approx(210e-9)]


def test_in_loop_gap(split):
    # 200-210, 450-455, 515-530, 600-610, 720-800 inside the program
    assert split["engine_s"] == pytest.approx(600e-9)
    assert split["loop_gap_s"] == pytest.approx(120e-9)
    assert sum(split["scope_s"].values()) + split["loop_gap_s"] == (
        pytest.approx(split["engine_s"]))


def test_idle_by_innermost_program_span(split):
    assert split["idle_by_span_s"] == pytest.approx({
        "workload_build": 80e-9,             # 0-10, 30-100
        "eudoxia/run/prepare": 30e-9,        # 100-110, 130-150
        "eudoxia/workload/generate": 20e-9,  # 110-130, nested in prepare
        "eudoxia/run/dispatch": 50e-9,       # 150-200
        "engine_call": 120e-9,               # 800-900, 980-1000
        "eudoxia/run/unpack": 70e-9,         # 900-920, 930-980
        "reduce": 100e-9})
    # the entry point's spans, not the workload span nested in them
    assert split["entry_idle_s"] == pytest.approx(150e-9)
    gaps = split["breakdown"]["idle_gaps_in_program"]
    assert gaps[:3] == [["engine_call", pytest.approx(100e-9)],
                        ["reduce", pytest.approx(100e-9)],
                        ["workload_build", pytest.approx(70e-9)]]


def test_an_op_missing_from_the_map_raises(trace):
    records, smap = trace
    smap = {k: v for k, v in smap.items() if k != "fusion.3"}
    with pytest.raises(KeyError, match="fusion.3"):
        scopes.reduce_events([r for r in records if r[4] in (-1, 0)], smap)


@pytest.mark.parametrize("start,dur", [(140, 600), (200, 850)])
def test_clocks_that_disagree_raise(trace, start, dur):
    """An engine program that starts before the dispatch span, or runs
    past the benchmark's ``engine_call`` span, is refused."""
    records, smap = trace
    moved = [("module", n, start, dur, c) if k == "module" and "_fleet_" in n
             else (k, n, s, d, c) for k, n, s, d, c in records if c in (-1, 0)]
    with pytest.raises(ValueError, match="clocks disagree"):
        scopes.reduce_events(moved, smap)


def test_no_engine_program_gives_none(trace):
    records, smap = trace
    assert scopes.reduce_events(
        [r for r in records if r[4] == -1 or r[1] == "jit_squeeze"
         or r[1].startswith("%squeeze")], smap) is None


def test_chips_are_averaged(trace, tmp_path, monkeypatch):
    records, smap = trace
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    host = [r for r in records if r[0] == "span"]
    monkeypatch.setattr(scopes.trace_reduce, "load_xplane", lambda p: [
        r for r in records if not r[1].startswith("eudoxia/")])
    monkeypatch.setattr(scopes, "load_program_spans", lambda p: [
        r for r in host if r[1].startswith("eudoxia/")])
    out = scopes.reduce_dir(str(tmp_path), smap, chips=2)
    assert out["scope_s"]["apply"] == pytest.approx((210 + 100) / 2 * 1e-9)
    assert out["scope_s"]["phase1"] == pytest.approx(20 / 2 * 1e-9)
    assert out["engine_s"] == pytest.approx((600 + 100) / 2 * 1e-9)
    assert out["loop_gap_s"] == pytest.approx(120 / 2 * 1e-9)


@pytest.mark.parametrize("metric,want", [
    ("phase1_us_per_iter", 20e-9 / 2 * 1e6),
    ("scheduler_us_per_iter", 160e-9 / 2 * 1e6),
    ("apply_us_per_iter", 210e-9 / 2 * 1e6),
    ("advance_us_per_iter", 30e-9 / 2 * 1e6),
    ("loop_us_per_iter", 40e-9 / 2 * 1e6),
    ("loop_gap_us_per_iter", 120e-9 / 2 * 1e6),
    ("entry_idle_ms", 150e-9 * 1e3),
])
def test_readers(split, metric, want):
    """Each reader on the synthetic call (two trips); None where the
    trace has no engine program, or the harness gives no split."""
    read = cells.load_reader(LAYERS / f"{metric}.py")
    trace = {"kernel_launches": 2}
    assert read(types.SimpleNamespace(trace=trace, scopes=split)) == (
        pytest.approx(want))
    assert read(types.SimpleNamespace(trace=trace, scopes=None)) is None
    assert read(types.SimpleNamespace(trace=trace)) is None


@pytest.mark.parametrize("name", ["mc_fleet", "single_run"])
def test_the_map_of_a_cell_s_call(tiny_root, name, monkeypatch):
    """The map of the program each kind of call runs names the engine's
    scopes; a program that cannot make one gives None, and no split."""
    import repro.core.sweep as sweep
    from repro.core import SimParams

    c = cells.load_cell(name, tiny_root)
    traffic = c.make_traffic(SimParams.from_dict(c.params), 2**31 + 9)
    smap = scopes.call_scope_map(traffic, 0)
    assert {"phase1", "scheduler", "apply", "advance", "loop"} <= set(
        smap.values())
    monkeypatch.delattr(sweep, "compiled_scope_map")
    assert scopes.call_scope_map(traffic, 0) is None
    assert scopes.reduce_dir("no_trace_here", None) is None
