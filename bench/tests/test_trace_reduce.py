"""The trace reduction on a small synthetic trace of one call: busy
union and idle share, ``sim_tick`` launches as loop trips, the engine
program's busy time, the ops that took most time, and idle gaps by the
host span they fall in."""
from __future__ import annotations

import json
import pathlib

import pytest

import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data" / "synthetic_trace.json"


@pytest.fixture(scope="module")
def records():
    return [tuple(r) for r in json.loads(DATA.read_text())["records"]]


@pytest.fixture(scope="module")
def reduced(records):
    return trace_reduce.reduce_events([r for r in records if r[4] in (-1, 0)])


def test_busy_union_and_idle_share(reduced):
    # union inside [0, 1100): 10-30, 100-710 (the while op covers the
    # engine's ops and the gaps between them), 1010-1020, 1095-1100
    # (the last op is clipped at the window's end)
    busy = 20 + 610 + 10 + 5
    assert reduced["window_s"] == pytest.approx(1100e-9)
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9)
    assert reduced["idle_share"] == pytest.approx(1 - busy / 1100)


def test_sim_tick_launches_are_trips(reduced):
    assert reduced["kernel_launches"] == 3
    assert reduced["kernel_s"] == pytest.approx(30e-9)
    # engine busy: the busy time inside the engine program's execution
    assert reduced["engine_busy_s"] == pytest.approx(610e-9)


def test_ops_that_took_most_time(reduced):
    top = reduced["breakdown"]["device_ops"]
    assert top[0] == ["%fusion.2 f32[64] fusion", pytest.approx(150e-9)]
    assert top[1][1] == pytest.approx(150e-9)  # fusion.4
    names = [n for n, _ in top]
    assert not any("while" in n for n in names)  # containers count once
    assert len(top) <= 10


def test_largest_gaps_by_host_span(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert gaps[0] == ["engine_call", pytest.approx(300e-9)]  # 710-1010
    assert gaps[1] == ["reduce", pytest.approx(75e-9)]        # 1020-1095
    assert gaps[2] == ["workload_build", pytest.approx(70e-9)]  # 30-100
    assert reduced["idle_by_span_s"]["workload_build"] == pytest.approx(
        80e-9)  # 0-10 and 30-100


def test_chips_are_reduced_apart(records, tmp_path, monkeypatch):
    """Two chips: busy time is averaged, and the imbalance is the
    busiest chip's engine time over the mean."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    recs = records + [("module", "jit__fleet_sharded", 700, 300, 1)]
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: recs)
    out = trace_reduce.reduce_dir(str(tmp_path), chips=2)
    assert out["busy_s"] == pytest.approx((645 + 300) / 2 * 1e-9)
    assert out["engine_busy_s"] == pytest.approx((610 + 300) / 2 * 1e-9)
    assert out["imbalance"] == pytest.approx(610 / 455)


def test_short_names():
    assert trace_reduce.short_name(
        "%fusion.556 = f32[131072]{0:T(1024)S(1)} fusion(f32[64,16]{0,1} "
        "%g, s32[131072]{0} %b), kind=kCustom, calls=%f") == (
        "%fusion.556 f32[131072] fusion")
    assert trace_reduce.short_name(
        "%while.5 = (s32[64]{0}, f32[2]{0}) while((s32[64]{0}, f32[2]{0}) "
        "%t), condition=%c, body=%b").endswith("while")
