"""The harness finds a cell's files by name, counts its work right, and
refuses to run without a chip."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import cell as cells
import generator
import run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_every_cell_resolves_its_files_by_name():
    spec = cells.load_spec(ROOT)
    bench = ROOT / spec["paths"][0]
    for w in spec["workloads"]:
        c = cells.load_cell(w["name"], ROOT)
        assert (bench / "kinds" / f"{c.traffic['kind']}.py").is_file()
        assert issubclass(c.kind.Traffic, generator.LaneTraffic)
        assert c.reference.is_file()
        assert c.config["params"]["duration"] > 0
        assert set(c.config["limits"]) == {
            "state_mismatch", "util_rel_gap", "cost_rel_gap"}
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
        for m in c.per_layer:
            assert callable(c.readers[m["name"]])
        assert [m["name"] for m in c.end_to_end][-1] == "setup_s"
    for m in spec["per_layer"]:
        assert (bench / "layers" / f"{m['name']}.py").is_file()


# a kind of call that only a new file defines: lanes built one by one
# and stacked, then one unsharded fleet_run
NEW_KIND = """
import jax
import jax.numpy as jnp
import repro.core as core
from repro.core.workload import generate_workload

from generator import LaneTraffic


class Traffic(LaneTraffic):
    lanes_per_call = 3

    def build(self, seeds):
        lanes = [generate_workload(self.params, jax.random.PRNGKey(int(s)))
                 for s in seeds]
        return jax.tree.map(lambda *x: jnp.stack(x), *lanes)

    def issue(self, wls):
        return core.fleet_run(self.params, workloads=wls)
"""

# a reference that only a new file defines: the open-loop reference,
# restated through its module
NEW_REFERENCE = """
import pathlib

from cell import load_module

_base = load_module(pathlib.Path(__file__).with_name("open_loop.py"),
                    "reference")
compare = _base.compare


def simulate(job, dtype):
    return _base.simulate_lane(job.params, job.workload, dtype=dtype)
"""


def test_new_files_are_found_with_no_edit(tmp_path):
    """A configuration with a reference of its own, a traffic mix of a
    new kind of call and a per-layer metric, added as files plus
    BENCHMARK.json entries, are picked up by name and run whole."""
    import jax

    from conftest import write_tiny_tree

    root = write_tiny_tree(tmp_path)
    spec = cells.load_spec(root)
    bench = root / spec["paths"][0]
    cfg = json.loads((root / spec["configs"][0]["file"]).read_text())
    cfg["params"]["num_pools"] = 4
    cfg["reference"] = "restated"
    (bench / "configs" / "four_pools.json").write_text(json.dumps(cfg))
    (bench / "references" / "restated.py").write_text(NEW_REFERENCE)
    (bench / "kinds" / "stacked.py").write_text(NEW_KIND)
    (bench / "traffic" / "stacked_lanes.json").write_text(json.dumps(
        {"kind": "stacked", "pool_lanes": 6, "pool_seed": 1,
         "check_lanes_per_call": 2, "check_lanes_max": 4,
         "check_lanes_min": 2}))
    (bench / "layers" / "lanes_seen.py").write_text(
        "def read(ctx):\n    return ctx.calls * 3\n")
    spec["configs"].append({
        "name": "four_pools", "source": "x", "reduced": [], "why": "x",
        "file": "bench/configs/four_pools.json"})
    spec["workloads"].append({
        "name": "four_pool_lanes", "config": "four_pools",
        "traffic": "stacked_lanes", "chips": 1, "why": "x"})
    spec["per_layer"].append({
        "name": "lanes_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "workload", "moves": "sim_s_per_s",
        "workloads": ["four_pool_lanes"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    c = cells.load_cell("four_pool_lanes", root)
    assert c.params["num_pools"] == 4
    assert c.reference == bench / "references" / "restated.py"
    assert c.readers["lanes_seen"](type("Ctx", (), {"calls": 3})) == 9
    res, _ = run.measure(c, 2**33 + 1, 0.1, False, jax.devices()[:1],
                         workers=1)
    assert res["correct"] is True, res["checked"]
    # whole passes over the pool of 6 lanes, 3 to a call
    assert res["attempted"] % 6 == 0 and res["failed"] == 0
    with pytest.raises(KeyError):
        cells.load_cell("no_such_cell", root)


@pytest.mark.parametrize("name", ["mc_fleet", "single_run"])
def test_work_counters_on_a_tiny_fleet(tiny_root, name):
    """Lanes, simulated seconds and failed lanes of each call, counted
    through the traffic module's own functions."""
    from repro.core import SimParams

    c = cells.load_cell(name, tiny_root)
    params = SimParams.from_dict(c.params)
    traffic = c.make_traffic(params, 2**31 + 5)
    spans = run.Spans()
    got = [traffic.call(k, spans) for k in range(2)]
    L = c.traffic.get("lanes_per_call", 1)
    for n in got:
        assert n.lanes == L
        assert n.sim_seconds == L * params.duration
        assert n.failed == 0
        assert n.events > 0
    assert [s[0] for s in spans.spans] == [
        "workload_build", "engine_call", "reduce"] * 2
    # the window's lanes differ from call to call and from the warm-up's
    seeds = [traffic.seeds(k) for k in (generator.WARMUP, 0, 1)]
    assert len(set(np.concatenate(seeds).tolist())) == 3 * L
    assert all(s >= 2**31 for s in seeds[0]) and all(
        s < 2**31 for s in np.concatenate(seeds[1:]))


@pytest.mark.parametrize("name", ["mc_fleet", "single_run"])
def test_every_seed_does_the_same_work_in_another_order(name):
    """Each pass over the pool covers every pool lane once, whatever the
    seed; the seed changes the order."""
    from repro.core import SimParams

    c = cells.load_cell(name, ROOT)
    params = SimParams.from_dict(c.params)
    runs = {}
    for seed in (1, 2**31 + 3):
        t = c.make_traffic(params, seed)
        calls = t.calls_per_pass
        runs[seed] = [t.seeds(k).tolist() for k in range(2 * calls)]
    a, b = runs.values()
    for r in (a, b):
        assert sorted(sum(r[:calls], [])) == sorted(t.pool.tolist())
        assert sorted(sum(r[calls:], [])) == sorted(t.pool.tolist())
    assert a != b


@pytest.mark.parametrize("first,seconds,calls", [
    (0, 1e-9, 4), (1, 1e-9, 3), (0, 0.05, 8)])
def test_the_window_runs_whole_passes(first, seconds, calls):
    """A new call starts while time is left, and on to the end of the
    pass in progress, however short the window."""
    import time

    class Fake:
        lanes_per_call = 2
        calls_per_pass = 4

        def call(self, k, span):
            time.sleep(0.01)
            return generator.CallCount(2, 2.0, 0, 1)

    win = run.window(Fake(), seconds, run.Spans(), run.Compiles(), first)
    assert len(win.counts) == calls


def test_a_whole_run_without_the_chip_check(tiny_root):
    """A run of the unbroken program on the CPU: whole calls only, no
    failed lane, correct, and the numbers compared come last."""
    import jax

    c = cells.load_cell("mc_fleet", tiny_root)
    res, _ = run.measure(c, 11, 0.5, False, jax.devices()[:1], workers=1)
    m = res["metrics"]
    assert res["attempted"] % c.traffic["lanes_per_call"] == 0
    assert res["failed"] == 0 and res["correct"] is True
    assert m["sim_s_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert list(res)[-1] == "checked"


def test_run_refuses_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "mc_fleet", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "metrics" not in p.stdout


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"


def test_benchmark_json_is_well_formed():
    """The shape BENCHMARK.json keeps: keys, names, units, one-line
    texts, every configuration used, every per-layer metric moving an
    end-to-end metric of the cells it lists."""
    import re

    spec = cells.load_spec(ROOT)
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [c["name"] for c in spec["configs"]]
    used = {w["config"] for w in spec["workloads"]}
    assert set(names) == used and len(set(names)) == len(names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(spec["paths"][0] + "/")
    cell_names = [w["name"] for w in spec["workloads"]]
    assert len(set(cell_names)) == len(cell_names)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(cell_names) // 2)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cell_names)
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
    for e in spec["configs"] + spec["workloads"]:
        assert re.match(NAME, e["name"]) and 1 <= len(e["why"]) <= 200
        assert "\n" not in e["why"] and "\t" not in e["why"]
