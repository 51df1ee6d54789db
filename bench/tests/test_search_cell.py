"""The ``policy_search`` cell on the CPU: its plain reference agrees with
the program on a tiny tree, the control in bfloat16 and faults planted
under the search are not correct, the reference agrees with the Python
engine on random policies, and a traced call holds the spans the
per-layer readers need."""
from __future__ import annotations

import json
import shutil
import types

import jax
import numpy as np
import pytest

import cell as cells
import check
import run
import trace_reduce
import repro.search.grid
from repro.core import SimParams, generate_workload
from repro.core import run as sim_run
from repro.core.policy import DEFAULT_POINTS
from repro.search import PolicySpace
from test_correct import FAULTS

CELL = "policy_search"
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def grid_root(tiny_root, tmp_path_factory):
    """The tiny tree with a wider mix: 4 candidates x 2 scenario lanes a
    call, so a call holds both named points and uniform draws and every
    lane of it is checked."""
    root = tmp_path_factory.mktemp("tiny_search") / "tree"
    shutil.copytree(tiny_root, root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = next(w for w in spec["workloads"] if w["name"] == CELL)
    path = root / spec["paths"][0] / "traffic" / f"{cfg['traffic']}.json"
    mix = json.loads(path.read_text())
    mix.update(lanes_per_call=8, scenario_lanes=2, pool_lanes=16,
               check_lanes_per_call=8)
    path.write_text(json.dumps(mix))
    return root


def _measure(root, seed=SEED):
    c = cells.load_cell(CELL, root)
    res, _ = run.measure(c, seed, 0.3, False, jax.devices()[:1], workers=1)
    return res


@pytest.mark.parametrize("tree", ["tiny", "grid"])
def test_the_reference_agrees_with_the_search(tiny_root, grid_root, tree):
    res = _measure(tiny_root if tree == "tiny" else grid_root)
    assert res["correct"] is True, res["checked"]
    assert res["checked"]["state_mismatch"]["value"] == 0


def test_control_in_bfloat16_is_not_correct(grid_root):
    """The reference computed in bfloat16 in place of the program's
    float32 fails the comparison on every seed tried."""
    c = cells.load_cell(CELL, grid_root)
    limits = dict(c.config["limits"], lanes_compared=1)
    for seed in (3, 4, 2**31 + 7):
        params = SimParams.from_dict(c.params)
        traffic = c.make_traffic(params, seed)
        spans = run.Spans()
        for k in range(2):
            traffic.call(k, spans)
        jobs = traffic.jobs(dict(c.params))
        for job, out in zip(jobs, check.run_reference(
                c.reference, jobs, 1, "bfloat16")):
            job.program = out
        ok, rows = check.verdict(check.compare(
            c.reference, jobs, check.run_reference(c.reference, jobs, 1)),
            limits, 0)
        assert not ok, rows


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_under_the_search_is_not_correct(grid_root, monkeypatch,
                                               fault):
    broken = FAULTS[fault](repro.search.grid.fleet_run)
    monkeypatch.setattr(repro.search.grid, "fleet_run", broken)
    res = _measure(grid_root)
    assert res["correct"] is False, res["checked"]


def test_a_call_runs_each_candidate_on_every_scenario(grid_root):
    c = cells.load_cell(CELL, grid_root)
    traffic = c.make_traffic(SimParams.from_dict(c.params), SEED)
    assert (traffic.S, traffic.C, traffic.calls_per_pass) == (2, 4, 2)
    named = np.stack([p.to_vector() for p in DEFAULT_POINTS.values()])
    np.testing.assert_array_equal(traffic.candidates[:6], named)
    draws = [traffic.seeds(k) for k in range(4)]
    for d in draws:
        np.testing.assert_array_equal(d.scenarios, traffic.scenarios)
    # each pass evaluates the whole pool once, in another order
    for first in (0, 2):
        seen = np.concatenate([draws[first].candidates,
                               draws[first + 1].candidates])
        assert sorted(map(tuple, seen)) == sorted(
            map(tuple, traffic.candidates))
    warm = traffic.seeds(-1)
    assert not set(warm.scenarios) & set(traffic.scenarios)
    assert warm.candidates.shape == (4, 15)


def _random_policies(n):
    space = PolicySpace()
    drawn = space.denormalize(space.sample_uniform(jax.random.PRNGKey(3), n))
    excl = DEFAULT_POINTS["naive"]._replace(preempt=1.0).to_vector()
    return np.concatenate([drawn, excl[None]])


def test_the_reference_equals_the_python_engine_on_random_policies(
        tiny_root):
    """``references/policy_grid.py`` against ``engine="python"`` on
    lanes of the cell's configuration under random policies and the
    exclusive mode with preemption on."""
    c = cells.load_cell(CELL, tiny_root)
    params = SimParams.from_dict(c.params)
    ref = cells.load_module(c.reference, "reference")
    jobs, answers = [], []
    for lane, vec in enumerate(_random_policies(4)):
        wl = generate_workload(params, jax.random.PRNGKey(lane))
        wl = wl._replace(policy=vec)
        state = sim_run(params, workload=wl, engine="python").state
        job = types.SimpleNamespace(
            params=dict(c.params),
            workload={**{f: np.asarray(getattr(wl, f)) for f in
                         ("arrival", "prio", "op_valid", "op_level",
                          "op_ram", "op_base", "op_alpha", "pipe_out")},
                      "policy": vec},
            program={f: np.asarray(getattr(state, f)) for f in
                     ref._base.EXACT_FIELDS + ("util_cpu_s",
                                               "cost_dollars")})
        assert int(job.program["done_count"]) > 0
        jobs.append(job)
        answers.append(ref.simulate(job))
    nums = ref.compare(jobs, answers)
    assert nums["state_mismatch"] == 0, nums
    limits = c.config["limits"]
    assert nums["util_rel_gap"] <= limits["util_rel_gap"], nums
    assert nums["cost_rel_gap"] <= limits["cost_rel_gap"], nums


def test_a_traced_call_holds_the_benchmark_spans(grid_root, tmp_path):
    """The traced path the per-layer readers take: a profile of one
    call of the cell, loaded as the harness loads it, holds the
    benchmark's three host spans around the whole search, so the reduced
    window covers the tiling, the engine and the objectives."""
    c = cells.load_cell(CELL, grid_root)
    traffic = c.make_traffic(SimParams.from_dict(c.params), SEED)
    spans = run.Spans()
    traffic.call(-1, spans)
    run.trace_call(traffic, spans, str(tmp_path))
    path, = tmp_path.glob("**/*.xplane.pb")
    records = trace_reduce.load_xplane(str(path))
    assert [r[1] for r in records if r[0] == "span"] == [
        "workload_build", "engine_call", "reduce"]
