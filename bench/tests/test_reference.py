"""The benchmark's plain reference agrees with the program's Python
engine (``engine="python"``) and with its compiled engine, bitwise on
every field the check compares, and refuses what it does not model."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

import generator
from cell import BENCH_DIR, load_module
from repro.core import SimParams, run
from repro.core.policy import DEFAULT_POINTS, policy_bounds
from repro.core.workload import generate_workload

reference = load_module(BENCH_DIR / "references" / "open_loop.py",
                        "reference")

PARAMS = SimParams(duration=2.0, max_pipelines=96, num_pools=2,
                   scheduling_algo="priority", max_containers=16)


def _lane(params, seed, policy=None):
    wl = generate_workload(params, jax.random.PRNGKey(seed))
    arrays = {f: np.asarray(getattr(wl, f)) for f in generator.WL_FIELDS}
    if policy is not None:
        wl = wl._replace(policy=np.asarray(policy, np.float32))
    return wl, arrays


def _assert_same(state, ref):
    for f in reference.EXACT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(state, f)), ref[f],
                                      err_msg=f)
    np.testing.assert_allclose(np.asarray(state.util_cpu_s),
                               ref["util_cpu_s"], rtol=1e-5)
    np.testing.assert_allclose(float(state.cost_dollars),
                               ref["cost_dollars"], rtol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("engine", ["python", "event"])
def test_priority_lane_matches_the_program(seed, engine):
    wl, arrays = _lane(PARAMS, seed)
    ref = reference.simulate_lane(dataclasses.asdict(PARAMS), arrays)
    assert ref["done_count"] > 0
    _assert_same(run(PARAMS, workload=wl, engine=engine).state, ref)


@pytest.mark.parametrize("seed", [3, 4, 5, 6])
def test_policy_lane_matches_the_program(seed):
    """Random points of the policy family, naive-mode switches and
    multi-pool choice included."""
    params = PARAMS.replace(scheduling_algo="policy")
    lo, hi = policy_bounds()
    u = np.random.default_rng(seed).random(lo.shape[0]).astype(np.float32)
    pol = (lo + u * (hi - lo)).astype(np.float32)
    wl, arrays = _lane(params, seed, pol)
    ref = reference.simulate_lane(dataclasses.asdict(params), arrays,
                                  policy=pol)
    _assert_same(run(params, workload=wl, engine="python").state, ref)


def test_priority_is_its_policy_point():
    np.testing.assert_array_equal(
        np.asarray(reference.NAMED_POLICIES["priority"], np.float32),
        DEFAULT_POINTS["priority"].to_vector())
    assert reference.POLICY_FIELDS == DEFAULT_POINTS["priority"]._fields


@pytest.mark.parametrize("knob", ["crash_mtbf_ticks", "cache_gb_per_pool",
                                  "client_max_inflight"])
def test_unmodelled_mechanisms_are_refused(knob):
    _, arrays = _lane(PARAMS, 0)
    params = dataclasses.asdict(PARAMS.replace(**{knob: 1}))
    with pytest.raises(ValueError):
        reference.simulate_lane(params, arrays)
