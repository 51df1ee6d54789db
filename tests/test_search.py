"""The policy-search determinism / property wall.

Three layers:

* **Pure-helper properties** (hypothesis vs independent numpy oracles):
  ``scalarize`` / ``elite_select`` / ``halving_lane_counts`` and the
  Pareto edge cases (ties, NaN guards, single-candidate fronts) — the
  host-side math the CEM driver leans on.
* **Driver invariants**: elites ⊆ full-fidelity survivors, history
  shape, per-generation best score monotone non-increasing (the
  elitist-carryover guarantee).
* **End-to-end determinism**: the whole CEM run — engine evaluations
  included — is byte-identical across two same-seed runs and across
  ``shard=None`` vs ``shard="auto"`` (``SearchResult.to_json()`` is
  the canonical artifact the comparison diffs).

Plus the cross-engine parity leg: the Python reference engine accepts
``PolicyParams`` vectors through the same dynamic ``"policy"`` key and
matches the fused engine's states on them.
"""
import functools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SimParams, generate_workload, run
from repro.core.policy import DEFAULT_POINTS, PolicyParams
from repro.search import (
    PolicySpace,
    cem_search,
    dominates,
    elite_select,
    halving_lane_counts,
    pareto_front,
    scalarize,
    weakly_dominates,
)
from repro.search.grid import OBJECTIVES, evaluate_policies, scenario_factory

# ---------------------------------------------------------------------------
# Pareto edge cases
# ---------------------------------------------------------------------------
def test_pareto_ties_all_stay():
    objs = [[1.0, 2.0], [1.0, 2.0], [1.0, 2.0]]
    assert pareto_front(objs).tolist() == [0, 1, 2]
    assert not dominates(objs[0], objs[1])
    assert weakly_dominates(objs[0], objs[1])


def test_pareto_single_candidate():
    assert pareto_front([[5.0, 5.0, 5.0]]).tolist() == [0]
    assert pareto_front(np.empty((0, 3))).tolist() == []


def test_pareto_nan_guard():
    objs = [[1.0, np.nan], [2.0, 3.0], [np.nan, np.nan]]
    # NaN -> +inf: row 0 survives on its finite column, row 2 is
    # dominated by row 1 (finite everywhere)
    assert pareto_front(objs).tolist() == [0, 1]
    assert dominates(objs[1], objs[2])
    assert not dominates(objs[2], objs[1])
    assert not weakly_dominates(objs[2], objs[1])


def test_pareto_classic_front():
    objs = [[1.0, 4.0], [2.0, 3.0], [3.0, 3.0], [2.0, 5.0]]
    assert pareto_front(objs).tolist() == [0, 1]


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 12),
    seed=st.integers(0, 2**16),
)
def test_pareto_front_oracle(n, seed):
    """Front == brute-force 'no other row strictly dominates me'."""
    rng = np.random.default_rng(seed)
    objs = rng.integers(0, 4, size=(n, 3)).astype(float)  # ties likely
    objs[rng.random(size=n) < 0.15] = np.nan  # NaN rows
    front = set(pareto_front(objs).tolist())
    for i in range(n):
        dominated = any(
            dominates(objs[j], objs[i]) for j in range(n) if j != i
        )
        assert (i not in front) == dominated


# ---------------------------------------------------------------------------
# Pure CEM helpers vs numpy oracles
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 20), seed=st.integers(0, 2**16))
def test_scalarize_oracle(n, seed):
    rng = np.random.default_rng(seed)
    objs = rng.normal(size=(n, 4))
    objs[rng.random(size=(n, 4)) < 0.1] = np.nan
    w = rng.uniform(0.1, 2.0, size=4)
    got = scalarize(objs, w)
    clean = np.where(np.isnan(objs), np.inf, objs)
    want = clean @ w
    want = np.where(np.isfinite(want), want, np.inf)
    np.testing.assert_array_equal(got, want)
    assert (got[np.isnan(objs).any(axis=1)] == np.inf).all()


def test_scalarize_rejects_bad_weights():
    with pytest.raises(ValueError):
        scalarize(np.zeros((2, 4)), weights=(1.0, 2.0))


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 30),
    k=st.integers(1, 30),
    seed=st.integers(0, 2**16),
)
def test_elite_select_oracle(n, k, seed):
    if k > n:
        k = n
    rng = np.random.default_rng(seed)
    scores = rng.integers(0, 5, size=n).astype(float)  # heavy ties
    idx = elite_select(scores, k)
    assert idx.shape == (k,)
    assert len(set(idx.tolist())) == k
    # oracle: stable sort by score keeps index order inside ties
    want = np.argsort(scores, kind="stable")[:k]
    np.testing.assert_array_equal(idx, want)


def test_elite_select_bounds():
    with pytest.raises(ValueError):
        elite_select(np.zeros(3), 0)
    with pytest.raises(ValueError):
        elite_select(np.zeros(3), 4)


@settings(max_examples=25, deadline=None)
@given(
    n_lanes=st.integers(1, 64),
    seed=st.integers(0, 2**16),
)
def test_halving_lane_counts_invariants(n_lanes, seed):
    rng = np.random.default_rng(seed)
    rungs = sorted(rng.uniform(0.05, 1.0, size=rng.integers(1, 4)))
    counts = halving_lane_counts(n_lanes, rungs)
    assert counts[-1] == n_lanes
    assert all(c >= 1 for c in counts)
    assert all(b > a for a, b in zip(counts, counts[1:]))  # strictly up


def test_halving_rejects_bad_fraction():
    with pytest.raises(ValueError):
        halving_lane_counts(8, (0.0, 1.0))
    with pytest.raises(ValueError):
        halving_lane_counts(8, (1.5,))


# ---------------------------------------------------------------------------
# PolicySpace
# ---------------------------------------------------------------------------
def test_space_normalize_roundtrip_on_defaults():
    sp = PolicySpace()
    for name, pt in DEFAULT_POINTS.items():
        vec = pt.to_vector()
        u = sp.normalize(vec)
        assert (u >= 0).all() and (u <= 1).all(), name
        np.testing.assert_allclose(
            sp.denormalize(u), vec, rtol=1e-6, atol=1e-6
        )


def test_space_rejects_bad_bounds():
    lo, hi = PolicySpace().lo, PolicySpace().hi
    with pytest.raises(ValueError):
        PolicySpace(lo=hi, hi=lo)  # hi < lo somewhere
    with pytest.raises(ValueError):
        PolicySpace(lo=lo[:3], hi=hi[:3])


# ---------------------------------------------------------------------------
# End-to-end CEM: determinism + invariants (the expensive leg)
# ---------------------------------------------------------------------------
def _arena() -> SimParams:
    return SimParams(
        duration=0.05,
        seed=0,
        scheduling_algo="policy",
        num_pools=2,
        waiting_ticks_mean=400.0,
        op_base_seconds_mean=0.004,
        max_pipelines=16,
        max_containers=32,
        total_cpus=4,
        total_ram_gb=8,
        cache_gb_per_pool=4.0,
        scan_ticks_per_gb=50.0,
        cold_start_ticks=40,
        container_warm_ticks=2_000,
        cloud_scaling=True,
    )


def _small_search(shard=None, seed=5):
    make = scenario_factory(["bursty"], _arena(), 2, seed=11)
    return cem_search(
        make,
        seed=seed,
        generations=2,
        population=10,
        rungs=(0.5, 1.0),
        shard=shard,
    )


@pytest.fixture(scope="module")
def small_search_result():
    return _small_search()


def test_cem_same_seed_bitwise(small_search_result):
    res2 = _small_search()
    assert small_search_result.to_json() == res2.to_json()


def test_cem_shard_invariant(small_search_result):
    res_sharded = _small_search(shard="auto")
    assert small_search_result.to_json() == res_sharded.to_json()


def test_cem_seed_actually_matters(small_search_result):
    assert small_search_result.to_json() != _small_search(seed=6).to_json()


def test_cem_history_invariants(small_search_result):
    res = small_search_result
    assert len(res.history) == 2
    pop = res.meta["population"]
    for g in res.history:
        n_cand = len(g["policies"])
        assert n_cand == pop
        survivors = g["survivors"]
        elites = g["elites"]
        assert set(elites) <= set(survivors) <= set(range(n_cand))
        # rung lane counts increase, last rung is the full batch
        lanes = [r["lanes"] for r in g["rungs"]]
        assert lanes == res.meta["lane_counts"]
        # the baseline block (indices < B) heads every generation
        B = len(res.baseline_names)
        assert g["origin"][:B] == [
            f"baseline:{n}" for n in res.baseline_names
        ]
    # elitist carryover: best full-fidelity score never worsens
    bests = [g["best_score"] for g in res.history]
    assert all(b >= a for a, b in zip(bests[1:], bests[:-1]))


def test_cem_front_is_nondominated(small_search_result):
    objs = small_search_result.pareto_objectives
    n = objs.shape[0]
    assert n >= 1
    for i in range(n):
        for j in range(n):
            if i != j:
                assert not dominates(objs[j], objs[i])


def test_evaluate_policies_shapes_and_guards():
    make = scenario_factory(["bursty"], _arena(), 2, seed=11)
    pols = np.stack(
        [
            DEFAULT_POINTS["priority"].to_vector(),
            DEFAULT_POINTS["sjf"].to_vector(),
        ]
    )
    res = evaluate_policies(make, pols)
    assert res["C"] == 2 and res["S"] == 2
    assert res["objectives"].shape == (2, len(OBJECTIVES))
    with pytest.raises(ValueError):
        evaluate_policies(make, pols, lane_limit=0)


# ---------------------------------------------------------------------------
# Cross-engine parity: the Python reference accepts PolicyParams too
# ---------------------------------------------------------------------------
PARITY_FIELDS = [
    "pipe_status",
    "pipe_completion",
    "pipe_fails",
    "pipe_preempts",
    "done_count",
    "failed_count",
    "oom_events",
    "preempt_events",
    "cache_hits",
    "cold_starts",
    "bytes_moved_gb",
]


# random points of the search box (drawn in the test, off collection):
# about a quarter are exclusive with preemption on, the mode whose
# grant must neither preempt nor move pools
N_RANDOM_POINTS = 40
# naive mode with preemption switched on: exclusive grants of whole
# pools meet running lower-priority containers in the arena below
EXCLUSIVE_PREEMPT = DEFAULT_POINTS["naive"]._replace(preempt=1.0)
PARITY_POINTS = {
    "priority_pool": DEFAULT_POINTS["priority_pool"],
    "sjf": DEFAULT_POINTS["sjf"],
    # an off-grid point no named scheduler maps to
    "searched": PolicyParams(
        chunk_frac=0.2,
        size_weight=0.5,
        prio_weight=1.0,
        preempt=1.0,
        multi_pool=1.0,
        cache_pin=1.0,
    ),
    "exclusive_preempt": EXCLUSIVE_PREEMPT,
    "exclusive_preempt_multi_pool": EXCLUSIVE_PREEMPT._replace(
        multi_pool=1.0
    ),
    **{f"uniform{i:02d}": i for i in range(N_RANDOM_POINTS)},
}


@functools.lru_cache(maxsize=1)
def _uniform_points() -> np.ndarray:
    import jax

    space = PolicySpace()
    return space.denormalize(
        space.sample_uniform(jax.random.PRNGKey(0), N_RANDOM_POINTS)
    )


@pytest.mark.parametrize("point", PARITY_POINTS.values(),
                         ids=PARITY_POINTS.keys())
def test_python_engine_policy_parity(point):
    # busy enough that arrivals meet running containers of every
    # priority, so exclusive lanes face a preemption they must not make
    params = _arena().replace(max_pipelines=32, duration=0.1)
    vec = (_uniform_points()[point] if isinstance(point, int)
           else point.to_vector())
    wl = generate_workload(params)
    wl = wl._replace(policy=vec)
    fused = run(params, workload=wl, engine="event")
    ref = run(params, workload=wl, engine="python")
    assert int(np.asarray(fused.state.done_count)) > 0  # non-trivial sim
    for f in PARITY_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(fused.state, f)),
            np.asarray(getattr(ref.state, f)),
            err_msg=f"policy-parity/{f}",
        )


def test_python_engine_policy_requires_vector():
    params = _arena()
    wl = generate_workload(params)
    with pytest.raises(ValueError, match="policy"):
        run(params, workload=wl, engine="python")
