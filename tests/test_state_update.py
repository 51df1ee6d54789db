"""Fused executor state-update landings (Pallas phase 3) vs the seed passes.

``kernels/state_update`` replaces the executor's write-side scatters:
``retire_land`` fuses the ``.at[pid].add/max`` retirement landings of
``_apply_retirements``, and ``assign_gather`` lands the assignment rows
collected by ``apply_decision``'s early-exit loop in one one-hot pass
instead of a full-state ``lax.cond`` per slot. The sequential passes
stay exported as the oracles; everything here pins the fused paths to
them bitwise — including the corners the issue calls out (capacity
edge, all-masked decisions, cache-full / LRU ties, simultaneous
retire + release + arrival) — and checks the Pallas kernels against
the jnp references in interpret mode so CPU CI covers them.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SimParams, generate_workload
from repro.core import executor
from repro.core.scheduler import SchedDecision, onehot_take
from repro.core.state import INF_TICK, init_state
from repro.core.types import ContainerStatus, PipeStatus, TICKS_PER_SECOND
from repro.kernels.state_update import (
    assign_gather_ref,
    retire_land,
    retire_land_ref,
)
from repro.kernels.state_update.kernel import (
    assign_gather_kernel,
    retire_land_kernel,
)


def _rng(seed):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# retire_land vs the seed's scatter landing (the exact ops of
# `_apply_retirements`), on arbitrary tables — duplicates included.
# ---------------------------------------------------------------------------
# jitted like the ref: the engine runs both under jit, and the f32
# latency sum's reduction order is only pinned within a compile context
@functools.partial(jax.jit, static_argnames=("timeout_on",))
def _retire_scatter_oracle(
    ctr_pipe, ctr_end, ctr_start, oomed, done, timed_in, arrival, prio,
    tick, timeout_on,
):
    i32 = jnp.int32
    MP = arrival.shape[0]
    retired = oomed | done
    if timeout_on:
        timed = done & timed_in
        done_eff = done & ~timed
    else:
        timed = jnp.zeros_like(done)
        done_eff = done
    pid = jnp.where(retired, ctr_pipe, MP)
    oom_hit = (
        jnp.zeros((MP,), i32).at[pid].add(oomed.astype(i32), mode="drop")
    ) > 0
    done_hit = (
        jnp.zeros((MP,), i32).at[pid].add(done_eff.astype(i32), mode="drop")
    ) > 0
    end_of = (
        jnp.full((MP,), 0, i32)
        .at[pid]
        .max(jnp.where(done_eff, ctr_end, 0), mode="drop")
    )
    timed_hit = (
        jnp.zeros((MP,), i32)
        .at[jnp.where(timed, ctr_pipe, MP)]
        .add(timed.astype(i32), mode="drop")
    ) > 0
    timed_wasted = jnp.sum(jnp.where(timed, tick - ctr_start, 0)).astype(i32)
    lat_s = (end_of - arrival).astype(jnp.float32) / TICKS_PER_SECOND
    lat_s = jnp.where(done_hit, lat_s, 0.0)
    prio_oh = prio[None, :] == jnp.arange(3, dtype=i32)[:, None]
    return (
        oom_hit, done_hit, timed_hit, end_of, timed_wasted,
        jnp.sum(lat_s),
        jnp.sum(jnp.where(prio_oh, lat_s[None, :], 0.0), axis=1),
        jnp.sum(prio_oh & done_hit[None, :], axis=1).astype(i32),
        jnp.sum(done_hit).astype(i32),
        jnp.sum(oom_hit).astype(i32),
    )


def _draw_retire_tables(rng, MC, MP, tick_hi):
    ctr_pipe = jnp.asarray(rng.integers(0, MP, MC), jnp.int32)
    ctr_end = jnp.asarray(rng.integers(0, tick_hi, MC), jnp.int32)
    ctr_start = jnp.asarray(rng.integers(0, tick_hi, MC), jnp.int32)
    oomed = jnp.asarray(rng.random(MC) < 0.3)
    done = jnp.asarray(rng.random(MC) < 0.4)
    timed = jnp.asarray(rng.random(MC) < 0.3)
    arrival = jnp.asarray(rng.integers(0, tick_hi, MP), jnp.int32)
    prio = jnp.asarray(rng.integers(0, 3, MP), jnp.int32)
    tick = jnp.asarray(rng.integers(0, tick_hi), jnp.int32)
    return ctr_pipe, ctr_end, ctr_start, oomed, done, timed, arrival, prio, tick


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    MC=st.sampled_from([1, 8, 32]),
    MP=st.sampled_from([4, 32, 128]),
    # 3 -> duplicate/tie-heavy (many containers of the same pipeline
    # retiring at the same tick), 200000 -> realistic range
    tick_hi=st.sampled_from([3, 200_000]),
    timeout_on=st.booleans(),
)
def test_retire_land_matches_scatter_oracle(seed, MC, MP, tick_hi, timeout_on):
    args = _draw_retire_tables(_rng(seed), MC, MP, tick_hi)
    ref = _retire_scatter_oracle(*args, timeout_on=timeout_on)
    out = retire_land(*args, timeout_on=timeout_on)
    for name, r, o in zip(
        "oom_hit done_hit timed_hit end_of timed_wasted lat_sum lat_prio"
        " done_prio n_done n_oom".split(), ref, out,
    ):
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(o), err_msg=name
        )


def test_retire_land_all_masked_is_identity_shaped():
    # no retirements at all -> every landing output is zero
    MC, MP = 8, 16
    z = jnp.zeros((MC,), bool)
    out = retire_land(
        jnp.zeros((MC,), jnp.int32), jnp.zeros((MC,), jnp.int32),
        jnp.zeros((MC,), jnp.int32), z, z, None,
        jnp.zeros((MP,), jnp.int32), jnp.zeros((MP,), jnp.int32),
        jnp.int32(7),
    )
    for o in out:
        assert not np.asarray(o).any()


# ---------------------------------------------------------------------------
# Fused phase 1 (arrival + release + retirement in one where-chain,
# retirements landed through retire_land) vs the sequential seed
# composition, on states where all three fire simultaneously.
# ---------------------------------------------------------------------------
def _phase1_params(**kw):
    return SimParams(
        duration=0.02, max_pipelines=32, max_containers=16, num_pools=2,
        waiting_ticks_mean=300.0, op_base_seconds_mean=0.005, **kw,
    )


def _random_phase1_state(params, wl, rng, tick):
    """A mid-flight state: some pipelines suspended with releases due at
    ``tick``, some containers running with retirements due at ``tick``,
    and (via the workload draw) arrivals due as well."""
    MP, MC = params.max_pipelines, params.max_containers
    NP = params.num_pools
    state = init_state(params)
    status = rng.choice(
        [int(PipeStatus.EMPTY), int(PipeStatus.WAITING),
         int(PipeStatus.SUSPENDED), int(PipeStatus.RUNNING),
         int(PipeStatus.DONE)],
        MP, p=[0.3, 0.2, 0.2, 0.2, 0.1],
    )
    release = rng.integers(0, int(tick) * 2 + 2, MP)
    ctr_status = rng.choice(
        [int(ContainerStatus.EMPTY), int(ContainerStatus.RUNNING)],
        MC, p=[0.4, 0.6],
    )
    running = ctr_status == int(ContainerStatus.RUNNING)
    end = rng.integers(0, int(tick) * 2 + 2, MC)
    oom = np.where(
        rng.random(MC) < 0.3, rng.integers(0, int(tick) * 2 + 2, MC),
        INF_TICK,
    )
    return state._replace(
        pipe_status=jnp.asarray(status, jnp.int32),
        pipe_release=jnp.asarray(
            np.where(status == int(PipeStatus.SUSPENDED), release, INF_TICK),
            jnp.int32,
        ),
        ctr_status=jnp.asarray(ctr_status, jnp.int32),
        ctr_pipe=jnp.asarray(
            np.where(running, rng.integers(0, MP, MC), -1), jnp.int32
        ),
        ctr_pool=jnp.asarray(
            np.where(running, rng.integers(0, NP, MC), 0), jnp.int32
        ),
        ctr_end=jnp.asarray(np.where(running, end, INF_TICK), jnp.int32),
        ctr_oom=jnp.asarray(np.where(running, oom, INF_TICK), jnp.int32),
        ctr_start=jnp.asarray(
            np.where(running, rng.integers(0, int(tick) + 1, MC), INF_TICK),
            jnp.int32,
        ),
        ctr_cpus=jnp.asarray(
            np.where(running, rng.integers(1, 8, MC), 0.0), jnp.float32
        ),
        ctr_ram=jnp.asarray(
            np.where(running, rng.integers(1, 16, MC), 0.0), jnp.float32
        ),
        ctr_prio=jnp.asarray(
            np.where(running, rng.integers(0, 3, MC), -1), jnp.int32
        ),
        ctr_timed=jnp.asarray(running & (rng.random(MC) < 0.4)),
    )


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    timeout=st.sampled_from([0, 5_000]),
)
def test_fused_phase1_matches_sequential(seed, timeout):
    from repro.kernels.sim_tick import fleet_tick

    params = _phase1_params(
        timeout_ticks=timeout, seed=seed % 97,
    )
    wl = generate_workload(params)
    rng = _rng(seed)
    tick = jnp.int32(rng.integers(1, 2_000))
    state = _random_phase1_state(params, wl, rng, tick)

    # jit both sides: that is how the engine runs them, and it pins the
    # f32 latency-sum reduction order to one compile context
    @jax.jit
    def seq_fn(s, w, t):
        s = executor.process_arrivals(s, w, t)
        s = executor.process_releases(s, t)
        return executor.process_completions(s, w, t, params)

    seq = seq_fn(state, wl, tick)

    ph = fleet_tick(
        state.ctr_status[None], state.ctr_end[None], state.ctr_oom[None],
        state.ctr_cpus[None], state.ctr_ram[None], state.ctr_pool[None],
        state.pipe_status[None], wl.arrival[None], state.pipe_release[None],
        tick[None], num_pools=params.num_pools,
    )
    fused = jax.jit(
        lambda s, w, t, p: executor.apply_fused_phase1(s, w, t, params, p)
    )(state, wl, tick, jax.tree.map(lambda x: x[0], ph))

    for f in seq._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(seq, f)), np.asarray(getattr(fused, f)),
            err_msg=f"phase1 field {f}",
        )


# ---------------------------------------------------------------------------
# apply_decision: fused early-exit landing vs the fori_loop cond-commit
# oracle, bitwise over the whole SimState — duplicates, capacity edges,
# cache-full / LRU-tie draws included.
# ---------------------------------------------------------------------------
def _decision_params(dp, timeout, **kw):
    extra = dict(
        cache_gb_per_pool=2.0,       # tiny -> constant LRU eviction
        scan_ticks_per_gb=50.0,
        cold_start_ticks=40,
        container_warm_ticks=2_000,
    ) if dp else {}
    extra.update(kw)
    extra.setdefault("num_pools", 2)
    return SimParams(
        duration=0.02, max_pipelines=32, max_containers=16,
        waiting_ticks_mean=300.0, op_base_seconds_mean=0.005,
        timeout_ticks=timeout, **extra,
    )


def _draw_decision(rng, params, full_slots=False, empty_decision=False):
    MP, MC = params.max_pipelines, params.max_containers
    K = params.max_assignments_per_tick
    if empty_decision:
        pipes = np.full(K, -1)
    else:
        # duplicates and invalid (-1) slots on purpose; duplicate pipes
        # exercise the carried waiting-mask vs the oracle's status read
        pipes = rng.integers(-1, MP, K)
        pipes[rng.random(K) < 0.3] = rng.integers(0, MP)
    return SchedDecision(
        suspend=jnp.asarray(rng.random(MC) < 0.15),
        reject=jnp.asarray(rng.random(MP) < 0.1),
        assign_pipe=jnp.asarray(pipes, jnp.int32),
        assign_pool=jnp.asarray(
            rng.integers(0, params.num_pools, K), jnp.int32
        ),
        assign_cpus=jnp.asarray(rng.integers(1, 8, K), jnp.float32),
        assign_ram=jnp.asarray(rng.integers(1, 16, K), jnp.float32),
    )


def _assert_states_equal(a, b, ctx):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{ctx}: field {f}",
        )


def _check_decision_case(seed, dp, timeout, full_slots, empty_decision):
    params = _decision_params(dp, timeout, seed=seed % 89)
    wl = generate_workload(params)
    rng = _rng(seed)
    tick = jnp.int32(rng.integers(1, 2_000))
    state = _random_phase1_state(params, wl, rng, tick)
    if full_slots:
        # capacity edge: every container slot occupied -> no assignment
        # can land, whatever the decision says
        state = state._replace(
            ctr_status=jnp.full_like(
                state.ctr_status, int(ContainerStatus.RUNNING)
            )
        )
    # make plenty of pipelines actually waiting so assignments commit
    state = executor.process_arrivals(state, wl, tick + 500)
    dec = _draw_decision(rng, params, full_slots, empty_decision)

    def apply(early_exit, with_aux=False):
        return jax.jit(
            lambda s, w, d, t: executor.apply_decision(
                s, w, d, t, params, early_exit=early_exit, with_aux=with_aux
            )
        )(state, wl, dec, tick)

    oracle = apply(early_exit=False)
    fused = apply(early_exit=True)
    _assert_states_equal(oracle, fused, "early_exit")

    fused_aux, (aux_i, aux_f) = apply(early_exit=True, with_aux=True)
    _assert_states_equal(oracle, fused_aux, "with_aux")

    # the aux is the commit's own intermediates: committed rows name
    # waiting pipelines, and the miss sum is the bytes-moved delta
    aux_i = np.asarray(aux_i)
    aux_f = np.asarray(aux_f)
    valid = aux_i[:, 0] >= 0
    assert ((aux_i[~valid] == np.array([-1, -1, 0, 0])).all())
    assert (aux_f[~valid] == 0.0).all()
    for p in aux_i[valid, 0]:
        assert int(np.asarray(state.pipe_status)[p]) == int(PipeStatus.WAITING)
        assert int(np.asarray(oracle.pipe_status)[p]) == int(PipeStatus.RUNNING)
    np.testing.assert_allclose(
        aux_f[valid, 3].sum(),
        float(oracle.bytes_moved_gb) - float(state.bytes_moved_gb),
        rtol=1e-6,
    )


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    dp=st.booleans(),
    timeout=st.sampled_from([0, 5_000]),
)
def test_fused_assignments_match_fori_oracle(seed, dp, timeout):
    _check_decision_case(seed, dp, timeout, False, False)


def test_fused_assignments_capacity_edge():
    _check_decision_case(3, True, 0, True, False)


def test_fused_assignments_empty_decision():
    _check_decision_case(5, True, 5_000, False, True)


def test_fused_assignments_cache_lru_ties():
    # every assignment lands on pool 0 with identical output sizes: the
    # 2 GB cache is permanently full and eviction constantly tie-breaks
    params = _decision_params(True, 0, seed=13, num_pools=1)
    wl = generate_workload(params)
    rng = _rng(13)
    tick = jnp.int32(1_000)
    state = _random_phase1_state(params, wl, rng, tick)
    state = executor.process_arrivals(state, wl, tick + 500)
    K = params.max_assignments_per_tick
    waiting = np.flatnonzero(
        np.asarray(state.pipe_status) == int(PipeStatus.WAITING)
    )[:K]
    pipes = np.full(K, -1)
    pipes[: len(waiting)] = waiting
    dec = SchedDecision(
        suspend=jnp.zeros((params.max_containers,), bool),
        reject=jnp.zeros((params.max_pipelines,), bool),
        assign_pipe=jnp.asarray(pipes, jnp.int32),
        assign_pool=jnp.zeros((K,), jnp.int32),
        assign_cpus=jnp.full((K,), 2.0, jnp.float32),
        assign_ram=jnp.full((K,), 4.0, jnp.float32),
    )
    oracle = jax.jit(
        lambda s, w, d, t: executor.apply_decision(s, w, d, t, params)
    )(state, wl, dec, tick)
    fused = jax.jit(
        lambda s, w, d, t: executor.apply_decision(
            s, w, d, t, params, early_exit=True
        )
    )(state, wl, dec, tick)
    _assert_states_equal(oracle, fused, "lru_ties")
    assert float(oracle.pool_cache_used[0]) <= params.cache_gb_per_pool


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode) vs the jnp references, batched — what
# the TPU dispatch runs, checked on CPU CI.
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 1000),
    # 6 exercises the fleet-axis padding path (6 % block_fleet=4 != 0)
    F=st.sampled_from([1, 4, 6]),
    timeout_on=st.booleans(),
)
def test_retire_kernel_matches_ref(seed, F, timeout_on):
    rng = _rng(seed)
    MC, MP = 16, 32
    lanes = [_draw_retire_tables(rng, MC, MP, 50_000) for _ in range(F)]
    args = [jnp.stack([lane[i] for lane in lanes]) for i in range(9)]
    ref = retire_land_ref(*args, timeout_on=timeout_on)
    out = retire_land_kernel(
        *args, timeout_on=timeout_on, block_fleet=4, interpret=True
    )
    for name, r, o in zip(
        "oom_hit done_hit timed_hit end_of timed_wasted lat_sum lat_prio"
        " done_prio n_done n_oom".split(), ref, out,
    ):
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(o), err_msg=name
        )


def _draw_assign_rows(rng, F, K, MC, MP, p_valid=0.6):
    """Per-lane assignment rows: valid rows carry unique slots/pipes
    (the loop invariant)."""
    valid = jnp.asarray(rng.random((F, K)) < p_valid)
    slot = jnp.stack([
        jnp.asarray(rng.permutation(MC)[:K], jnp.int32) for _ in range(F)
    ])
    pipe = jnp.stack([
        jnp.asarray(rng.permutation(MP)[:K], jnp.int32) for _ in range(F)
    ])
    pool = jnp.asarray(rng.integers(0, 4, (F, K)), jnp.int32)
    cpus = jnp.asarray(rng.integers(1, 8, (F, K)), jnp.float32)
    ram = jnp.asarray(rng.integers(1, 16, (F, K)), jnp.float32)
    end = jnp.asarray(rng.integers(0, 50_000, (F, K)), jnp.int32)
    oom = jnp.asarray(rng.integers(0, 50_000, (F, K)), jnp.int32)
    prio = jnp.asarray(rng.integers(0, 3, (F, K)), jnp.int32)
    warm = jnp.asarray(rng.random((F, K)) < 0.5)
    timed = jnp.asarray(rng.random((F, K)) < 0.3)
    return (valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), F=st.sampled_from([1, 4, 6]))
def test_assign_kernel_matches_ref(seed, F):
    K, MC, MP = 8, 16, 32
    args = _draw_assign_rows(_rng(seed), F, K, MC, MP)
    ref = assign_gather_ref(*args, max_containers=MC, max_pipelines=MP)
    out = assign_gather_kernel(
        *args, max_containers=MC, max_pipelines=MP, block_fleet=4,
        interpret=True,
    )
    for i, (r, o) in enumerate(zip(ref, out)):
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(o), err_msg=f"output {i}"
        )


# ---------------------------------------------------------------------------
# assign_gather_ref vs a plain ``.at[].set`` scatter, at the shapes of the
# benchmark's fleet cell: 64 lanes, K = 16 rows, 64 containers, 2048
# pipelines.
# ---------------------------------------------------------------------------
FLEET_F, FLEET_K, FLEET_MC, FLEET_MP = 64, 16, 64, 2048
ASSIGN_OUT = (
    "hit_c l_pipe l_pool l_cpus l_ram l_end l_oom l_prio l_warm l_timed"
    " hit_p l_pcpus l_pram".split()
)


@functools.partial(jax.jit, static_argnames=("MC", "MP"))
def _assign_scatter_oracle(
    valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed, MC, MP
):
    def lane(valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm,
             timed):
        # invalid rows point past the end and are dropped
        sv = jnp.where(valid, slot, MC)
        pv = jnp.where(valid, pipe, MP)

        def on(idx, n, x):
            return jnp.zeros((n,), x.dtype).at[idx].set(x, mode="drop")

        c = [on(sv, MC, x) for x in (
            pipe, pool, cpus, ram, end, oom, prio, warm, timed)]
        return (
            on(sv, MC, valid), *c,
            on(pv, MP, valid), on(pv, MP, cpus), on(pv, MP, ram),
        )

    return jax.vmap(lane)(
        valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed
    )


def _fleet_assign_case(case):
    F, K, MC, MP = FLEET_F, FLEET_K, FLEET_MC, FLEET_MP
    rng = _rng(14)
    p_valid = {"random": 0.6, "all_invalid": 0.0, "all_valid": 1.0,
               "extremes": 0.8}[case]
    rows = list(_draw_assign_rows(rng, F, K, MC, MP, p_valid))
    if case == "extremes":
        f32 = np.finfo(np.float32)
        floats = np.array(
            [-0.0, 0.0, f32.max, -f32.max, f32.tiny, f32.smallest_subnormal,
             np.inf, -np.inf, 1.0], np.float32,
        )
        ints = np.array(
            [np.iinfo(np.int32).min, np.iinfo(np.int32).max, -1, 0],
            np.int32,
        )
        for i in (4, 5):  # cpus, ram
            rows[i] = jnp.asarray(rng.choice(floats, (F, K)))
        for i in (6, 7):  # end, oom
            rows[i] = jnp.asarray(rng.choice(ints, (F, K)))
        # bools on every row, both values in every lane
        rows[9] = jnp.asarray(np.arange(F * K).reshape(F, K) % 2 == 0)
        rows[10] = ~rows[9]
    return tuple(rows)


def _bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize(
    "case", ["random", "all_invalid", "all_valid", "extremes"]
)
def test_assign_land_matches_scatter_oracle(case):
    F, MC, MP = FLEET_F, FLEET_MC, FLEET_MP
    args = _fleet_assign_case(case)
    want = _assign_scatter_oracle(*args, MC=MC, MP=MP)
    got = assign_gather_ref(*args, max_containers=MC, max_pipelines=MP)
    for name, w, g in zip(ASSIGN_OUT, want, got):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        # bitwise: -0.0 must land as -0.0, not as 0.0
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=name)
    n_hit = int(np.asarray(got[0]).sum())
    if case == "all_invalid":
        assert n_hit == 0
    elif case == "all_valid":
        assert n_hit == F * FLEET_K
    else:
        assert 0 < n_hit < F * FLEET_K


def test_assign_land_lowers_without_gather():
    """The vmapped landing at the fleet cell's shapes compiles with no
    ``gather``: under ``vmap`` a read at a traced index is a batched
    gather, which a TPU runs one output element at a time."""
    F, K, MC, MP = FLEET_F, FLEET_K, FLEET_MC, FLEET_MP
    args = _fleet_assign_case("random")
    hlo = assign_gather_ref.lower(
        *args, max_containers=MC, max_pipelines=MP
    ).compile().as_text()
    assert not re.search(r"\bgather\(", hlo)
    # the check sees the op it looks for: a vmapped dynamic read is one
    probe = jax.jit(jax.vmap(lambda x, i: x[i])).lower(
        jnp.zeros((F, MP), jnp.float32), jnp.zeros((F, K), jnp.int32)
    ).compile().as_text()
    assert re.search(r"\bgather\(", probe)


@pytest.mark.parametrize("dtype", ["bool", "int32", "float32"])
def test_onehot_take_matches_indexing(dtype):
    """``onehot_take`` reads the executor's K rows per lane bitwise like
    ``x[idx]``, and the vmapped read lowers with no ``gather``."""
    F, K, MP = FLEET_F, FLEET_K, FLEET_MP
    rng = _rng(15)
    if dtype == "bool":
        x = rng.random((F, MP)) < 0.5
    elif dtype == "int32":
        x = rng.integers(np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                         (F, MP), dtype=np.int32)
    else:
        f32 = np.finfo(np.float32)
        x = rng.choice(np.array(
            [-0.0, 0.0, f32.max, -f32.max, f32.smallest_subnormal, np.inf,
             -np.inf, 0.5], np.float32), (F, MP))
    x = jnp.asarray(x)
    idx = jnp.asarray(rng.integers(0, MP, (F, K)), jnp.int32)
    take = jax.jit(jax.vmap(onehot_take))
    np.testing.assert_array_equal(
        _bits(take(x, idx)), _bits(jax.vmap(lambda a, i: a[i])(x, idx))
    )
    hlo = take.lower(x, idx).compile().as_text()
    assert not re.search(r"\bgather\(", hlo)


def test_dispatch_kernel_impl_matches_ref():
    rng = _rng(7)
    args = _draw_retire_tables(rng, 16, 32, 10_000)
    batched = tuple(
        jnp.broadcast_to(a, (4,) + a.shape) for a in args
    )
    a = retire_land(*batched, timeout_on=True)
    b = retire_land(*batched, timeout_on=True, impl="kernel", interpret=True)
    for r, o in zip(a, b):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))
