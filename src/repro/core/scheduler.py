"""Schedulers (paper §3.2.3, §4.1.2).

A scheduler "accept[s] a set of Pipelines from the workload generator,
and output[s] a list of new Container allocations and Container
preemptions to the Executor". In the compiled engines this is a pure
function over the struct-of-arrays state:

    fn(sched_state, sim: SimState, wl: Workload, params) ->
        (sched_state, SchedDecision)

``SchedDecision`` carries fixed-capacity arrays (suspension mask over
containers, rejection mask over pipelines, up to K new assignments).

Three built-ins mirror §4.1.2:

* ``naive``          — one pool; all resources to the head of the queue.
* ``priority``       — 10 % chunks, OOM-retry doubling capped at 50 %,
                       preemption of lower-priority containers.
* ``priority_pool``  — ditto, but allocates on the pool with the most
                       available resources.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.sched_select import masked_lex_argmin

from .params import SimParams
from .policy import DEFAULT_POINTS, N_POLICY_PARAMS, PolicyParams
from .state import INF_TICK, SimState, Workload
from .types import ContainerStatus, PipeStatus, Priority

EPS = 1e-5


class SchedDecision(NamedTuple):
    suspend: jax.Array      # [MC] bool — containers to preempt
    reject: jax.Array       # [MP] bool — pipelines failed back to the user
    assign_pipe: jax.Array  # [K] int32 (-1 = unused slot)
    assign_pool: jax.Array  # [K] int32
    assign_cpus: jax.Array  # [K] f32
    assign_ram: jax.Array   # [K] f32


def empty_decision(params: SimParams) -> SchedDecision:
    K = params.max_assignments_per_tick
    return SchedDecision(
        suspend=jnp.zeros((params.max_containers,), bool),
        reject=jnp.zeros((params.max_pipelines,), bool),
        assign_pipe=jnp.full((K,), -1, jnp.int32),
        assign_pool=jnp.zeros((K,), jnp.int32),
        assign_cpus=jnp.zeros((K,), jnp.float32),
        assign_ram=jnp.zeros((K,), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Masked selection helpers (queue semantics without materialised queues):
# waiting order = priority desc, then (re-)entry tick asc, then pid asc.
#
# These three-pass forms are the *oracles*: the schedulers below run the
# fused ``repro.kernels.sched_select.masked_lex_argmin`` instead (one
# narrowing sweep, Pallas on TPU), which tests/test_sched_select.py
# property-tests bitwise against these on the engine's domain.
# ---------------------------------------------------------------------------
def select_next_pipe(mask: jax.Array, prio: jax.Array, entered: jax.Array):
    any_ = jnp.any(mask)
    p = jnp.where(mask, prio, -1)
    m2 = mask & (prio == jnp.max(p))
    e = jnp.where(m2, entered, INF_TICK)
    m3 = m2 & (entered == jnp.min(e))
    idx = jnp.argmax(m3).astype(jnp.int32)
    return jnp.where(any_, idx, -1)


def select_victim(
    live: jax.Array, ctr_prio: jax.Array, ctr_start: jax.Array, below_prio: jax.Array
):
    """Preemption victim: lowest priority, then latest start (least progress
    lost). ``below_prio`` is the exclusive priority upper bound."""
    m = live & (ctr_prio < below_prio)
    any_ = jnp.any(m)
    p = jnp.where(m, ctr_prio, jnp.int32(2**30))
    m2 = m & (ctr_prio == jnp.min(p))
    s = jnp.where(m2, ctr_start, -1)
    m3 = m2 & (ctr_start == jnp.max(s))
    idx = jnp.argmax(m3).astype(jnp.int32)
    return jnp.where(any_, idx, -1)


def onehot_set(arr: jax.Array, idx: jax.Array, val):
    """``arr.at[idx].set(val)`` as an elementwise select.

    Dynamic-index scatters lower to a serialized ``while`` thunk per
    scatter on XLA:CPU under the engine's per-lane ``vmap``; the
    one-hot select stays elementwise. Bitwise identical: only position
    ``idx`` takes ``val``, every other element is passed through."""
    iota = jnp.arange(arr.shape[0], dtype=jnp.int32)
    return jnp.where(iota == idx, val, arr)


def onehot_add(arr: jax.Array, idx: jax.Array, val):
    """``arr.at[idx].add(val)`` as an elementwise select (see
    :func:`onehot_set`). Exact: the selected element is the same single
    ``arr[idx] + val`` the scatter-add performs; the rest pass through
    untouched (no reassociation anywhere)."""
    iota = jnp.arange(arr.shape[0], dtype=jnp.int32)
    return jnp.where(iota == idx, arr + val, arr)


def onehot_take(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """``arr[idx]`` for a 1-D ``arr`` of 32-bit or bool values and
    in-range ``idx``, as a one-hot masked reduction.

    Under the engine's per-lane ``vmap`` a read at a traced index is a
    batched ``gather``, which a TPU v5e runs one output element at a
    time (~8.8 ns each); this stays compare, select and reduce. Exact
    for every value: one position matches, and the sum is of its int32
    bits (``any`` for bools). An out-of-range ``idx`` reads 0."""
    oh = idx[..., None] == jnp.arange(arr.shape[0], dtype=jnp.int32)
    if arr.dtype == jnp.bool_:
        return jnp.any(oh & arr, axis=-1)
    bits = jax.lax.bitcast_convert_type(arr, jnp.int32)
    s = jnp.sum(jnp.where(oh, bits, 0), axis=-1, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(s, arr.dtype)


# ---------------------------------------------------------------------------
# Decision-slot loop runner shared by the K-assignment schedulers.
# ---------------------------------------------------------------------------
def decision_loop(step, K: int, carry0, early_exit: bool):
    """Run ``step(k, carry) -> (carry, keep_going)`` over the K decision
    slots. With ``early_exit`` the loop stops at the first ``keep_going
    = False`` — valid whenever later iterations are provable no-ops
    (the waiting-queue mask can only shrink); under vmap the while_loop
    trip count becomes the max over lanes of actual queue length. This
    knob is what parameterises a scheduler *family* in the unified
    registry below: both variants are bitwise-identical, and the
    lane-major core compiles ``early_exit=True``."""
    if early_exit:

        def w_cond(c):
            k, go, _ = c
            return (k < K) & go

        def w_body(c):
            k, _, carry = c
            carry, go = step(k, carry)
            return k + 1, go, carry

        *_, carry = jax.lax.while_loop(
            w_cond, w_body, (jnp.int32(0), jnp.bool_(True), carry0)
        )
        return carry

    def body(k, carry):
        carry, _ = step(k, carry)
        return carry

    return jax.lax.fori_loop(0, K, body, carry0)


# ---------------------------------------------------------------------------
# NAIVE (paper §4.1.2): single pool, everything to the queue head, no
# concurrency, no preemption. A pipeline that OOMed with all resources can
# never succeed -> permanent failure.
# ---------------------------------------------------------------------------
def naive_scheduler(
    sched_state: Any, sim: SimState, wl: Workload, params: SimParams
):
    dec = empty_decision(params)
    waiting = sim.pipe_status == int(PipeStatus.WAITING)
    # fail-back: it already had every resource, doubling is impossible
    reject = waiting & sim.pipe_fail_flag
    waiting = waiting & ~reject

    idle = ~jnp.any(sim.ctr_status == int(ContainerStatus.RUNNING))
    pipe = masked_lex_argmin(waiting, (-wl.prio, sim.pipe_entered))
    do = idle & (pipe >= 0)
    dec = dec._replace(
        reject=reject,
        assign_pipe=dec.assign_pipe.at[0].set(jnp.where(do, pipe, -1)),
        assign_pool=dec.assign_pool.at[0].set(0),
        assign_cpus=dec.assign_cpus.at[0].set(sim.pool_cpu_cap[0]),
        assign_ram=dec.assign_ram.at[0].set(sim.pool_ram_cap[0]),
    )
    return sched_state, dec


def decision_provenance(sim: SimState, wl: Workload, dec: SchedDecision):
    """``(chosen, runner_up)`` pipeline ids behind a decision's first
    assignment slot — the runner-up is the pipeline the head-of-queue
    rule (priority desc, arrival asc) would have picked had the chosen
    one not been waiting. Both are -1 when not applicable. Used by the
    telemetry recorder for SCHED_DECISION provenance records; reads
    only, never part of the simulation step."""
    chosen = dec.assign_pipe[0]
    waiting = sim.pipe_status == int(PipeStatus.WAITING)
    others = waiting & (
        jnp.arange(wl.max_pipelines, dtype=jnp.int32) != chosen
    )
    runner = masked_lex_argmin(others, (-wl.prio, sim.pipe_entered))
    return chosen, jnp.where(chosen >= 0, runner, -1)


# ---------------------------------------------------------------------------
# PRIORITY / PRIORITY-POOL (paper §4.1.2) and the data-plane variants
# (cache_aware / locality_pool, registered from extra_schedulers.py).
#
# ``pool_mode`` picks the pool-selection rule; every rule is mirrored
# f32-op-for-op by ``engine_python._pool_select_py``:
#   "single"   — always pool 0 (paper ``priority``)
#   "free"     — most free resources (paper ``priority_pool``)
#   "cache"    — pool holding the pipeline's parent outputs, else "free"
#   "locality" — "free" score with a small bonus for pools holding any
#                of the pipeline's data (locality tie-break)
# ---------------------------------------------------------------------------
LOCALITY_BONUS = 1e-3


def _pool_select(pool_mode: str, free_cpu, free_ram, sim: SimState, pipe_c):
    if pool_mode == "single":
        return jnp.int32(0)
    score = free_cpu / jnp.maximum(sim.pool_cpu_cap, EPS) + (
        free_ram / jnp.maximum(sim.pool_ram_cap, EPS)
    )
    if pool_mode == "free":
        return jnp.argmax(score).astype(jnp.int32)
    row = sim.cache_bytes[:, pipe_c]  # [NP] bytes of this pipe's data
    if pool_mode == "cache":
        return jnp.where(
            jnp.max(row) > 0, jnp.argmax(row), jnp.argmax(score)
        ).astype(jnp.int32)
    if pool_mode == "locality":
        bonus = jnp.where(row > 0, jnp.float32(LOCALITY_BONUS), 0.0)
        return jnp.argmax(score + bonus).astype(jnp.int32)
    raise ValueError(f"unknown pool_mode {pool_mode!r}")


def _priority_like(pool_mode: str, early_exit: bool = False):
    """The generalised priority scheduler family.

    ``early_exit=True`` swaps the fixed K-iteration ``fori_loop`` for a
    ``while_loop`` that stops as soon as the waiting queue is exhausted
    (once ``select_next_pipe`` returns -1 the candidate mask can only
    shrink, so every later iteration is a no-op). Bitwise-identical
    decisions; the lane-major core compiles the early-exit variant so
    events with short queues stop paying K sequential scheduler steps.
    """
    multi_pool = pool_mode != "single"

    def scheduler(
        sched_state: Any, sim: SimState, wl: Workload, params: SimParams
    ):
        K = params.max_assignments_per_tick
        NP = params.num_pools
        total_cpu = jnp.sum(sim.pool_cpu_cap)
        total_ram = jnp.sum(sim.pool_ram_cap)
        chunk_cpu = 0.10 * total_cpu
        chunk_ram = 0.10 * total_ram
        cap_cpu = 0.50 * total_cpu
        cap_ram = 0.50 * total_ram

        dec = empty_decision(params)
        free_cpu = sim.pool_cpu_free
        free_ram = sim.pool_ram_free
        live = sim.ctr_status == int(ContainerStatus.RUNNING)
        waiting0 = sim.pipe_status == int(PipeStatus.WAITING)
        # OOMed at the RAM cap already -> return failure to the user.
        reject = waiting0 & sim.pipe_fail_flag & (sim.pipe_last_ram >= cap_ram - EPS)
        dec = dec._replace(reject=reject)
        # Fused-selection keys, hoisted out of the decision loop: the
        # candidate masks are the only per-slot inputs (``tried`` grows,
        # ``live`` shrinks); priorities and entry/start ticks are fixed
        # for the whole decision, so each slot pays one narrowing sweep
        # instead of re-deriving the three-pass reductions.
        head_keys = (-wl.prio, sim.pipe_entered)
        victim_keys = (sim.ctr_prio, -sim.ctr_start)
        base_mask = waiting0 & ~reject

        def step(k, carry):
            dec, free_cpu, free_ram, live, tried = carry
            mask = base_mask & ~tried
            pipe = masked_lex_argmin(mask, head_keys)
            valid = pipe >= 0
            pipe_c = jnp.maximum(pipe, 0)

            failed = sim.pipe_fail_flag[pipe_c]
            seen = sim.pipe_last_ram[pipe_c] > 0.0
            # doubling for OOM retries; same-as-last for preempted pipes;
            # 10% chunk for fresh arrivals (paper §4.1.2)
            want_cpu = jnp.where(
                failed,
                jnp.minimum(2.0 * sim.pipe_last_cpus[pipe_c], cap_cpu),
                jnp.where(seen, sim.pipe_last_cpus[pipe_c], chunk_cpu),
            )
            want_ram = jnp.where(
                failed,
                jnp.minimum(2.0 * sim.pipe_last_ram[pipe_c], cap_ram),
                jnp.where(seen, sim.pipe_last_ram[pipe_c], chunk_ram),
            )

            pool = _pool_select(pool_mode, free_cpu, free_ram, sim, pipe_c)

            fits = (free_cpu[pool] >= want_cpu - EPS) & (
                free_ram[pool] >= want_ram - EPS
            )

            # ---- preemption path: high-priority pipe, no room ------------
            can_preempt = valid & ~fits & (wl.prio[pipe_c] > int(Priority.BATCH))
            victim = masked_lex_argmin(
                live & (sim.ctr_prio < wl.prio[pipe_c]), victim_keys
            )
            has_victim = can_preempt & (victim >= 0)
            victim_c = jnp.maximum(victim, 0)
            vpool = sim.ctr_pool[victim_c]
            free_cpu2 = jnp.where(
                has_victim,
                onehot_add(free_cpu, vpool, sim.ctr_cpus[victim_c]),
                free_cpu,
            )
            free_ram2 = jnp.where(
                has_victim,
                onehot_add(free_ram, vpool, sim.ctr_ram[victim_c]),
                free_ram,
            )
            live2 = jnp.where(
                has_victim, onehot_set(live, victim_c, False), live
            )
            if multi_pool:
                pool2 = jnp.where(
                    has_victim,
                    vpool,
                    _pool_select(pool_mode, free_cpu2, free_ram2, sim, pipe_c),
                ).astype(jnp.int32)
            else:
                pool2 = pool
            fits2 = (free_cpu2[pool2] >= want_cpu - EPS) & (
                free_ram2[pool2] >= want_ram - EPS
            )

            do = valid & (fits | (has_victim & fits2))
            use_pool = jnp.where(fits, pool, pool2)
            # commit preemption only when it actually enables the assignment
            commit_victim = has_victim & ~fits & fits2
            suspend = jnp.where(
                commit_victim,
                onehot_set(dec.suspend, victim_c, True),
                dec.suspend,
            )
            free_cpu3 = jnp.where(commit_victim, free_cpu2, free_cpu)
            free_ram3 = jnp.where(commit_victim, free_ram2, free_ram)
            live3 = jnp.where(commit_victim, live2, live)

            free_cpu4 = jnp.where(
                do, onehot_add(free_cpu3, use_pool, -want_cpu), free_cpu3
            )
            free_ram4 = jnp.where(
                do, onehot_add(free_ram3, use_pool, -want_ram), free_ram3
            )
            dec = dec._replace(
                suspend=suspend,
                assign_pipe=onehot_set(
                    dec.assign_pipe, k, jnp.where(do, pipe_c, -1)
                ),
                assign_pool=onehot_set(dec.assign_pool, k, use_pool),
                assign_cpus=onehot_set(dec.assign_cpus, k, want_cpu),
                assign_ram=onehot_set(dec.assign_ram, k, want_ram),
            )
            # whether assigned or blocked, don't reconsider this pipe today
            tried = jnp.where(valid, onehot_set(tried, pipe_c, True), tried)
            return (dec, free_cpu4, free_ram4, live3, tried), valid

        tried0 = jnp.zeros((params.max_pipelines,), bool)
        carry0 = (dec, free_cpu, free_ram, live, tried0)
        dec, *_ = decision_loop(step, K, carry0, early_exit)
        return sched_state, dec

    return scheduler


# ---------------------------------------------------------------------------
# THE PARAMETERISED SCHEDULER FAMILY (policy search substrate).
#
# One decision loop generalising every built-in: the hard-coded knobs of
# ``naive_scheduler`` / ``_priority_like`` / ``extra_schedulers._sjf_like``
# become the flat f32 :class:`repro.core.policy.PolicyParams` vector.
# Evaluated at a named scheduler's ``DEFAULT_POINTS`` entry the family
# makes BITWISE-identical decisions (the zero-weight lead key is a
# constant and narrows nothing; disabled preemption passes every carry
# through; a zero locality bonus adds +0.0 to a nonnegative pool score;
# f32 images of the small-int priorities compare exactly), so the final
# states — and the 48-config digest grid in tests/captures/ — are
# preserved verbatim. tests/test_policy_family.py asserts this identity
# against the legacy implementations above, which remain as oracles.
#
# Two modes:
#   * static point (named schedulers): the point's floats are baked
#     into the jaxpr as constants — ``register_vector_scheduler_family
#     (key, params=point)`` wires the registry;
#   * dynamic (key ``"policy"``): the vector is read from
#     ``wl.policy``, so a vmapped fleet evaluates a different candidate
#     policy per lane in ONE compiled program (repro.search).
# ---------------------------------------------------------------------------
def _policy_pool_select(pol: PolicyParams, free_cpu, free_ram,
                        sim: SimState, pipe_c):
    """Knob-driven pool selection generalising :func:`_pool_select`.

    ``multi_pool`` off reproduces "single" (pool 0); on, the most-free
    score rule with ``locality_bonus`` added where the pipe has cached
    data ("free" at bonus 0, "locality" at 1e-3) and ``cache_pin``
    overriding to the best caching pool when one exists ("cache")."""
    score = free_cpu / jnp.maximum(sim.pool_cpu_cap, EPS) + (
        free_ram / jnp.maximum(sim.pool_ram_cap, EPS)
    )
    row = sim.cache_bytes[:, pipe_c]  # [NP] bytes of this pipe's data
    bonus = jnp.where(row > 0, jnp.float32(pol.locality_bonus),
                      jnp.float32(0.0))
    best = jnp.argmax(score + bonus)
    use_cache = (pol.cache_pin > 0.5) & (jnp.max(row) > 0)
    pool = jnp.where(use_cache, jnp.argmax(row), best)
    return jnp.where(pol.multi_pool > 0.5, pool, 0).astype(jnp.int32)


def _policy_family(early_exit: bool, static_policy: PolicyParams | None):
    """Build the parameterised scheduler (see the block comment above).

    ``static_policy`` is a :class:`PolicyParams` of python floats (the
    named-scheduler points) or None, which reads the traced vector from
    ``wl.policy`` — the per-lane axis policy-grid fleets vmap over."""

    def scheduler(
        sched_state: Any, sim: SimState, wl: Workload, params: SimParams
    ):
        if static_policy is not None:
            pol = PolicyParams(
                *(jnp.float32(v) for v in static_policy)
            )
        else:
            if wl.policy is None:
                raise ValueError(
                    "scheduler 'policy' needs a workload with a policy "
                    "vector attached; see repro.search.attach_policies / "
                    "sweep.policy_grid_workloads"
                )
            vec = wl.policy.astype(jnp.float32)
            pol = PolicyParams(*(vec[i] for i in range(N_POLICY_PARAMS)))

        K = params.max_assignments_per_tick
        total_cpu = jnp.sum(sim.pool_cpu_cap)
        total_ram = jnp.sum(sim.pool_ram_cap)
        chunk_cpu = pol.chunk_frac * total_cpu
        chunk_ram = pol.chunk_frac * total_ram
        cap_cpu = pol.cap_frac * total_cpu
        cap_ram = pol.cap_frac * total_ram

        preempt_on = pol.preempt > 0.5
        excl_on = pol.exclusive > 0.5
        grab_on = pol.grab_all > 0.5
        gate_on = pol.ram_gate > 0.5

        dec = empty_decision(params)
        live0 = sim.ctr_status == int(ContainerStatus.RUNNING)
        idle0 = ~jnp.any(live0)
        waiting0 = sim.pipe_status == int(PipeStatus.WAITING)
        # OOM fail-back: at the RAM cap already (ram_gate on), or any
        # prior OOM at all (ram_gate off — the naive rule: it held every
        # resource, doubling is impossible)
        over_cap = sim.pipe_last_ram >= cap_ram - EPS
        reject = waiting0 & sim.pipe_fail_flag & jnp.where(
            gate_on, over_cap, True
        )
        dec = dec._replace(reject=reject)

        # fused-selection keys, hoisted out of the decision loop. The
        # f32 lead key mixes sjf-vs-fifo ordering; at all-zero weights
        # it is constantly +0.0 (every term is a product with +0.0 over
        # nonnegative finite operands) and the narrowing sweep passes
        # the mask through untouched.
        prio_f = wl.prio.astype(jnp.float32)
        lead = (
            pol.size_weight * wl.n_ops.astype(jnp.float32)
            + pol.age_weight * sim.pipe_entered.astype(jnp.float32)
            - pol.prio_weight * prio_f
        )
        head_keys = (lead, -wl.prio, sim.pipe_entered)
        victim_keys = (sim.ctr_prio, -sim.ctr_start)
        ctr_prio_f = sim.ctr_prio.astype(jnp.float32)
        base_mask = waiting0 & ~reject

        def step(k, carry):
            dec, free_cpu, free_ram, live, tried, assigned = carry
            mask = base_mask & ~tried
            pipe = masked_lex_argmin(mask, head_keys)
            valid = pipe >= 0
            pipe_c = jnp.maximum(pipe, 0)

            failed = sim.pipe_fail_flag[pipe_c]
            seen = sim.pipe_last_ram[pipe_c] > 0.0
            want_cpu = jnp.where(
                failed,
                jnp.minimum(pol.retry_mult * sim.pipe_last_cpus[pipe_c],
                            cap_cpu),
                jnp.where(seen, sim.pipe_last_cpus[pipe_c], chunk_cpu),
            )
            want_ram = jnp.where(
                failed,
                jnp.minimum(pol.retry_mult * sim.pipe_last_ram[pipe_c],
                            cap_ram),
                jnp.where(seen, sim.pipe_last_ram[pipe_c], chunk_ram),
            )

            pool = _policy_pool_select(pol, free_cpu, free_ram, sim, pipe_c)
            # naive's grab-everything grant: the chosen pool's full caps
            want_cpu = jnp.where(grab_on, sim.pool_cpu_cap[pool], want_cpu)
            want_ram = jnp.where(grab_on, sim.pool_ram_cap[pool], want_ram)

            fits = (free_cpu[pool] >= want_cpu - EPS) & (
                free_ram[pool] >= want_ram - EPS
            )

            # ---- preemption path: gated by the policy knobs -------------
            can_preempt = (
                valid & ~fits & preempt_on
                & (prio_f[pipe_c] > pol.preempt_min_prio)
            )
            victim = masked_lex_argmin(
                live & (ctr_prio_f < prio_f[pipe_c] - pol.victim_prio_gap),
                victim_keys,
            )
            has_victim = can_preempt & (victim >= 0)
            victim_c = jnp.maximum(victim, 0)
            vpool = sim.ctr_pool[victim_c]
            free_cpu2 = jnp.where(
                has_victim,
                onehot_add(free_cpu, vpool, sim.ctr_cpus[victim_c]),
                free_cpu,
            )
            free_ram2 = jnp.where(
                has_victim,
                onehot_add(free_ram, vpool, sim.ctr_ram[victim_c]),
                free_ram,
            )
            live2 = jnp.where(
                has_victim, onehot_set(live, victim_c, False), live
            )
            pool2_multi = jnp.where(
                has_victim,
                vpool,
                _policy_pool_select(pol, free_cpu2, free_ram2, sim, pipe_c),
            ).astype(jnp.int32)
            pool2 = jnp.where(pol.multi_pool > 0.5, pool2_multi, pool)
            fits2 = (free_cpu2[pool2] >= want_cpu - EPS) & (
                free_ram2[pool2] >= want_ram - EPS
            )

            do_norm = valid & (fits | (has_victim & fits2))
            # exclusive (naive) mode: idle cluster, one assignment, no
            # fits test — the grant is the full pool anyway
            do_excl = valid & idle0 & ~assigned
            do = jnp.where(excl_on, do_excl, do_norm)
            # exclusive mode never preempts: its one grant lands on the
            # chosen pool, and a grant that does not fit waits for an
            # idle cluster instead of suspending a victim
            use_pool = jnp.where(fits | excl_on, pool, pool2)
            commit_victim = has_victim & ~fits & fits2 & ~excl_on
            suspend = jnp.where(
                commit_victim,
                onehot_set(dec.suspend, victim_c, True),
                dec.suspend,
            )
            free_cpu3 = jnp.where(commit_victim, free_cpu2, free_cpu)
            free_ram3 = jnp.where(commit_victim, free_ram2, free_ram)
            live3 = jnp.where(commit_victim, live2, live)

            free_cpu4 = jnp.where(
                do, onehot_add(free_cpu3, use_pool, -want_cpu), free_cpu3
            )
            free_ram4 = jnp.where(
                do, onehot_add(free_ram3, use_pool, -want_ram), free_ram3
            )
            dec = dec._replace(
                suspend=suspend,
                assign_pipe=onehot_set(
                    dec.assign_pipe, k, jnp.where(do, pipe_c, -1)
                ),
                assign_pool=onehot_set(dec.assign_pool, k, use_pool),
                assign_cpus=onehot_set(dec.assign_cpus, k, want_cpu),
                assign_ram=onehot_set(dec.assign_ram, k, want_ram),
            )
            assigned = assigned | do
            tried = jnp.where(valid, onehot_set(tried, pipe_c, True), tried)
            return (dec, free_cpu4, free_ram4, live3, tried, assigned), valid

        tried0 = jnp.zeros((params.max_pipelines,), bool)
        carry0 = (
            dec, sim.pool_cpu_free, sim.pool_ram_free, live0, tried0,
            jnp.bool_(False),
        )
        dec, *_ = decision_loop(step, K, carry0, early_exit)
        return sched_state, dec

    return scheduler


def policy_family_make(point: PolicyParams | None, early_exit: bool):
    """Family factory for the registry: ``make(early_exit)`` with the
    policy point partially applied (``functools.partial``-friendly)."""
    return _policy_family(early_exit, point)


# ---------------------------------------------------------------------------
# Vector-scheduler registry (the compiled lane-major core). The
# Python-API registry (paper Listing 4 decorators) lives in
# ``algorithm.py``.
#
# ONE registry of scheduler *families*: a family is a factory
# ``make(early_exit: bool) -> scheduler`` over the existing
# ``decision_loop(early_exit=...)`` knob. Both variants of a family make
# bitwise-identical decisions; ``early_exit=True`` (what the engine
# compiles) trades the fixed K-iteration loop for a while_loop that
# vmaps into max-over-lanes trip counts. Plain schedulers (the custom
# user path) register a single function that serves both variants.
# Builds are cached per (key, early_exit) so repeated lookups hand jit
# the same callable.
# ---------------------------------------------------------------------------
VectorScheduler = Callable[
    [Any, SimState, Workload, SimParams], tuple[Any, SchedDecision]
]
SchedulerFamily = Callable[[bool], VectorScheduler]

_VECTOR_FAMILIES: dict[str, SchedulerFamily] = {}
_VECTOR_INITS: dict[str, Callable[[SimParams], Any]] = {}
_BUILT: dict[tuple[str, bool], VectorScheduler] = {}
# scheduler key -> the PolicyParams point it sits at in the policy
# space (the ``params=`` registry axis). Only schedulers registered
# with a point appear; the dynamic "policy" family reads its vector
# from the workload instead and is deliberately absent.
_POLICY_POINTS: dict[str, PolicyParams] = {}
# early-exit overrides installed via the deprecated fleet-registry shim;
# kept separate so (re-)registering a plain scheduler cannot clobber
# them — registration order stays irrelevant, as under the old dual
# registries. Dies with the shim.
_SHIM_EARLY_EXIT: dict[str, VectorScheduler] = {}


def _norm(key: str) -> str:
    return key.replace("-", "_").lower()


def _invalidate(k: str) -> None:
    _BUILT.pop((k, False), None)
    _BUILT.pop((k, True), None)
    if k in _SHIM_EARLY_EXIT:
        _BUILT[(k, True)] = _SHIM_EARLY_EXIT[k]


def register_vector_scheduler(key: str):
    """Register a plain lane-major scheduler (used for both variants)."""

    def deco(fn: VectorScheduler) -> VectorScheduler:
        k = _norm(key)
        _VECTOR_FAMILIES[k] = lambda early_exit, _fn=fn: _fn
        _invalidate(k)
        return fn

    return deco


def register_vector_scheduler_family(
    key: str, params: PolicyParams | None = None
):
    """Register a scheduler family ``make(early_exit: bool) -> fn``.

    With ``params=`` (the policy-search axis) the decorated factory is
    instead called ``make(params, early_exit)`` — pass
    :func:`policy_family_make` to place a named scheduler at a
    :class:`PolicyParams` point of the parameterised family — and the
    point is recorded for :func:`get_policy_point`, so searches can seed
    populations from (and compare against) every named scheduler.
    """

    def deco(make) -> SchedulerFamily:
        k = _norm(key)
        if params is not None:
            _VECTOR_FAMILIES[k] = functools.partial(make, params)
            _POLICY_POINTS[k] = params
        else:
            _VECTOR_FAMILIES[k] = make
            _POLICY_POINTS.pop(k, None)
        _invalidate(k)
        return make

    return deco


def get_policy_point(key: str) -> PolicyParams:
    """The :class:`PolicyParams` point scheduler ``key`` sits at.

    Raises ``KeyError`` for schedulers registered without ``params=``
    (custom schedulers, the dynamic "policy" family itself).
    """
    k = _norm(key)
    if k not in _POLICY_POINTS:
        raise KeyError(
            f"scheduler {key!r} has no registered policy point; "
            f"pointed schedulers: {sorted(_POLICY_POINTS)}"
        )
    return _POLICY_POINTS[k]


def has_policy_point(key: str) -> bool:
    return _norm(key) in _POLICY_POINTS


def policy_points() -> dict[str, PolicyParams]:
    """All named schedulers with a policy point (search baselines)."""
    return dict(_POLICY_POINTS)


def register_vector_scheduler_init(key: str):
    def deco(fn: Callable[[SimParams], Any]):
        _VECTOR_INITS[_norm(key)] = fn
        return fn

    return deco


def get_vector_scheduler(key: str, early_exit: bool = False) -> VectorScheduler:
    k = _norm(key)
    if k not in _VECTOR_FAMILIES:
        raise KeyError(
            f"unknown scheduler {key!r}; registered: "
            f"{sorted(_VECTOR_FAMILIES)}"
        )
    ck = (k, bool(early_exit))
    if ck not in _BUILT:
        _BUILT[ck] = _VECTOR_FAMILIES[k](bool(early_exit))
    return _BUILT[ck]


def get_vector_scheduler_init(key: str) -> Callable[[SimParams], Any]:
    return _VECTOR_INITS.get(_norm(key), lambda params: None)


def has_vector_scheduler(key: str) -> bool:
    return _norm(key) in _VECTOR_FAMILIES


# ---------------------------------------------------------------------------
# Deprecated fleet-registry shims (one release). The single/fleet split
# collapsed into the family registry above; these keep old call sites
# working while warning.
# ---------------------------------------------------------------------------
def register_fleet_vector_scheduler(key: str):
    import warnings

    warnings.warn(
        "register_fleet_vector_scheduler is deprecated: the scheduler "
        "registries were unified — register a family with "
        "register_vector_scheduler_family(key)(make) instead",
        DeprecationWarning,
        stacklevel=2,
    )

    def deco(fn: VectorScheduler) -> VectorScheduler:
        k = _norm(key)
        # honour the old semantics: this fn is the variant the engine
        # runs, regardless of plain-registration order
        _SHIM_EARLY_EXIT[k] = fn
        _BUILT[(k, True)] = fn
        if k not in _VECTOR_FAMILIES:
            _VECTOR_FAMILIES[k] = lambda early_exit, _fn=fn: _fn
        return fn

    return deco


def get_fleet_vector_scheduler(key: str) -> VectorScheduler:
    """Deprecated alias for ``get_vector_scheduler(key, early_exit=True)``."""
    import warnings

    warnings.warn(
        "get_fleet_vector_scheduler is deprecated: use "
        "get_vector_scheduler(key, early_exit=True)",
        DeprecationWarning,
        stacklevel=2,
    )
    return get_vector_scheduler(key, early_exit=True)


# The named schedulers ARE points of the parameterised family: each
# registers through `policy_family_make` at its DEFAULT_POINTS entry
# (bitwise-identical to the legacy implementations — see the family
# block comment). The legacy implementations stay registered under
# `*_ref` keys as independent oracles for the identity test wall; the
# sjf pair registers from extra_schedulers.py.
register_vector_scheduler_family("naive", params=DEFAULT_POINTS["naive"])(
    policy_family_make
)
register_vector_scheduler_family(
    "priority", params=DEFAULT_POINTS["priority"]
)(policy_family_make)
register_vector_scheduler_family(
    "priority_pool", params=DEFAULT_POINTS["priority_pool"]
)(policy_family_make)
register_vector_scheduler_family(
    "cache_aware", params=DEFAULT_POINTS["cache_aware"]
)(policy_family_make)
register_vector_scheduler_family(
    "locality_pool", params=DEFAULT_POINTS["locality_pool"]
)(policy_family_make)
# the dynamic family: per-lane vectors from ``wl.policy`` (vmapped
# policy grids — repro.search evaluates candidate populations with it)
register_vector_scheduler_family("policy")(
    functools.partial(policy_family_make, None)
)

register_vector_scheduler("naive_ref")(naive_scheduler)
register_vector_scheduler_family("priority_ref")(
    functools.partial(_priority_like, "single")
)
register_vector_scheduler_family("priority_pool_ref")(
    functools.partial(_priority_like, "free")
)
register_vector_scheduler_family("cache_aware_ref")(
    functools.partial(_priority_like, "cache")
)
register_vector_scheduler_family("locality_pool_ref")(
    functools.partial(_priority_like, "locality")
)

# stable aliases for the no-early-exit builds (public API compat) — all
# four resolve through the registry so they ARE the `_BUILT`-cached
# instances jit sees everywhere else (a bare `_priority_like(...)` call
# here would build uncached duplicates and defeat the jit-identity
# cache).
def mask_down_pools(sim: SimState, tick: jax.Array) -> SimState:
    """Scheduler view of ``sim`` with down pools' free capacity zeroed.

    A pool is down while ``tick < pool_down_until`` (chaos layer, see
    docs/faults.md). The engine hands the scheduler this masked *view*
    — free-resource-driven schedulers then treat the pool as full and
    place elsewhere — while the committed state keeps the true free
    counts (the outage killed containers and refunded their resources;
    recovery must not re-inflate capacity). Schedulers that read pool
    *caps* rather than free counts (``naive``) are caught by the
    engine's decision filter, which drops assignments onto down pools
    before they commit.
    """
    down = tick < sim.pool_down_until
    return sim._replace(
        pool_cpu_free=jnp.where(down, 0.0, sim.pool_cpu_free),
        pool_ram_free=jnp.where(down, 0.0, sim.pool_ram_free),
    )


priority_scheduler = get_vector_scheduler("priority")
priority_pool_scheduler = get_vector_scheduler("priority_pool")
cache_aware_scheduler = get_vector_scheduler("cache_aware")
locality_pool_scheduler = get_vector_scheduler("locality_pool")


__all__ = [
    "SchedDecision",
    "decision_loop",
    "empty_decision",
    "mask_down_pools",
    "select_next_pipe",
    "select_victim",
    "naive_scheduler",
    "policy_family_make",
    "get_policy_point",
    "has_policy_point",
    "policy_points",
    "priority_scheduler",
    "priority_pool_scheduler",
    "cache_aware_scheduler",
    "locality_pool_scheduler",
    "register_vector_scheduler",
    "register_vector_scheduler_family",
    "register_vector_scheduler_init",
    "register_fleet_vector_scheduler",
    "get_vector_scheduler",
    "get_vector_scheduler_init",
    "get_fleet_vector_scheduler",
    "has_vector_scheduler",
]
