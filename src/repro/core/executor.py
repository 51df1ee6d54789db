"""Executor (paper §3.2.2): manager of the simulated physical resources.

Pure-JAX transition functions shared by the tick engine and the
event-skip engine. Order inside one tick:

    arrivals -> suspension releases -> completions/OOMs ->
    scheduler -> apply (suspend, reject, assign) -> integrate utilisation

Containers compute their completion tick and (if the RAM allocation is
insufficient) their OOM tick *at creation time*, exactly as §3.2.2
describes, via :func:`repro.core.state.container_schedule`.

Data plane (beyond the paper; cf. Bauplan, arXiv 2410.17465): at
creation the container is additionally charged

* a **cold-start latency** unless it lands on a slot kept warm by a
  container that retired on the same pool within ``container_warm_ticks``,
* a **data-scan cost** (``scan_ticks_per_gb``) for the pipeline's
  intermediate bytes not resident in the pool's zero-copy cache,

and the pipeline's intermediates are inserted into the pool's cache
(LRU by last-touch tick, capacity ``cache_gb_per_pool``). Both charges
are folded into ``ctr_end``/``ctr_oom`` at creation, so the event-skip
engine's ``_next_event`` accounts for cold-start release ticks for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .exact import exp2_int
from .params import SimParams
from .scheduler import SchedDecision, onehot_take
from .state import (
    INF_TICK,
    SimState,
    Workload,
    cache_insert,
    container_schedule,
    used_resources,
)
from .types import ContainerStatus, PipeStatus, TICKS_PER_SECOND
from repro.kernels.state_update import assign_gather, retire_land


def _warm_until(tick: jax.Array, params: SimParams) -> jax.Array:
    """Warmth expiry tick, saturated at INF_TICK so huge warm windows
    ("keep slots warm forever") cannot overflow int32. Int32-safe even
    with x64 disabled: the window is clamped to the headroom INF_TICK -
    tick before the add. The python engine keeps these in int64 and
    clamps at export, so the saturation preserves engine equivalence."""
    window = jnp.int32(min(int(params.container_warm_ticks), int(INF_TICK)))
    return tick + jnp.minimum(window, INF_TICK - tick)


def process_arrivals(state: SimState, wl: Workload, tick: jax.Array) -> SimState:
    """PENDING/EMPTY slots whose arrival tick has come join the queue."""
    fresh = (state.pipe_status == int(PipeStatus.EMPTY)) & (wl.arrival <= tick)
    return state._replace(
        pipe_status=jnp.where(
            fresh, int(PipeStatus.WAITING), state.pipe_status
        ),
        pipe_entered=jnp.where(fresh, wl.arrival, state.pipe_entered),
    )


def process_releases(state: SimState, tick: jax.Array) -> SimState:
    """Suspended pipelines re-enter the waiting queue after their 1-tick
    stay in the suspending queue (paper §4.1.3 (1))."""
    suspended = state.pipe_status == int(PipeStatus.SUSPENDED)
    rel = suspended & (state.pipe_release <= tick)
    # next-event register: min release over the pipes still suspended
    still = suspended & ~rel
    nxt_release = jnp.min(jnp.where(still, state.pipe_release, INF_TICK))
    return state._replace(
        pipe_status=jnp.where(rel, int(PipeStatus.WAITING), state.pipe_status),
        pipe_entered=jnp.where(rel, state.pipe_release, state.pipe_entered),
        pipe_release=jnp.where(rel, INF_TICK, state.pipe_release),
        nxt_release=nxt_release,
    )


def _requeue_faulted(
    state: SimState,
    tick: jax.Array,
    params: SimParams,
    fault_hit: jax.Array,  # [MP] bool — pipelines whose container was killed
) -> SimState:
    """Re-queue fault-killed / timed-out pipelines under the retry policy.

    A struck pipeline with retry budget left re-enters the queue at
    ``tick + base_backoff_ticks * 2**attempt`` (through the existing
    SUSPENDED/release machinery, so the event registers need no new
    source); once ``max_retries`` attempts are spent it transitions to
    FAILED. Unlike an OOM, a fault kill does not set ``pipe_fail_flag``
    (the allocation was fine — the worker died), so the scheduler's
    doubling/reject rules are untouched. The backoff arithmetic is f32
    (exact for power-of-two scaling), mirrored op-for-op by
    ``engine_python._requeue_faulted_py``.
    """
    i32 = jnp.int32
    attempt = state.pipe_retries
    exhausted = fault_hit & (attempt >= params.max_retries)
    retry = fault_hit & ~exhausted
    backoff = jnp.minimum(
        jnp.float32(params.base_backoff_ticks)
        * exp2_int(jnp.minimum(attempt, 30)),
        jnp.float32(2**30),
    ).astype(i32)
    release = tick + jnp.maximum(backoff, 1)
    nxt_release = jnp.minimum(
        state.nxt_release,
        jnp.min(jnp.where(retry, release, INF_TICK)),
    )
    return state._replace(
        pipe_status=jnp.where(
            exhausted,
            int(PipeStatus.FAILED),
            jnp.where(retry, int(PipeStatus.SUSPENDED), state.pipe_status),
        ),
        pipe_completion=jnp.where(exhausted, tick, state.pipe_completion),
        pipe_release=jnp.where(retry, release, state.pipe_release),
        pipe_retries=state.pipe_retries + retry.astype(i32),
        failed_count=state.failed_count + jnp.sum(exhausted).astype(i32),
        retry_events=state.retry_events + jnp.sum(retry).astype(i32),
        nxt_release=nxt_release,
    )


def apply_faults(
    state: SimState, wl: Workload, tick: jax.Array, params: SimParams
):
    """Apply the crash/outage events due at ``tick`` (chaos layer).

    Runs between phase 1 and the scheduler when
    ``params.fault_events_active`` — the faults-off engine never calls
    it. Consumes the pre-materialised fault trace through the
    ``crash_cursor``/``outage_cursor`` registers:

    * each due **crash** kills the longest-running container (start tick
      asc, slot asc) — a crash with nothing running strikes an idle
      worker and kills nothing;
    * each due **outage** marks its pool down until ``outage_end``
      (scheduler capacity is masked while ``tick < pool_down_until``),
      kills every container on it, flushes the pool's LRU cache and its
      warm slots;

    killed pipelines re-queue via :func:`_requeue_faulted`, and the
    ``nxt_fault`` register is recomputed so the event-skip loop wakes at
    the next crash, outage start, or pool recovery.

    Returns ``(state, fault_aux)``; ``fault_aux = (kill, kill_pipe,
    kill_pool, kill_cause, kill_wasted, down_new, up_now,
    pool_down_until)`` feeds the telemetry recorder (reads only).
    """
    ft = wl.faults
    i32 = jnp.int32
    MC = state.ctr_status.shape[0]
    NP = state.pool_cpu_cap.shape[0]
    MP = state.pipe_status.shape[0]
    MF = ft.crash_time.shape[0]
    fidx = jnp.arange(MF, dtype=i32)
    slots = jnp.arange(MC, dtype=i32)
    running = state.ctr_status == int(ContainerStatus.RUNNING)

    # pools recovering exactly now (telemetry marker; the capacity unmask
    # is implicit — a pool is down iff tick < pool_down_until)
    up_now = (state.pool_down_until > 0) & (state.pool_down_until == tick)

    # ---- transient crashes -------------------------------------------------
    if params.crash_mtbf_ticks > 0:
        new_ccur = jnp.searchsorted(
            ft.crash_time, tick, side="right"
        ).astype(i32)
        k_due = new_ccur - state.crash_cursor
        # rank running containers by (start asc, slot asc); the k_due
        # longest-running are struck
        earlier = (state.ctr_start[None, :] < state.ctr_start[:, None]) | (
            (state.ctr_start[None, :] == state.ctr_start[:, None])
            & (slots[None, :] < slots[:, None])
        )
        rank = jnp.sum(running[None, :] & earlier, axis=1).astype(i32)
        crash_kill = running & (rank < k_due)
    else:
        new_ccur = state.crash_cursor
        k_due = jnp.int32(0)
        crash_kill = jnp.zeros((MC,), bool)

    # ---- pool outages ------------------------------------------------------
    pool_down_until = state.pool_down_until
    if params.outage_mtbf_ticks > 0:
        new_ocur = jnp.searchsorted(
            ft.outage_start, tick, side="right"
        ).astype(i32)
        due = (fidx >= state.outage_cursor) & (fidx < new_ocur)
        n_due = new_ocur - state.outage_cursor
        pool_t = jnp.where(due, ft.outage_pool, NP)  # NP = not due, no hit
        # one-hot forms, not ``.at[pool_t].add/max`` scatters: a vmapped
        # dynamic-index scatter serializes into a while thunk per
        # scatter on XLA:CPU (see docs/architecture.md §"Kernel
        # subsystems"). Bitwise identical — ``> 0`` == any for the hit
        # mask, and the int scatter-max is a reassociation-exact
        # max-fold.
        pool_oh_f = pool_t[:, None] == jnp.arange(NP, dtype=i32)[None, :]
        down_new = jnp.any(pool_oh_f, axis=0)
        pool_down_until = jnp.maximum(
            pool_down_until,
            jnp.max(
                jnp.where(
                    pool_oh_f, jnp.where(due, ft.outage_end, 0)[:, None], 0
                ),
                axis=0,
                initial=0,
            ),
        )
        out_kill = running & ~crash_kill & down_new[state.ctr_pool]
    else:
        new_ocur = state.outage_cursor
        n_due = jnp.int32(0)
        down_new = jnp.zeros((NP,), bool)
        out_kill = jnp.zeros((MC,), bool)

    kill = crash_kill | out_kill
    kill_pipe = jnp.where(kill, state.ctr_pipe, -1)
    kill_pool = jnp.where(kill, state.ctr_pool, -1)
    kill_cause = jnp.where(crash_kill, 0, 1).astype(i32)
    kill_wasted = jnp.where(kill, tick - state.ctr_start, 0).astype(i32)

    # ---- free struck resources, clear struck containers --------------------
    pool_oh = (
        state.ctr_pool[None, :] == jnp.arange(NP, dtype=i32)[:, None]
    ) & kill[None, :]
    freed_cpu = jnp.sum(
        jnp.where(pool_oh, state.ctr_cpus[None, :], 0.0), axis=1
    )
    freed_ram = jnp.sum(
        jnp.where(pool_oh, state.ctr_ram[None, :], 0.0), axis=1
    )
    still = running & ~kill
    nxt_retire = jnp.min(
        jnp.where(still, jnp.minimum(state.ctr_end, state.ctr_oom), INF_TICK)
    )
    pid = jnp.where(kill, state.ctr_pipe, MP)  # MP = not killed, no hit
    # one-hot membership, not a ``.at[pid].add`` scatter (see the outage
    # landing above for why); ``> 0`` == any, bitwise
    fault_hit = jnp.any(
        pid[:, None] == jnp.arange(MP, dtype=i32)[None, :], axis=0
    )

    # a struck slot is cold (no warm hand-off), and every slot kept warm
    # for a newly-down pool loses its warmth with the pool
    slot_warm_pool = jnp.where(kill, -1, state.slot_warm_pool)
    slot_warm_until = jnp.where(kill, 0, state.slot_warm_until)
    if params.outage_mtbf_ticks > 0:
        warm_down = (slot_warm_pool >= 0) & down_new[
            jnp.clip(slot_warm_pool, 0, NP - 1)
        ]
        slot_warm_pool = jnp.where(warm_down, -1, slot_warm_pool)
        slot_warm_until = jnp.where(warm_down, 0, slot_warm_until)

    # ---- next-fault register (next crash / outage start / recovery) --------
    nxt_fault = jnp.asarray(INF_TICK, i32)
    if params.crash_mtbf_ticks > 0:
        nxt_fault = jnp.minimum(
            nxt_fault,
            jnp.min(jnp.where(fidx >= new_ccur, ft.crash_time, INF_TICK)),
        )
    if params.outage_mtbf_ticks > 0:
        nxt_fault = jnp.minimum(
            nxt_fault,
            jnp.min(jnp.where(fidx >= new_ocur, ft.outage_start, INF_TICK)),
        )
        nxt_fault = jnp.minimum(
            nxt_fault,
            jnp.min(
                jnp.where(pool_down_until > tick, pool_down_until, INF_TICK)
            ),
        )

    state = state._replace(
        ctr_status=jnp.where(
            kill, int(ContainerStatus.EMPTY), state.ctr_status
        ),
        ctr_pipe=jnp.where(kill, -1, state.ctr_pipe),
        ctr_end=jnp.where(kill, INF_TICK, state.ctr_end),
        ctr_oom=jnp.where(kill, INF_TICK, state.ctr_oom),
        ctr_start=jnp.where(kill, INF_TICK, state.ctr_start),
        ctr_prio=jnp.where(kill, -1, state.ctr_prio),
        ctr_warm=jnp.where(kill, False, state.ctr_warm),
        ctr_timed=jnp.where(kill, False, state.ctr_timed),
        slot_warm_pool=slot_warm_pool,
        slot_warm_until=slot_warm_until,
        pool_cpu_free=state.pool_cpu_free + freed_cpu,
        pool_ram_free=state.pool_ram_free + freed_ram,
        nxt_retire=nxt_retire,
        pool_down_until=pool_down_until,
        crash_cursor=new_ccur,
        outage_cursor=new_ocur,
        nxt_fault=nxt_fault,
        crash_events=state.crash_events + k_due,
        outage_events=state.outage_events + n_due,
        fault_kills=state.fault_kills + jnp.sum(kill).astype(i32),
        wasted_ticks=state.wasted_ticks + jnp.sum(kill_wasted),
    )
    if params.outage_mtbf_ticks > 0 and params.cache_gb_per_pool > 0:
        # outage flushes the pool's zero-copy cache: recovery is cold
        state = state._replace(
            cache_bytes=jnp.where(down_new[:, None], 0.0, state.cache_bytes),
            cache_last=jnp.where(down_new[:, None], 0, state.cache_last),
            pool_cache_used=jnp.where(down_new, 0.0, state.pool_cache_used),
        )
    if params.closed_loop_active:
        # overload bookkeeping (docs/closed-loop.md): remember the last
        # crash/outage tick and the backlog at the FIRST fault; a new
        # fault re-arms drain detection (apply_closed_loop re-stamps
        # drain_tick once the backlog recovers). Kills and re-queues
        # never touch WAITING pipelines, so the backlog count is the
        # same anywhere in this pass.
        fault_now = (k_due > 0) | (n_due > 0)
        backlog_now = jnp.sum(
            state.pipe_status == int(PipeStatus.WAITING)
        ).astype(i32)
        state = state._replace(
            last_fault_tick=jnp.where(
                fault_now, tick, state.last_fault_tick
            ),
            prefault_backlog=jnp.where(
                fault_now & (state.prefault_backlog < 0),
                backlog_now,
                state.prefault_backlog,
            ),
            drain_tick=jnp.where(
                fault_now, INF_TICK, state.drain_tick
            ),
        )
    state = _requeue_faulted(state, tick, params, fault_hit)
    fault_aux = (
        kill, kill_pipe, kill_pool, kill_cause, kill_wasted,
        down_new, up_now, pool_down_until,
    )
    return state, fault_aux


def _apply_retirements(
    state: SimState,
    wl: Workload,
    tick: jax.Array,
    params: SimParams,
    oomed: jax.Array,
    done: jax.Array,
    freed_cpu: jax.Array,
    freed_ram: jax.Array,
    nxt_retire: jax.Array,
) -> SimState:
    """Apply precomputed retire masks + freed-resource sums to the state.

    Shared by :func:`process_completions` (which derives the masks
    itself) and :func:`apply_fused_phase1` (which gets them from the
    fused ``sim_tick`` pass) — one body, so the bitwise fused-vs-
    sequential invariant cannot drift when completion effects change.
    """
    retired = oomed | done

    # ---- timeout split (chaos layer, compiled out when the knob is 0) ------
    # a container whose ``ctr_end`` is the timeout deadline (not a real
    # completion) retires like a completion — the slot frees and stays
    # warm — but its pipeline re-queues under the retry policy instead
    # of completing.
    if params.timeout_ticks > 0:
        timed = done & state.ctr_timed
        done_eff = done & ~timed
    else:
        timed = jnp.zeros_like(done)
        done_eff = done

    # ---- per-pipeline effects (scatter via segment-sum over containers) ----
    MP = state.pipe_status.shape[0]
    pid = jnp.where(retired, state.ctr_pipe, MP)  # out-of-range = dropped
    oom_hit = (
        jnp.zeros((MP,), jnp.int32)
        .at[pid]
        .add(oomed.astype(jnp.int32), mode="drop")
    ) > 0
    done_hit = (
        jnp.zeros((MP,), jnp.int32)
        .at[pid]
        .add(done_eff.astype(jnp.int32), mode="drop")
    ) > 0
    end_of = (
        jnp.full((MP,), 0, jnp.int32)
        .at[pid]
        .max(jnp.where(done_eff, state.ctr_end, 0), mode="drop")
    )
    if params.timeout_ticks > 0:
        # timed-out pipelines and wasted work, read before the container
        # table is cleared below
        timed_hit = (
            jnp.zeros((MP,), jnp.int32)
            .at[jnp.where(timed, state.ctr_pipe, MP)]
            .add(timed.astype(jnp.int32), mode="drop")
        ) > 0
        timed_wasted = jnp.sum(
            jnp.where(timed, tick - state.ctr_start, 0)
        ).astype(jnp.int32)

    lat_s = (end_of - wl.arrival).astype(jnp.float32) / TICKS_PER_SECOND
    lat_s = jnp.where(done_hit, lat_s, 0.0)
    prio_oh = (
        wl.prio[None, :] == jnp.arange(3, dtype=jnp.int32)[:, None]
    )  # [3, MP]

    state = state._replace(
        nxt_retire=nxt_retire,
        pipe_status=jnp.where(
            oom_hit,
            int(PipeStatus.WAITING),
            jnp.where(done_hit, int(PipeStatus.DONE), state.pipe_status),
        ),
        pipe_entered=jnp.where(oom_hit, tick, state.pipe_entered),
        pipe_fail_flag=state.pipe_fail_flag | oom_hit,
        pipe_fails=state.pipe_fails + oom_hit.astype(jnp.int32),
        pipe_completion=jnp.where(done_hit, end_of, state.pipe_completion),
        ctr_status=jnp.where(
            retired, int(ContainerStatus.EMPTY), state.ctr_status
        ),
        ctr_pipe=jnp.where(retired, -1, state.ctr_pipe),
        ctr_end=jnp.where(retired, INF_TICK, state.ctr_end),
        ctr_oom=jnp.where(retired, INF_TICK, state.ctr_oom),
        ctr_start=jnp.where(retired, INF_TICK, state.ctr_start),
        ctr_prio=jnp.where(retired, -1, state.ctr_prio),
        # retired containers keep their slot warm on their pool for a while
        ctr_warm=jnp.where(retired, False, state.ctr_warm),
        slot_warm_pool=jnp.where(retired, state.ctr_pool, state.slot_warm_pool),
        slot_warm_until=jnp.where(
            retired, _warm_until(tick, params), state.slot_warm_until
        ),
        pool_cpu_free=state.pool_cpu_free + freed_cpu,
        pool_ram_free=state.pool_ram_free + freed_ram,
        done_count=state.done_count + jnp.sum(done_hit).astype(jnp.int32),
        oom_events=state.oom_events + jnp.sum(oom_hit).astype(jnp.int32),
        sum_latency_s=state.sum_latency_s + jnp.sum(lat_s),
        sum_latency_s_prio=state.sum_latency_s_prio
        + jnp.sum(jnp.where(prio_oh, lat_s[None, :], 0.0), axis=1),
        done_prio=state.done_prio
        + jnp.sum(prio_oh & done_hit[None, :], axis=1).astype(jnp.int32),
    )
    if params.timeout_ticks > 0:
        state = state._replace(
            ctr_timed=jnp.where(retired, False, state.ctr_timed),
            timeout_events=state.timeout_events
            + jnp.sum(timed).astype(jnp.int32),
            wasted_ticks=state.wasted_ticks + timed_wasted,
        )
        state = _requeue_faulted(state, tick, params, timed_hit)
    return state


def process_completions(
    state: SimState, wl: Workload, tick: jax.Array, params: SimParams
) -> SimState:
    """Retire containers whose OOM or completion tick has arrived."""
    running = state.ctr_status == int(ContainerStatus.RUNNING)
    oomed = running & (state.ctr_oom <= tick)
    done = running & ~oomed & (state.ctr_end <= tick)
    retired = oomed | done

    # ---- free pool resources ------------------------------------------------
    NP = state.pool_cpu_cap.shape[0]
    pool_oh = (
        state.ctr_pool[None, :] == jnp.arange(NP, dtype=jnp.int32)[:, None]
    ) & retired[None, :]
    freed_cpu = jnp.sum(jnp.where(pool_oh, state.ctr_cpus[None, :], 0.0), axis=1)
    freed_ram = jnp.sum(jnp.where(pool_oh, state.ctr_ram[None, :], 0.0), axis=1)

    # next-event register: min(end, oom) over the containers still running
    still = running & ~retired
    nxt_retire = jnp.min(
        jnp.where(still, jnp.minimum(state.ctr_end, state.ctr_oom), INF_TICK)
    )

    return _apply_retirements(
        state, wl, tick, params, oomed, done, freed_cpu, freed_ram, nxt_retire
    )


def apply_decision(
    state: SimState,
    wl: Workload,
    dec: SchedDecision,
    tick: jax.Array,
    params: SimParams,
    early_exit: bool = False,
    with_aux: bool = False,
) -> SimState:
    """Apply one scheduler decision.

    ``early_exit=True`` replaces the fixed ``fori_loop`` over the K
    assignment slots with a ``while_loop`` that stops after the last
    populated slot — bitwise-identical (skipped slots are provable
    no-ops: ``assign_one`` ignores slots with ``assign_pipe < 0``), but
    events with empty decisions no longer pay K sequential iterations.
    The fleet engine uses it; the legacy paths keep the static loop.

    ``with_aux=True`` (early-exit path only) additionally returns the
    per-slot assignment provenance the telemetry recorder needs:
    ``aux_i [K, 4]`` int32 columns ``(pipe, pool, cold_ticks, is_warm)``
    and ``aux_f [K, 5]`` float32 columns ``(cpus, ram, hit_gb, miss_gb,
    total_out)``, with ``pipe = -1`` marking slots that assigned
    nothing. The aux values are the exact intermediates of the commit,
    read out of the same computation — collecting them does not change
    the state update.
    """
    # ---- 1. suspensions (preemptions) --------------------------------------
    susp = dec.suspend & (state.ctr_status == int(ContainerStatus.RUNNING))
    NP = params.num_pools
    pool_oh = (
        state.ctr_pool[None, :] == jnp.arange(NP, dtype=jnp.int32)[:, None]
    ) & susp[None, :]
    freed_cpu = jnp.sum(jnp.where(pool_oh, state.ctr_cpus[None, :], 0.0), axis=1)
    freed_ram = jnp.sum(jnp.where(pool_oh, state.ctr_ram[None, :], 0.0), axis=1)
    MP = params.max_pipelines
    pid = jnp.where(susp, state.ctr_pipe, MP)  # MP = not suspended, no hit
    # one-hot membership, not a ``.at[pid].add`` scatter: vmapped
    # dynamic scatters serialize on XLA:CPU (``> 0`` == any, bitwise)
    susp_hit = jnp.any(
        pid[:, None] == jnp.arange(MP, dtype=jnp.int32)[None, :], axis=0
    )

    # next-event registers: preempted containers leave the running set
    # (recompute the retire min over the survivors); every new suspension
    # releases at tick + 1, so the release min is a running minimum.
    any_susp = jnp.any(susp)
    still = (state.ctr_status == int(ContainerStatus.RUNNING)) & ~susp
    nxt_retire = jnp.min(
        jnp.where(still, jnp.minimum(state.ctr_end, state.ctr_oom), INF_TICK)
    )
    nxt_release = jnp.where(
        any_susp, jnp.minimum(state.nxt_release, tick + 1), state.nxt_release
    )

    state = state._replace(
        nxt_retire=nxt_retire,
        nxt_release=nxt_release,
        pipe_status=jnp.where(
            susp_hit, int(PipeStatus.SUSPENDED), state.pipe_status
        ),
        pipe_release=jnp.where(susp_hit, tick + 1, state.pipe_release),
        pipe_preempts=state.pipe_preempts + susp_hit.astype(jnp.int32),
        ctr_status=jnp.where(susp, int(ContainerStatus.EMPTY), state.ctr_status),
        ctr_pipe=jnp.where(susp, -1, state.ctr_pipe),
        ctr_end=jnp.where(susp, INF_TICK, state.ctr_end),
        ctr_oom=jnp.where(susp, INF_TICK, state.ctr_oom),
        ctr_start=jnp.where(susp, INF_TICK, state.ctr_start),
        ctr_prio=jnp.where(susp, -1, state.ctr_prio),
        ctr_warm=jnp.where(susp, False, state.ctr_warm),
        slot_warm_pool=jnp.where(susp, state.ctr_pool, state.slot_warm_pool),
        slot_warm_until=jnp.where(
            susp, _warm_until(tick, params), state.slot_warm_until
        ),
        pool_cpu_free=state.pool_cpu_free + freed_cpu,
        pool_ram_free=state.pool_ram_free + freed_ram,
        preempt_events=state.preempt_events + jnp.sum(susp).astype(jnp.int32),
    )
    if params.timeout_ticks > 0:
        state = state._replace(
            ctr_timed=jnp.where(susp, False, state.ctr_timed)
        )

    # ---- 2. rejections (failures returned to the user) ---------------------
    rej = dec.reject & (state.pipe_status == int(PipeStatus.WAITING))
    state = state._replace(
        pipe_status=jnp.where(rej, int(PipeStatus.FAILED), state.pipe_status),
        pipe_completion=jnp.where(rej, tick, state.pipe_completion),
        failed_count=state.failed_count + jnp.sum(rej).astype(jnp.int32),
    )

    # ---- 3. assignments ------------------------------------------------------
    def assign_one(k, st: SimState, collect_aux: bool = False):
        pipe = dec.assign_pipe[k]
        valid = pipe >= 0
        pipe_c = jnp.maximum(pipe, 0)
        # only assign pipelines still waiting (belt & braces vs. stale dec)
        valid = valid & (st.pipe_status[pipe_c] == int(PipeStatus.WAITING))
        empty = st.ctr_status == int(ContainerStatus.EMPTY)
        has_slot = jnp.any(empty)
        pool = dec.assign_pool[k]
        if params.cold_start_ticks > 0:
            # prefer the lowest warm slot for the target pool (mirrors
            # engine_python._pick_slot); gated on the knob so the slot
            # order is bit-identical to pre-data-plane when it is off
            warm_ok = (
                empty
                & (st.slot_warm_pool == pool)
                & (tick < st.slot_warm_until)
            )
            slot = jnp.where(
                jnp.any(warm_ok), jnp.argmax(warm_ok), jnp.argmax(empty)
            ).astype(jnp.int32)
        else:
            slot = jnp.argmax(empty).astype(jnp.int32)
        valid = valid & has_slot
        is_warm = (st.slot_warm_pool[slot] == pool) & (
            tick < st.slot_warm_until[slot]
        )
        cold_ticks = jnp.where(is_warm, 0, jnp.int32(params.cold_start_ticks))
        cpus = dec.assign_cpus[k]
        ram = dec.assign_ram[k]
        # ---- data plane: scan inputs missing from the pool's cache ---------
        total_out = wl.pipe_out[pipe_c]
        cached = st.cache_bytes[pool, pipe_c]
        hit_gb = jnp.minimum(cached, total_out)
        miss_gb = jnp.maximum(total_out - cached, 0.0)
        scan_ticks = jnp.ceil(
            jnp.float32(params.scan_ticks_per_gb) * miss_gb
        ).astype(jnp.int32)
        startup = cold_ticks + scan_ticks
        dur, oom_off = container_schedule(wl, pipe_c, cpus, ram)
        if params.straggler_prob > 0:
            # straggler stretch: the sampled per-pipeline slowdown factor
            # (>= 1) scales the compute duration and the OOM offset alike
            # (both are "progress clocks"). f32 stretch mirrored by
            # engine_python; min-then-stretch == stretch-then-min since
            # ceil is monotone, so both engines may pick either order.
            f = wl.faults.straggler[pipe_c]
            stretch = lambda t: jnp.minimum(  # noqa: E731
                jnp.ceil(t.astype(jnp.float32) * f), jnp.float32(2**30)
            ).astype(jnp.int32)
            dur = stretch(dur)
            oom_off = jnp.where(oom_off == INF_TICK, INF_TICK, stretch(oom_off))
        end = tick + startup + dur
        oom = jnp.where(
            oom_off == INF_TICK,
            INF_TICK,
            tick + startup + jnp.minimum(oom_off, dur),
        )
        if params.timeout_ticks > 0:
            # wall-clock deadline: a container that would outlive it is
            # killed there instead (ctr_timed marks the retirement as a
            # TIMEOUT -> retry, not a completion). An OOM due at the same
            # tick wins (``done`` excludes ``oomed`` at retirement).
            deadline = tick + jnp.int32(params.timeout_ticks)
            timed = end > deadline
            end = jnp.minimum(end, deadline)
        else:
            timed = jnp.zeros((), bool)

        def commit(st: SimState) -> SimState:
            st = st._replace(
                nxt_retire=jnp.minimum(st.nxt_retire, jnp.minimum(end, oom)),
                pipe_status=st.pipe_status.at[pipe_c].set(int(PipeStatus.RUNNING)),
                pipe_last_cpus=st.pipe_last_cpus.at[pipe_c].set(cpus),
                pipe_last_ram=st.pipe_last_ram.at[pipe_c].set(ram),
                pipe_fail_flag=st.pipe_fail_flag.at[pipe_c].set(False),
                pipe_first_start=st.pipe_first_start.at[pipe_c].min(tick),
                ctr_status=st.ctr_status.at[slot].set(int(ContainerStatus.RUNNING)),
                ctr_pipe=st.ctr_pipe.at[slot].set(pipe_c),
                ctr_pool=st.ctr_pool.at[slot].set(pool),
                ctr_cpus=st.ctr_cpus.at[slot].set(cpus),
                ctr_ram=st.ctr_ram.at[slot].set(ram),
                ctr_start=st.ctr_start.at[slot].set(tick),
                ctr_end=st.ctr_end.at[slot].set(end),
                ctr_oom=st.ctr_oom.at[slot].set(oom),
                ctr_prio=st.ctr_prio.at[slot].set(wl.prio[pipe_c]),
                ctr_warm=st.ctr_warm.at[slot].set(is_warm),
                pool_cpu_free=st.pool_cpu_free.at[pool].add(-cpus),
                pool_ram_free=st.pool_ram_free.at[pool].add(-ram),
                cache_hit_gb=st.cache_hit_gb + hit_gb,
                bytes_moved_gb=st.bytes_moved_gb + miss_gb,
                cache_hits=st.cache_hits + (hit_gb > 0).astype(jnp.int32),
                cache_lookups=st.cache_lookups
                + (total_out > 0).astype(jnp.int32),
                cold_starts=st.cold_starts + (~is_warm).astype(jnp.int32),
                warm_starts=st.warm_starts + is_warm.astype(jnp.int32),
                cold_start_tick_total=st.cold_start_tick_total + cold_ticks,
            )
            if params.timeout_ticks > 0:
                st = st._replace(ctr_timed=st.ctr_timed.at[slot].set(timed))
            if params.cache_gb_per_pool > 0:
                # materialise the pipeline's intermediates in the pool's
                # zero-copy cache (LRU-evicting under the capacity)
                row_b, row_l, used = cache_insert(
                    st.cache_bytes[pool],
                    st.cache_last[pool],
                    st.pool_cache_used[pool],
                    pipe_c,
                    total_out,
                    tick,
                    params.cache_gb_per_pool,
                )
                st = st._replace(
                    cache_bytes=st.cache_bytes.at[pool].set(row_b),
                    cache_last=st.cache_last.at[pool].set(row_l),
                    pool_cache_used=st.pool_cache_used.at[pool].set(used),
                )
            return st

        new_st = jax.lax.cond(valid, commit, lambda s: s, st)
        if not collect_aux:
            return new_st
        aux_i = jnp.where(
            valid,
            jnp.stack([pipe_c, pool, cold_ticks, is_warm.astype(jnp.int32)]),
            jnp.array([-1, -1, 0, 0], jnp.int32),
        )
        aux_f = jnp.where(
            valid,
            jnp.stack([cpus, ram, hit_gb, miss_gb, total_out]),
            jnp.float32(0.0),
        )
        return new_st, aux_i, aux_f

    K = params.max_assignments_per_tick
    if with_aux and not early_exit:
        raise ValueError("with_aux requires early_exit=True")
    if early_exit:
        # fused landing (kernels/state_update): the early-exit loop
        # collects one row of commit values per populated slot and the
        # table writes land afterwards as one masked scatter —
        # bitwise-identical to the per-slot cond-commit loop below,
        # which stays as the property-tested oracle
        return _apply_assignments_fused(
            state, wl, dec, tick, params, with_aux=with_aux
        )
    return jax.lax.fori_loop(0, K, assign_one, state)


def _apply_assignments_fused(
    state: SimState,
    wl: Workload,
    dec: SchedDecision,
    tick: jax.Array,
    params: SimParams,
    with_aux: bool = False,
):
    """Vectorised assignment pass with a fused landing (Pallas phase 3).

    Bitwise-identical to the legacy per-slot ``lax.cond`` commit loop
    (``apply_decision(early_exit=False)``, the oracle), but the per-row
    math runs over all K slots at once instead of a while_loop carrying
    the full SimState:

    * **validity** is closed-form: a row can only commit if it is the
      first occurrence of its pipeline (any earlier same-pipe row either
      consumed the pipeline or failed for a reason that persists), the
      pipeline was waiting before the loop, and its rank among such rows
      does not exceed the number of empty slots (capacity once exhausted
      never recovers inside the loop);
    * **slot pick**: with cold starts off every valid row takes the
      lowest remaining empty slot, so the rank-r row lands on the r-th
      lowest empty slot (cumsum matching). With warm-slot preference the
      pick order is pool-dependent, so a minimal while_loop carrying
      only the empty mask computes the picks;
    * **order-sensitive f32 accumulators** (pool frees, cache sums, LRU
      inserts) keep the seed's left-fold association in a small
      sequential loop over the populated slots — everything else (int
      counters, the ``nxt_retire`` min-fold) is reassociation-exact and
      reduces vectorised.

    The container/pipeline table writes land once through
    ``kernels/state_update.assign_gather``, a one-hot reduction over
    the K rows that is exact for every value with unique indices. Here
    and there no row is read at a traced index (the K-row reads go
    through ``scheduler.onehot_take``): under the engine's ``vmap`` such
    a read is a batched gather, which a TPU v5e runs at ~8.8 ns per
    output element. ``with_aux=True`` reads the telemetry aux straight
    out of the same row vectors the landing commits.
    """
    i32, f32 = jnp.int32, jnp.float32
    MC = state.ctr_status.shape[0]
    MP = state.pipe_status.shape[0]
    K = params.max_assignments_per_tick
    cache_on = params.cache_gb_per_pool > 0
    timeout_on = params.timeout_ticks > 0

    ks = jnp.arange(K, dtype=i32)
    # loops below only walk the populated prefix; most events carry
    # zero or one assignment, so they usually run 0-1 iterations
    n_slots = jnp.max(jnp.where(dec.assign_pipe >= 0, ks + 1, 0))

    pipe = dec.assign_pipe
    pipe_c = jnp.maximum(pipe, 0)
    pool = dec.assign_pool
    cpus = dec.assign_cpus
    ram = dec.assign_ram

    waiting0 = state.pipe_status == int(PipeStatus.WAITING)
    empty0 = state.ctr_status == int(ContainerStatus.EMPTY)
    n_empty = jnp.sum(empty0).astype(i32)

    # -- closed-form validity (proof in the docstring) -----------------------
    # a row repeating an earlier row's pipeline can never commit: the
    # earlier row either took the pipeline (no longer waiting) or failed
    # because it never waited / capacity ran out — conditions that still
    # hold at the later row
    dup_before = jnp.any(
        (pipe[None, :] == pipe[:, None]) & (ks[None, :] < ks[:, None]),
        axis=1,
    )
    pre = (pipe >= 0) & onehot_take(waiting0, pipe_c) & ~dup_before
    rank = jnp.cumsum(pre.astype(i32))  # 1-based, inclusive
    valid = pre & (rank <= n_empty)

    # -- slot pick -----------------------------------------------------------
    if params.cold_start_ticks > 0:
        # warm-slot preference makes the pick order pool-dependent, so
        # walk the populated slots with the smallest possible carry
        # (just the evolving empty mask). Commits never write slot
        # warmth, so the pre-loop warmth view is the loop-invariant
        # truth (mirrors engine_python._pick_slot).
        def _pick_body(c):
            k, empty, slots = c
            warm_ok = (
                empty
                & (state.slot_warm_pool == pool[k])
                & (tick < state.slot_warm_until)
            )
            s = jnp.where(
                jnp.any(warm_ok), jnp.argmax(warm_ok), jnp.argmax(empty)
            ).astype(i32)
            # one-hot selects, not ``.at[]`` scatters (vmapped dynamic
            # scatters serialize on XLA:CPU); bitwise identical
            return (
                k + 1,
                jnp.where(
                    valid[k] & (jnp.arange(empty.shape[0]) == s),
                    False,
                    empty,
                ),
                jnp.where(jnp.arange(slots.shape[0]) == k, s, slots),
            )

        _, _, slot = jax.lax.while_loop(
            lambda c: c[0] < n_slots,
            _pick_body,
            (jnp.int32(0), empty0, jnp.zeros((K,), i32)),
        )
    else:
        # every valid row takes the lowest remaining empty slot, so the
        # rank-r row lands on the r-th lowest empty slot
        cum = jnp.cumsum(empty0.astype(i32))
        eq = empty0[None, :] & (cum[None, :] == rank[:, None])
        slot = jnp.argmax(eq, axis=1).astype(i32)

    is_warm = (onehot_take(state.slot_warm_pool, slot) == pool) & (
        tick < onehot_take(state.slot_warm_until, slot)
    )
    cold_ticks = jnp.where(is_warm, 0, jnp.int32(params.cold_start_ticks))
    total_out = onehot_take(wl.pipe_out, pipe_c)

    # -- sequential walk over the populated slots ----------------------------
    # One small loop keeps (a) the order-sensitive f32 state — pool
    # frees, cache sums, LRU inserts — in the seed's left-fold
    # association, and (b) ``container_schedule`` (a [MO, MO] level
    # reduction) priced per *populated* slot only, exactly like the
    # legacy loop. The carry is a handful of small rows, not the whole
    # SimState.
    pcf0, prf0 = state.pool_cpu_free, state.pool_ram_free
    chg0, bmg0 = state.cache_hit_gb, state.bytes_moved_gb
    durs0 = jnp.zeros((K,), i32)
    ooms0 = jnp.zeros((K,), i32)
    if cache_on:
        # the cache gather must see earlier rows' LRU inserts, so the
        # data plane rides in the same loop
        def _slot_body(c):
            k, cb, cl, pcu, pcf, prf, chg, bmg, hits, misses, durs, ooms = c
            v, p, pc = valid[k], pool[k], pipe_c[k]
            to = total_out[k]
            cached = cb[p, pc]
            hg = jnp.minimum(cached, to)
            mg = jnp.maximum(to - cached, 0.0)
            row_b, row_l, used = cache_insert(
                cb[p], cl[p], pcu[p], pc, to, tick,
                params.cache_gb_per_pool,
            )
            d, o = container_schedule(wl, pc, cpus[k], ram[k])
            # one-hot selects, not ``.at[]`` scatters: a vmapped scatter
            # lowers to a serialized while loop on XLA:CPU; these stay
            # elementwise (and a select is trivially bitwise-exact)
            onp = jnp.arange(pcf.shape[0]) == p
            onk = ks == k
            return (
                k + 1,
                jnp.where(v & onp[:, None], row_b[None, :], cb),
                jnp.where(v & onp[:, None], row_l[None, :], cl),
                jnp.where(v & onp, used, pcu),
                jnp.where(v & onp, pcf - cpus[k], pcf),
                jnp.where(v & onp, prf - ram[k], prf),
                jnp.where(v, chg + hg, chg),
                jnp.where(v, bmg + mg, bmg),
                jnp.where(onk, hg, hits),
                jnp.where(onk, mg, misses),
                jnp.where(onk, d, durs),
                jnp.where(onk, o, ooms),
            )

        (_, cache_bytes, cache_last, pool_cache_used, pool_cpu_free,
         pool_ram_free, cache_hit_gb, bytes_moved_gb, hit_gb, miss_gb,
         dur, oom_off) = jax.lax.while_loop(
            lambda c: c[0] < n_slots,
            _slot_body,
            (jnp.int32(0), state.cache_bytes, state.cache_last,
             state.pool_cache_used, pcf0, prf0, chg0, bmg0,
             jnp.zeros((K,), f32), jnp.zeros((K,), f32), durs0, ooms0),
        )
    else:
        cached = onehot_take(state.cache_bytes.reshape(-1), pool * MP + pipe_c)
        hit_gb = jnp.minimum(cached, total_out)
        miss_gb = jnp.maximum(total_out - cached, 0.0)

        def _slot_body(c):
            k, pcf, prf, chg, bmg, durs, ooms = c
            v, p = valid[k], pool[k]
            d, o = container_schedule(wl, pipe_c[k], cpus[k], ram[k])
            # one-hot selects, not ``.at[]`` scatters: a vmapped scatter
            # lowers to a serialized while loop on XLA:CPU; these stay
            # elementwise (and a select is trivially bitwise-exact)
            onp = jnp.arange(pcf.shape[0]) == p
            onk = ks == k
            return (
                k + 1,
                jnp.where(v & onp, pcf - cpus[k], pcf),
                jnp.where(v & onp, prf - ram[k], prf),
                jnp.where(v, chg + hit_gb[k], chg),
                jnp.where(v, bmg + miss_gb[k], bmg),
                jnp.where(onk, d, durs),
                jnp.where(onk, o, ooms),
            )

        (_, pool_cpu_free, pool_ram_free, cache_hit_gb, bytes_moved_gb,
         dur, oom_off) = jax.lax.while_loop(
            lambda c: c[0] < n_slots,
            _slot_body,
            (jnp.int32(0), pcf0, prf0, chg0, bmg0, durs0, ooms0),
        )

    # -- row timing, vectorised ----------------------------------------------
    scan_ticks = jnp.ceil(
        jnp.float32(params.scan_ticks_per_gb) * miss_gb
    ).astype(i32)
    startup = cold_ticks + scan_ticks
    if params.straggler_prob > 0:
        fct = wl.faults.straggler[pipe_c]
        stretch = lambda t: jnp.minimum(  # noqa: E731
            jnp.ceil(t.astype(f32) * fct), jnp.float32(2**30)
        ).astype(i32)
        dur = stretch(dur)
        oom_off = jnp.where(oom_off == INF_TICK, INF_TICK, stretch(oom_off))
    end = tick + startup + dur
    oom = jnp.where(
        oom_off == INF_TICK,
        INF_TICK,
        tick + startup + jnp.minimum(oom_off, dur),
    )
    if timeout_on:
        deadline = tick + jnp.int32(params.timeout_ticks)
        timed = end > deadline
        end = jnp.minimum(end, deadline)
    else:
        timed = jnp.zeros((K,), bool)

    # reassociation-exact reductions (int sums / min-folds)
    nxt_retire = jnp.minimum(
        state.nxt_retire,
        jnp.min(jnp.where(valid, jnp.minimum(end, oom), INF_TICK)),
    )
    n_hit = jnp.sum(valid & (hit_gb > 0)).astype(i32)
    n_look = jnp.sum(valid & (total_out > 0)).astype(i32)
    n_warm = jnp.sum(valid & is_warm).astype(i32)
    n_cold = jnp.sum(valid & ~is_warm).astype(i32)
    cold_total = jnp.sum(jnp.where(valid, cold_ticks, 0)).astype(i32)

    # -- fused landing (kernels/state_update) --------------------------------
    prio = onehot_take(wl.prio, pipe_c)
    (hit_c, l_pipe, l_pool, l_cpus, l_ram, l_end, l_oom, l_prio, l_warm,
     l_timed, hit_p, l_pcpus, l_pram) = assign_gather(
        valid, slot, pipe_c, pool, cpus, ram, end, oom, prio, is_warm,
        timed, max_containers=MC, max_pipelines=MP,
    )
    state = state._replace(
        nxt_retire=nxt_retire,
        pipe_status=jnp.where(
            hit_p, int(PipeStatus.RUNNING), state.pipe_status
        ),
        pipe_last_cpus=jnp.where(hit_p, l_pcpus, state.pipe_last_cpus),
        pipe_last_ram=jnp.where(hit_p, l_pram, state.pipe_last_ram),
        pipe_fail_flag=jnp.where(hit_p, False, state.pipe_fail_flag),
        pipe_first_start=jnp.where(
            hit_p, jnp.minimum(state.pipe_first_start, tick),
            state.pipe_first_start,
        ),
        ctr_status=jnp.where(
            hit_c, int(ContainerStatus.RUNNING), state.ctr_status
        ),
        ctr_pipe=jnp.where(hit_c, l_pipe, state.ctr_pipe),
        ctr_pool=jnp.where(hit_c, l_pool, state.ctr_pool),
        ctr_cpus=jnp.where(hit_c, l_cpus, state.ctr_cpus),
        ctr_ram=jnp.where(hit_c, l_ram, state.ctr_ram),
        ctr_start=jnp.where(hit_c, tick, state.ctr_start),
        ctr_end=jnp.where(hit_c, l_end, state.ctr_end),
        ctr_oom=jnp.where(hit_c, l_oom, state.ctr_oom),
        ctr_prio=jnp.where(hit_c, l_prio, state.ctr_prio),
        ctr_warm=jnp.where(hit_c, l_warm, state.ctr_warm),
        pool_cpu_free=pool_cpu_free,
        pool_ram_free=pool_ram_free,
        cache_hit_gb=cache_hit_gb,
        bytes_moved_gb=bytes_moved_gb,
        cache_hits=state.cache_hits + n_hit,
        cache_lookups=state.cache_lookups + n_look,
        cold_starts=state.cold_starts + n_cold,
        warm_starts=state.warm_starts + n_warm,
        cold_start_tick_total=state.cold_start_tick_total + cold_total,
    )
    if timeout_on:
        state = state._replace(
            ctr_timed=jnp.where(hit_c, l_timed, state.ctr_timed)
        )
    if cache_on:
        state = state._replace(
            cache_bytes=cache_bytes,
            cache_last=cache_last,
            pool_cache_used=pool_cache_used,
        )
    if not with_aux:
        return state
    aux_i = jnp.where(
        valid[:, None],
        jnp.stack(
            [pipe_c, pool, cold_ticks, is_warm.astype(i32)], axis=1
        ),
        jnp.array([-1, -1, 0, 0], i32),
    )
    aux_f = jnp.where(
        valid[:, None],
        jnp.stack([cpus, ram, hit_gb, miss_gb, total_out], axis=1),
        jnp.float32(0.0),
    )
    return state, (aux_i, aux_f)


# ---------------------------------------------------------------------------
# Fused phase 1 (fleet-native event engine): apply the masks produced by
# ``repro.kernels.sim_tick.fleet_tick`` — arrivals + suspension releases +
# completions/OOMs in one pass. Bitwise-identical to the sequential
# ``process_arrivals -> process_releases -> process_completions``
# composition: the three phases read disjoint status partitions (EMPTY /
# SUSPENDED / RUNNING-container), so masks computed from the pre-state
# and applied together commute with the sequential wheres; each field is
# written once with its wheres chained in the sequential order
# (arrivals, then releases, then retirements). The retirement scatters
# (``.at[pid].add/max`` in ``_apply_retirements``, kept as the oracle)
# are replaced by the fused ``kernels/state_update.retire_land`` pass.
# ---------------------------------------------------------------------------
def apply_fused_phase1(
    state: SimState, wl: Workload, tick: jax.Array, params: SimParams, ph
) -> SimState:
    (oomed, done, _new_ctr_status, freed_cpu, freed_ram,
     fresh, rel, nxt_retire, nxt_release) = ph
    i32 = jnp.int32
    retired = oomed | done
    timeout_on = params.timeout_ticks > 0

    (oom_hit, done_hit, timed_hit, end_of, timed_wasted,
     lat_sum, lat_prio, dprio, n_done, n_oom) = retire_land(
        state.ctr_pipe, state.ctr_end, state.ctr_start, oomed, done,
        state.ctr_timed if timeout_on else None,
        wl.arrival, wl.prio, tick, timeout_on=timeout_on,
    )

    # ---- one write per field: arrivals -> releases -> retirements ----------
    W = int(PipeStatus.WAITING)
    pipe_status = jnp.where(fresh, W, state.pipe_status)
    pipe_entered = jnp.where(fresh, wl.arrival, state.pipe_entered)
    pipe_status = jnp.where(rel, W, pipe_status)
    pipe_entered = jnp.where(rel, state.pipe_release, pipe_entered)
    pipe_release = jnp.where(rel, INF_TICK, state.pipe_release)
    pipe_status = jnp.where(
        oom_hit, W, jnp.where(done_hit, int(PipeStatus.DONE), pipe_status)
    )
    pipe_entered = jnp.where(oom_hit, tick, pipe_entered)

    state = state._replace(
        nxt_retire=nxt_retire,
        nxt_release=nxt_release,
        pipe_status=pipe_status,
        pipe_entered=pipe_entered,
        pipe_release=pipe_release,
        pipe_fail_flag=state.pipe_fail_flag | oom_hit,
        pipe_fails=state.pipe_fails + oom_hit.astype(i32),
        pipe_completion=jnp.where(done_hit, end_of, state.pipe_completion),
        ctr_status=jnp.where(
            retired, int(ContainerStatus.EMPTY), state.ctr_status
        ),
        ctr_pipe=jnp.where(retired, -1, state.ctr_pipe),
        ctr_end=jnp.where(retired, INF_TICK, state.ctr_end),
        ctr_oom=jnp.where(retired, INF_TICK, state.ctr_oom),
        ctr_start=jnp.where(retired, INF_TICK, state.ctr_start),
        ctr_prio=jnp.where(retired, -1, state.ctr_prio),
        # retired containers keep their slot warm on their pool for a while
        ctr_warm=jnp.where(retired, False, state.ctr_warm),
        slot_warm_pool=jnp.where(retired, state.ctr_pool, state.slot_warm_pool),
        slot_warm_until=jnp.where(
            retired, _warm_until(tick, params), state.slot_warm_until
        ),
        pool_cpu_free=state.pool_cpu_free + freed_cpu,
        pool_ram_free=state.pool_ram_free + freed_ram,
        done_count=state.done_count + n_done,
        oom_events=state.oom_events + n_oom,
        sum_latency_s=state.sum_latency_s + lat_sum,
        sum_latency_s_prio=state.sum_latency_s_prio + lat_prio,
        done_prio=state.done_prio + dprio,
    )
    if timeout_on:
        state = state._replace(
            ctr_timed=jnp.where(retired, False, state.ctr_timed),
            timeout_events=state.timeout_events
            + jnp.sum(done & state.ctr_timed).astype(i32),
            wasted_ticks=state.wasted_ticks + timed_wasted,
        )
        state = _requeue_faulted(state, tick, params, timed_hit)
    return state


# ---------------------------------------------------------------------------
# Utilisation / cost integration over [t0, t1).
# ---------------------------------------------------------------------------
def integrate(
    state: SimState,
    t0: jax.Array,
    t1: jax.Array,
    params: SimParams,
    exact_buckets: bool,
) -> SimState:
    dt_s = (t1 - t0).astype(jnp.float32) / TICKS_PER_SECOND
    used_cpu, used_ram = used_resources(state)

    # cost model: base-rate for capacity within the un-scaled pool, premium
    # rate for cloud-scaled overflow (paper §3.2.2 "additional monetary cost")
    base_cpu = jnp.full_like(used_cpu, params.pool_cpus)
    over = jnp.maximum(used_cpu - base_cpu, 0.0)
    base_used = jnp.minimum(used_cpu, base_cpu)
    rate = params.cloud_cost_per_cpu_second
    cost = jnp.sum(base_used + params.cloud_premium_factor * over) * rate * dt_s

    B = params.util_log_buckets
    horizon = max(params.horizon_ticks, 1)
    if exact_buckets:
        # exact overlap of [t0, t1) with every bucket (event engine)
        edges = jnp.linspace(0.0, float(horizon), B + 1)
        lo = jnp.maximum(edges[:-1], t0.astype(jnp.float32))
        hi = jnp.minimum(edges[1:], t1.astype(jnp.float32))
        overlap_s = jnp.maximum(hi - lo, 0.0) / TICKS_PER_SECOND  # [B]
        add = overlap_s[:, None, None] * jnp.stack(
            [used_cpu, used_ram], axis=-1
        )[None, :, :]
        util_log = state.util_log + add
    else:
        # tick engine: the whole tick lands in one bucket (scatter-add)
        b = jnp.clip(t0 * B // horizon, 0, B - 1)
        util_log = state.util_log.at[b].add(
            dt_s * jnp.stack([used_cpu, used_ram], axis=-1)
        )

    state = state._replace(
        util_cpu_s=state.util_cpu_s + used_cpu * dt_s,
        util_ram_s=state.util_ram_s + used_ram * dt_s,
        cost_dollars=state.cost_dollars + cost,
        util_log=util_log,
    )
    if params.outage_mtbf_ticks > 0:
        # downtime integral (MTTR numerator). Exact: the event engine's
        # ``nxt_fault`` register includes every recovery tick, so an
        # integration interval never straddles a pool coming back up —
        # a pool down at t0 is down for the whole of [t0, t1).
        n_down = jnp.sum((t0 < state.pool_down_until).astype(jnp.float32))
        state = state._replace(pool_down_s=state.pool_down_s + dt_s * n_down)
    return state


__all__ = [
    "process_arrivals",
    "process_releases",
    "process_completions",
    "apply_faults",
    "apply_decision",
    "apply_fused_phase1",
    "integrate",
]
