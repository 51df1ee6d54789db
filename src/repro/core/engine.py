"""The lane-major simulation core.

One compiled engine advances every simulation. State is batched
*lane-major* — every array carries a leading fleet axis ``[F, ...]`` —
and a single shared ``lax.while_loop`` steps all lanes at once:

* phase 1 (completions + releases + arrival admission + per-pool freed
  resources + next-event registers) is one fused [F, MC]/[F, MP] pass
  through ``repro.kernels.sim_tick.fleet_tick`` (Pallas on TPU, the
  bitwise-equivalent jnp reference elsewhere);
* the scheduler and ``apply_decision`` run with early-exit inner loops
  (``decision_loop(early_exit=True)``), whose while_loops vmap into
  max-over-lanes trip counts — an event with an empty queue no longer
  pays K sequential steps;
* each lane skips to its own next event via the incremental
  ``nxt_retire``/``nxt_release`` registers plus an O(log MP) binary
  search of the sorted arrivals (``_next_event`` stays as the
  recompute-from-scratch oracle, property-tested in tests/test_fleet.py);
* finished lanes pass through untouched (``jnp.where`` on the carry)
  and the loop exits when every lane is done.

``run()`` is the F=1 special case (squeezed on return); ``fleet_run``
(``sweep.py``) is the N-lane case, optionally sharded across local
devices with ``shard_map``. Both engines the paper's design implied —
the per-tick ``lax.scan`` loop and a per-simulation event loop — were
deleted in the lane-major unification; the Python reference engine
(``engine="python"``) remains as the readable executable specification,
and the property suite checks the compiled core against it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from . import admission, executor
from .params import SimParams, load_params
from .scheduler import (
    get_vector_scheduler,
    get_vector_scheduler_init,
    mask_down_pools,
)
from .state import INF_TICK, SimState, Workload, broadcast_lanes, init_state
from .telemetry.record import TraceBuffer, record_step, step_block_rows
from .telemetry.schema import DEFAULT_TRACE_CAPACITY, RECORD_WIDTH
from .types import ContainerStatus, PipeStatus
from .workload import get_workload

# The engine's top-level ``jax.named_scope``s. Every instruction of the
# compiled loop carries one of them on its ``op_name`` metadata
# (:func:`scope_map`), so a profiler trace's device ops can be summed
# per phase; ``loop`` is the while_loop's own work (its cond and the
# finished-lane masking).
ENGINE_SCOPES = (
    "phase1", "faults", "closed_loop", "scheduler", "apply", "advance",
    "telemetry", "loop",
)
UNSCOPED = "unscoped"
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a transform's or jit's wrapper around a name-stack entry: vmap(apply)
_WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")


@dataclasses.dataclass
class SimResult:
    state: SimState
    workload: Workload
    params: SimParams
    sched_state: Any = None
    trace: Any = None  # telemetry.TraceEvents when run(trace=True)

    def summary(self) -> dict:
        from .metrics import summarize

        return summarize(
            self.state, self.workload, self.params, trace=self.trace
        )


# ---------------------------------------------------------------------------
# One tick worth of work, as the sequential composition of executor
# passes. This is the *reference* body: the lane-major engine fuses the
# first three passes (see ``lane_event_step``), and the property suite +
# the benchmark reconstruction of the deleted vmap baseline drive this
# composition to prove the fusion semantics-preserving.
# ---------------------------------------------------------------------------
def _tick_body(
    state: SimState,
    sched_state: Any,
    wl: Workload,
    params: SimParams,
    scheduler_fn: Callable,
    tick: jax.Array,
):
    state = executor.process_arrivals(state, wl, tick)
    state = executor.process_releases(state, tick)
    state = executor.process_completions(state, wl, tick, params)
    if params.fault_events_active:
        state, _ = executor.apply_faults(state, wl, tick, params)
    if params.closed_loop_active:
        state = admission.apply_closed_loop(state, wl, tick, params)
    view = (
        mask_down_pools(state, tick)
        if params.outage_mtbf_ticks > 0
        else state
    )
    sched_state, dec = scheduler_fn(sched_state, view, wl, params)
    if params.outage_mtbf_ticks > 0:
        dec = _filter_down_pool_assignments(dec, state, tick, params)
    state = executor.apply_decision(state, wl, dec, tick, params)
    acted = (
        jnp.any(dec.suspend)
        | jnp.any(dec.reject)
        | jnp.any(dec.assign_pipe >= 0)
    )
    return state, sched_state, acted


def _filter_down_pool_assignments(
    dec, state: SimState, tick: jax.Array, params: SimParams
):
    """Drop scheduler assignments that target a down pool.

    Free-resource-driven schedulers already avoid down pools through the
    masked view (:func:`mask_down_pools`); this filter is the safety net
    for schedulers that size allocations off pool *caps* (``naive``),
    which would otherwise commit onto dead capacity — and, because the
    filtered decision feeds ``acted``, it also keeps an un-placeable
    head-of-queue from spinning the event loop tick-by-tick for the
    whole outage."""
    down = tick < state.pool_down_until
    NP = params.num_pools
    bad = (dec.assign_pipe >= 0) & down[jnp.clip(dec.assign_pool, 0, NP - 1)]
    return dec._replace(assign_pipe=jnp.where(bad, -1, dec.assign_pipe))


def _sorted_arrivals(arrival: jax.Array) -> jax.Array:
    """Arrival ticks sorted ascending, INF-padded by one slot so a cursor
    that has consumed every arrival reads INF_TICK. Works along the last
    axis, so it serves both single workloads [MP] and fleets [F, MP]."""
    pad_shape = arrival.shape[:-1] + (1,)
    return jnp.concatenate(
        [jnp.sort(arrival, axis=-1), jnp.full(pad_shape, INF_TICK, jnp.int32)],
        axis=-1,
    )


def _next_event_registers(
    state: SimState, arr_sorted: jax.Array, tick: jax.Array, acted
):
    """Register-based twin of :func:`_next_event`.

    Instead of re-reducing the pipeline/container tables, reads the
    executor-maintained ``nxt_retire``/``nxt_release`` registers and
    binary-searches the arrival-sorted workload — O(log MP) per event
    rather than O(MP + MC). Provably equal to the full recompute:
    after arrival admission at tick t, a pipeline slot is EMPTY iff
    its arrival tick is > t, so the pending-arrival minimum is the first
    sorted arrival beyond t; the register invariants cover the rest
    (see the property test in tests/test_fleet.py).

    Returns ``(next_tick, cursor)``; the cursor (count of arrivals <= t)
    is stored on the state as ``nxt_arrival_cursor``.
    """
    cursor = jnp.searchsorted(arr_sorted[:-1], tick, side="right").astype(
        jnp.int32
    )
    next_arrival = arr_sorted[cursor]
    nxt = jnp.minimum(
        jnp.minimum(next_arrival, state.nxt_retire), state.nxt_release
    )
    # chaos layer: ``apply_faults`` keeps nxt_fault at the next crash /
    # outage start / pool recovery tick; faults-off it is pinned at
    # INF_TICK, so the min is the identity there
    nxt = jnp.minimum(nxt, state.nxt_fault)
    nxt = jnp.where(acted, jnp.minimum(nxt, tick + 1), nxt)
    return jnp.maximum(nxt, tick + 1), cursor


def _next_event(state: SimState, wl: Workload, tick: jax.Array, acted) -> jax.Array:
    """Earliest tick strictly after ``tick`` at which state can change.

    The recompute-from-scratch oracle for the ``nxt_retire`` /
    ``nxt_release`` registers the engine actually navigates by:
    tests/test_fleet.py steps ``lane_event_step`` and asserts this full
    table reduction equals :func:`_next_event_registers` at every event.
    """
    pending = state.pipe_status == int(PipeStatus.EMPTY)
    arr = jnp.where(pending & (wl.arrival > tick), wl.arrival, INF_TICK)
    next_arrival = jnp.min(arr)

    # ctr_end/ctr_oom include the data-plane warm-up (cold-start + scan
    # ticks) baked in at creation, so release ticks of cold containers are
    # accounted for here without a separate event source. Cache contents
    # and slot warmth change only when the executor acts, never passively,
    # so they add no event sources either (warmth *expiry* is passive, but
    # it is only read at assignment ticks, which are always events).
    running = state.ctr_status == int(ContainerStatus.RUNNING)
    ends = jnp.where(running, jnp.minimum(state.ctr_end, state.ctr_oom), INF_TICK)
    next_retire = jnp.min(ends)

    suspended = state.pipe_status == int(PipeStatus.SUSPENDED)
    rel = jnp.where(suspended, state.pipe_release, INF_TICK)
    next_release = jnp.min(rel)

    nxt = jnp.minimum(jnp.minimum(next_arrival, next_retire), next_release)

    if wl.faults is not None:
        # chaos layer event sources, recomputed from scratch: the fault
        # trace is sorted, so "next crash/outage" is the earliest entry
        # strictly beyond ``tick`` (= what the ``crash_cursor`` /
        # ``outage_cursor`` registers index to), plus the earliest pool
        # recovery still pending.
        ft = wl.faults
        nxt_crash = jnp.min(
            jnp.where(ft.crash_time > tick, ft.crash_time, INF_TICK)
        )
        nxt_outage = jnp.min(
            jnp.where(ft.outage_start > tick, ft.outage_start, INF_TICK)
        )
        nxt_recover = jnp.min(
            jnp.where(
                state.pool_down_until > tick, state.pool_down_until, INF_TICK
            )
        )
        nxt = jnp.minimum(
            nxt, jnp.minimum(nxt_crash, jnp.minimum(nxt_outage, nxt_recover))
        )

    # if the scheduler acted, it may act again next tick (queue longer than
    # one decision's capacity, freshly freed resources, ...)
    nxt = jnp.where(acted, jnp.minimum(nxt, tick + 1), nxt)
    return jnp.maximum(nxt, tick + 1)


@contextlib.contextmanager
def _quiet_partial_donation():
    """Silence XLA's partial-donation lowering warning.

    The workload batch is donated so its big ops tables can be aliased
    into outputs; a few small leaves (op metadata with no same-shaped
    output) are not aliasable, which XLA reports once per compilation.
    That partial reuse is exactly the intent, so the note is noise here
    — but only here: the filter is scoped to the compiled-engine call
    sites, not installed globally.
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


# ---------------------------------------------------------------------------
# The lane-major engine.
# ---------------------------------------------------------------------------
def _zero_fault_aux(state: SimState):
    """The ``fault_aux`` of a not-due :func:`executor.apply_faults` call,
    constructed without running it — bitwise what the skipped call would
    have returned: empty kill masks, causes defaulting to 1 (= outage),
    no new outages/recoveries (``pool_down_until == tick`` would have
    made the event due), and ``pool_down_until`` passed through. Shape-
    polymorphic over a leading fleet axis."""
    i32 = jnp.int32
    MC = state.ctr_status.shape[-1]
    NP = state.pool_cpu_cap.shape[-1]
    batch = state.ctr_status.shape[:-1]
    return (
        jnp.zeros(batch + (MC,), bool),       # kill
        jnp.full(batch + (MC,), -1, i32),     # kill_pipe
        jnp.full(batch + (MC,), -1, i32),     # kill_pool
        jnp.ones(batch + (MC,), i32),         # kill_cause
        jnp.zeros(batch + (MC,), i32),        # kill_wasted
        jnp.zeros(batch + (NP,), bool),       # down_new
        jnp.zeros(batch + (NP,), bool),       # up_now
        state.pool_down_until,
    )


def _fleet_gated_faults(
    params: SimParams,
    states: SimState,
    wls: Workload,
    tick: jax.Array,
    active: jax.Array,
):
    """Run the fault pass only on events where some active lane's
    ``nxt_fault`` register is due; event-skip steps with no fault due
    pay one scalar compare instead of the full pass. The skipped call
    is a provable identity (``tick < nxt_fault`` means the searchsorted
    cursors do not move, the kill masks are empty, and the register
    recompute reproduces itself), so the gate is bitwise-neutral. The
    predicate is hoisted to the fleet level because a per-lane cond
    under ``vmap`` lowers to a select that runs both branches."""
    with jax.named_scope("faults"):
        due = jnp.any(active & (tick >= states.nxt_fault))

        def apply(sts):
            return jax.vmap(
                lambda s, w, t: executor.apply_faults(s, w, t, params)
            )(sts, wls, tick)

        def skip(sts):
            return sts, _zero_fault_aux(sts)

        return jax.lax.cond(due, apply, skip, states)


def _lane_decide(
    params: SimParams,
    horizon: jax.Array,
    scheduler_fn: Callable,
    with_aux: bool,
    state: SimState,
    sched_state: Any,
    wl: Workload,
    arr_sorted: jax.Array,
    tick: jax.Array,
):
    """One lane, one event, from the scheduler onward (the post-phase-1 /
    post-faults half of the step): schedule, apply the decision, and
    jump to the lane's next event. The named scopes (:data:`ENGINE_SCOPES`)
    label the engine phases in the compiled HLO's metadata; they never
    change the computation."""
    if params.closed_loop_active:
        with jax.named_scope("closed_loop"):
            state = admission.apply_closed_loop(state, wl, tick, params)
    st1 = state
    with jax.named_scope("scheduler"):
        view = (
            mask_down_pools(state, tick)
            if params.outage_mtbf_ticks > 0
            else state
        )
        sched_state, dec = scheduler_fn(sched_state, view, wl, params)
        if params.outage_mtbf_ticks > 0:
            dec = _filter_down_pool_assignments(dec, state, tick, params)
    with jax.named_scope("apply"):
        if with_aux:
            state, aux = executor.apply_decision(
                state, wl, dec, tick, params, early_exit=True, with_aux=True
            )
        else:
            state = executor.apply_decision(
                state, wl, dec, tick, params, early_exit=True
            )
            aux = None
    with jax.named_scope("advance"):
        acted = (
            jnp.any(dec.suspend)
            | jnp.any(dec.reject)
            | jnp.any(dec.assign_pipe >= 0)
        )
        nxt, cursor = _next_event_registers(state, arr_sorted, tick, acted)
        nxt = jnp.minimum(nxt, horizon)
        state = executor.integrate(state, tick, nxt, params, exact_buckets=True)
    state = state._replace(tick=nxt, nxt_arrival_cursor=cursor)
    return state, sched_state, st1, dec, aux


def _lane_step_core(
    params: SimParams,
    horizon: jax.Array,
    scheduler_fn: Callable,
    state: SimState,
    sched_state: Any,
    wl: Workload,
    arr_sorted: jax.Array,
    tick: jax.Array,
    ph,
    with_aux: bool,
):
    """One lane, one event. Returns the advanced ``(state, sched_state)``
    plus — for the telemetry recorder — the post-phase-1 state the
    scheduler saw, its decision, and (``with_aux=True`` only) the
    per-slot assignment aux from ``apply_decision``. This single-lane
    composition gates the fault pass on the lane's own ``nxt_fault``
    register (here the cond genuinely branches — the fleet engine uses
    :func:`_fleet_gated_faults` instead, since a vmapped cond would
    run both sides)."""
    with jax.named_scope("phase1"):
        state = executor.apply_fused_phase1(state, wl, tick, params, ph)
    if params.fault_events_active:
        with jax.named_scope("faults"):
            state, fault_aux = jax.lax.cond(
                tick >= state.nxt_fault,
                lambda s: executor.apply_faults(s, wl, tick, params),
                lambda s: (s, _zero_fault_aux(s)),
                state,
            )
    else:
        fault_aux = None
    state, sched_state, st1, dec, aux = _lane_decide(
        params, horizon, scheduler_fn, with_aux, state, sched_state, wl,
        arr_sorted, tick,
    )
    return state, sched_state, st1, dec, aux, fault_aux


def lane_event_step(
    params: SimParams,
    horizon: jax.Array,
    scheduler_fn: Callable,
    state: SimState,
    sched_state: Any,
    wl: Workload,
    arr_sorted: jax.Array,
    tick: jax.Array,
    ph,
):
    """Advance ONE lane by one event: apply the fused phase-1 masks,
    schedule, apply the decision, and jump to the lane's next event.

    Module-level so the oracle test can drive a single lane directly
    (``_next_event`` vs ``_next_event_registers`` at every event); the
    engine vmaps it over the fleet axis.
    """
    state, sched_state, _, _, _, _ = _lane_step_core(
        params, horizon, scheduler_fn, state, sched_state, wl,
        arr_sorted, tick, ph, with_aux=False,
    )
    return state, sched_state


def lane_event_step_traced(
    params: SimParams,
    trace_capacity: int,
    horizon: jax.Array,
    scheduler_fn: Callable,
    state: SimState,
    sched_state: Any,
    tbuf: TraceBuffer,
    wl: Workload,
    arr_sorted: jax.Array,
    tick: jax.Array,
    ph,
    active: jax.Array,
):
    """:func:`lane_event_step` plus the telemetry recorder: identical
    state/scheduler updates (the recorder only reads), with every event
    of the step appended to the lane's trace buffer. ``active`` gates
    all buffer writes so finished lanes record nothing while the fleet
    loop drains stragglers."""
    pre = state
    state, sched_state, st1, dec, aux, fault_aux = _lane_step_core(
        params, horizon, scheduler_fn, state, sched_state, wl,
        arr_sorted, tick, ph, with_aux=True,
    )
    with jax.named_scope("telemetry"):
        tbuf = record_step(
            tbuf, trace_capacity, active, pre, st1, state, wl, params,
            tick, ph, dec, aux, fault_aux,
        )
    return state, sched_state, tbuf


def _run_lane_major_engine(
    params, wls, scheduler_fn, sched_state0, impl="auto", trace_capacity=0
):
    """Shared masked while_loop over the whole batch ``wls`` [F, ...].

    ``trace_capacity`` is static: 0 (the default) compiles exactly the
    untraced loop below — telemetry off costs nothing and perturbs
    nothing — while a positive capacity swaps in the traced lane step
    and threads per-lane :class:`TraceBuffer`\\ s through the carry,
    returning ``(states, scheds, tbufs)``. Trace buffers deliberately
    skip the finished-lane ``keep`` masking (that jnp.where would copy
    the whole [F, cap, W] table every event); the recorder itself gates
    writes on ``active``, so an inactive lane's cursor never advances
    and its valid prefix stays untouched. In the carry the tables hold
    ``step_block_rows`` scratch rows past ``capacity`` (the recorder's
    contiguous writer spills there on overflow); the scratch is sliced
    off before returning, so callers see exactly ``[F, cap, W]``.
    """
    from repro.kernels.sim_tick import fleet_tick

    horizon = jnp.int32(params.horizon_ticks)
    F = wls.arrival.shape[0]
    arr_sorted = _sorted_arrivals(wls.arrival)  # [F, MP + 1]

    states0 = broadcast_lanes(init_state(params), F)
    scheds0 = broadcast_lanes(sched_state0, F)
    faults_on = params.fault_events_active

    def phase1(state, wl, tick, ph):
        with jax.named_scope("phase1"):
            return executor.apply_fused_phase1(state, wl, tick, params, ph)

    def tick_kernel(states):
        with jax.named_scope("phase1"):
            return fleet_tick(
                states.ctr_status, states.ctr_end, states.ctr_oom,
                states.ctr_cpus, states.ctr_ram, states.ctr_pool,
                states.pipe_status, wls.arrival, states.pipe_release,
                states.tick, num_pools=params.num_pools, impl=impl,
            )

    def lanes_active(states):
        with jax.named_scope("loop"):
            return states.tick < horizon

    def cond(carry):  # carry[0] is the states, traced or not
        with jax.named_scope("loop"):
            return jnp.any(carry[0].tick < horizon)

    # finished lanes pass through untouched
    def keep(active, new, old):
        def one(n, o):
            mask = jnp.reshape(active, (F,) + (1,) * (n.ndim - 1))
            return jnp.where(mask, n, o)

        with jax.named_scope("loop"):
            return jax.tree.map(one, new, old)

    if trace_capacity == 0:
        lane = functools.partial(
            lane_event_step, params, horizon, scheduler_fn
        )
        decide = functools.partial(
            _lane_decide, params, horizon, scheduler_fn, False
        )

        def body(carry):
            states, scheds = carry
            tick = states.tick                     # [F]
            active = lanes_active(states)          # [F]
            ph = tick_kernel(states)

            if faults_on:
                # split body: vmap(phase1) -> fleet-gated faults ->
                # vmap(decide). vmap of the composition == composition
                # of the vmaps, so this is bitwise the single-vmap body
                # below with the fault pass hoisted behind its register.
                sts1 = jax.vmap(phase1)(states, wls, tick, ph)
                sts1, _ = _fleet_gated_faults(params, sts1, wls, tick, active)
                new_states, new_scheds, _, _, _ = jax.vmap(decide)(
                    sts1, scheds, wls, arr_sorted, tick
                )
            else:
                new_states, new_scheds = jax.vmap(lane)(
                    states, scheds, wls, arr_sorted, tick, ph
                )

            return keep(active, (new_states, new_scheds), (states, scheds))

        return jax.lax.while_loop(cond, body, (states0, scheds0))

    scratch = step_block_rows(
        params.max_pipelines, params.max_containers,
        params.max_assignments_per_tick, params,
    )
    tbufs0 = TraceBuffer(
        records=jnp.zeros(
            (F, trace_capacity + scratch, RECORD_WIDTH), jnp.int32
        ),
        count=jnp.zeros((F,), jnp.int32),
        dropped=jnp.zeros((F,), jnp.int32),
    )
    lane_t = functools.partial(
        lane_event_step_traced, params, trace_capacity, horizon, scheduler_fn
    )

    def decide_t(pre, st1_in, sched_state, tbuf, wl, arr_sorted_l, tick,
                 ph, active, fault_aux):
        state, sched_state, st1, dec, aux = _lane_decide(
            params, horizon, scheduler_fn, True, st1_in, sched_state, wl,
            arr_sorted_l, tick,
        )
        with jax.named_scope("telemetry"):
            tbuf = record_step(
                tbuf, trace_capacity, active, pre, st1, state, wl, params,
                tick, ph, dec, aux, fault_aux,
            )
        return state, sched_state, tbuf

    def body_t(carry):
        states, scheds, tbufs = carry
        tick = states.tick
        active = lanes_active(states)
        ph = tick_kernel(states)

        if faults_on:
            # same split as the untraced body; the recorder consumes the
            # batched fault_aux (zeros on skipped events — bitwise what
            # the ungated pass would have reported)
            sts1 = jax.vmap(phase1)(states, wls, tick, ph)
            sts1, fault_auxs = _fleet_gated_faults(
                params, sts1, wls, tick, active
            )
            new_states, new_scheds, tbufs = jax.vmap(decide_t)(
                states, sts1, scheds, tbufs, wls, arr_sorted, tick, ph,
                active, fault_auxs,
            )
        else:
            new_states, new_scheds, tbufs = jax.vmap(lane_t)(
                states, scheds, tbufs, wls, arr_sorted, tick, ph, active
            )

        states, scheds = keep(
            active, (new_states, new_scheds), (states, scheds)
        )
        return states, scheds, tbufs

    states, scheds, tbufs = jax.lax.while_loop(
        cond, body_t, (states0, scheds0, tbufs0)
    )
    tbufs = tbufs._replace(records=tbufs.records[:, :trace_capacity])
    return states, scheds, tbufs


@functools.partial(
    jax.jit,
    static_argnames=("params", "scheduler_key", "impl", "trace_capacity"),
    donate_argnames=("workloads",),
)
def _fleet_compiled(
    params: SimParams,
    workloads: Workload,  # batched: leading axis = fleet
    scheduler_key: str,
    impl: str = "auto",
    trace_capacity: int = 0,
):
    """THE compiled simulation core: every entry point lands here.

    ``run()`` passes a batch of one lane, ``fleet_run`` a batch of N
    (possibly one shard of a device-sharded fleet). Returns the batched
    final ``(SimState, sched_state)`` — plus batched ``TraceBuffer``\\ s
    when the static ``trace_capacity`` is positive (telemetry on).

    The workload batch is DONATED: XLA may reuse the ops tables' buffers
    for outputs, so a large fleet never holds two copies of them across
    the call. Callers must treat their ``workloads`` pytree as consumed
    (every in-repo entry point passes a freshly built batch).
    """
    scheduler_fn = get_vector_scheduler(scheduler_key, early_exit=True)
    sched_state0 = get_vector_scheduler_init(scheduler_key)(params)
    return _run_lane_major_engine(
        params, workloads, scheduler_fn, sched_state0, impl, trace_capacity
    )


def _scope_of(op_name: str) -> str:
    """The first engine scope on an ``op_name`` path, read through the
    ``jit(...)``/``vmap(...)`` wrappers and the ``while/body``,
    ``while/cond`` and ``shard_map`` entries around it."""
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in ENGINE_SCOPES:
            return part
    return UNSCOPED


def scope_map(hlo_text: str) -> dict[str, str]:
    """``{instruction name: engine scope}`` of a compiled engine's HLO
    text (``compiled.as_text()``), every computation's instructions
    included.

    An instruction's scope is the first of :data:`ENGINE_SCOPES` on its
    ``op_name``; one with no engine scope, or no metadata at all (the
    copies XLA inserts), maps to :data:`UNSCOPED`. A profiler trace
    names each device op by its instruction, so this map splits the
    trace's device time by engine phase (``sweep.compiled_scope_map``
    builds it for the program a call ran).

    >>> scope_map('%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), '
    ...           'metadata={op_name="jit(_fleet_compiled)/while/body/'
    ...           'vmap(apply)/gather"}\\n%copy.1 = f32[4]{0} copy(%p)')
    {'fusion.3': 'apply', 'copy.1': 'unscoped'}
    """
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = _scope_of(op.group(1)) if op else UNSCOPED
    return out


def run(
    paramfile: str | dict | SimParams,
    workload: Workload | None = None,
    engine: str | None = None,
    *,
    trace: bool = False,
    trace_capacity: int = DEFAULT_TRACE_CAPACITY,
) -> SimResult:
    """Run one simulation; this is what ``eudoxia.run_simulator`` wraps.

    A single run is a fleet of one: the workload gains a lane axis, the
    lane-major core advances it, and the result is squeezed back —
    bitwise-identical to the dedicated single-sim event engine this
    replaced (checked against a frozen capture during the unification
    refactor; continuously guarded by the Python-reference equivalence
    suite and the run-vs-fleet-lane tests in tests/test_fleet.py).

    ``trace=True`` records an on-device event trace of up to
    ``trace_capacity`` records (compiled engine only) and decodes it
    into ``result.trace`` (:class:`repro.core.telemetry.TraceEvents`);
    the simulated state is bitwise-identical either way (guarded by
    tests/test_telemetry.py). On overflow the earliest records win and
    ``result.trace.events_dropped`` counts the rest.

    While a ``jax.profiler`` trace runs, the host spans
    ``eudoxia/run/prepare``, ``eudoxia/run/dispatch`` and
    ``eudoxia/run/unpack`` mark its parts (docs/observability.md).
    """
    with TraceAnnotation("eudoxia/run/prepare"):
        params = load_params(paramfile)
        engine = engine or params.engine
        wl = workload if workload is not None else get_workload(params)
        if params.fault_trace_active and wl.faults is None:
            # chaos layer on but the workload came in bare (trace replay
            # / caller-built): materialise the fault trace from
            # params.seed so both engines replay the same fault sequence
            from .faults import attach_fault_trace

            wl = attach_fault_trace(wl, params)
    if engine == "python":
        if trace:
            raise ValueError(
                "trace=True requires the compiled event engine; the "
                "Python reference engine records no telemetry"
            )
        from .engine_python import run_python_engine

        return run_python_engine(params, wl)
    if engine != "event":
        raise ValueError(
            f"unknown engine {engine!r}: the per-tick scan engine was removed "
            "in the lane-major unification (the event core is "
            "bitwise-identical and strictly faster); use engine='event' "
            "(default) or the reference engine='python'"
        )
    capacity = int(trace_capacity) if trace else 0
    if trace and capacity <= 0:
        raise ValueError(f"trace_capacity must be positive, got {trace_capacity}")
    with TraceAnnotation("eudoxia/run/dispatch"), _quiet_partial_donation():
        wls = jax.tree.map(lambda x: x[None], wl)
        out = _fleet_compiled(
            params, wls, params.scheduling_algo, trace_capacity=capacity
        )
    with TraceAnnotation("eudoxia/run/unpack"):
        events = None
        if capacity:
            states, scheds, tbufs = out
            from .telemetry.decode import decode_lane

            events = decode_lane(tbufs, 0)
        else:
            states, scheds = out
        state = jax.tree.map(lambda x: x[0], states)
        sched_state = jax.tree.map(lambda x: x[0], scheds)
    return SimResult(
        state=state, workload=wl, params=params, sched_state=sched_state,
        trace=events,
    )


__all__ = [
    "ENGINE_SCOPES",
    "SimResult",
    "UNSCOPED",
    "run",
    "scope_map",
    "lane_event_step",
    "lane_event_step_traced",
    "_fleet_compiled",
    "_tick_body",
    "_next_event",
    "_next_event_registers",
    "_sorted_arrivals",
    "_run_lane_major_engine",
]
