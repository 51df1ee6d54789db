"""Simulator engine throughput (paper §3.1 "low-cost" claim, and the
headline §Perf / §Fleet-Perf hillclimbs): the unified lane-major core
vs the Python reference, in simulated-seconds per wall-second, and the
fleet section on a 64-lane batch with skewed per-lane durations/event
counts (LogNormal ``op_base_seconds_sigma=1.2`` — the chained-pipeline
regime where lockstep batching wastes the most work).

The fleet rows compare three paths:

* ``vmap`` — a benchmark-local reconstruction of the DELETED legacy
  fleet path (vmap of a per-simulation event while_loop over the
  reference ``_tick_body`` composition). It exists only here, as the
  baseline the lane-major core is tracked against across PRs
  (BENCH_fleet.json).
* ``fused`` — the lane-major core, ``fleet_run(..., shard=None)``.
* ``sharded`` — ``fleet_run(..., shard="auto")``: the same core
  shard_mapped over every local device (force >1 on CPU with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``), with
  event-density lane binning on (the default).

On top (EXPERIMENTS.md §Scheduler-Perf): ``selection_bench`` times the
schedulers' fused masked selection against the seed three-pass
helpers; it is recorded into BENCH_fleet.json by ``benchmarks.run``.
Device time per engine phase comes from a profiler trace of the fused
loop, split by ``engine.scope_map`` (docs/observability.md).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import SimParams, fleet_run, generate_workload, run
from repro.core import engine as engine_mod
from repro.core import executor
from repro.core.scheduler import (
    get_vector_scheduler,
    get_vector_scheduler_init,
    select_next_pipe,
    select_victim,
)
from repro.core.state import broadcast_lanes, init_state
from repro.core.sweep import make_workload_batch
from repro.kernels.sched_select import masked_lex_argmin


def _time(fn, reps=3):
    """Post-compile wall-clock: (min, mean) over ``reps`` runs."""
    fn()  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts), sum(ts) / len(ts)


def _legacy_vmap_runner(params: SimParams, scheduler_key: str):
    """Reconstruct the deleted ``fleet_engine="vmap"`` path: vmap of a
    per-simulation event while_loop over the generic tick body. Kept
    only as the benchmark baseline."""
    scheduler_fn = get_vector_scheduler(scheduler_key)
    sched_state0 = get_vector_scheduler_init(scheduler_key)(params)
    horizon = jnp.int32(params.horizon_ticks)

    def one(wl):
        arr_sorted = engine_mod._sorted_arrivals(wl.arrival)

        def cond(carry):
            state, _ = carry
            return state.tick < horizon

        def body(carry):
            state, ss = carry
            tick = state.tick
            state, ss, acted = engine_mod._tick_body(
                state, ss, wl, params, scheduler_fn, tick
            )
            nxt, cursor = engine_mod._next_event_registers(
                state, arr_sorted, tick, acted
            )
            nxt = jnp.minimum(nxt, horizon)
            state = executor.integrate(
                state, tick, nxt, params, exact_buckets=True
            )
            return state._replace(tick=nxt, nxt_arrival_cursor=cursor), ss

        state, _ = jax.lax.while_loop(
            cond, body, (init_state(params), sched_state0)
        )
        return state

    return jax.jit(jax.vmap(one))


def _fleet_params(smoke: bool) -> SimParams:
    # smoke keeps the compile cheap (small tables: MP=32, MC=32, F=32)
    # but simulates the full duration so walls land ~0.2 s — sub-0.1 s
    # walls on a loaded 2-core runner swing 3x, which would make the CI
    # regression gate's fused/vmap ratio pure jitter (min-of-3 reps and
    # the same-run ratio absorb the rest of the load noise)
    return SimParams(
        duration=1.0,
        waiting_ticks_mean=5_000,      # the simulator default arrival rate
        op_base_seconds_mean=0.03,
        op_base_seconds_sigma=1.2,     # heavy-tailed durations -> skew
        op_ram_gb_mean=2.0,
        max_pipelines=32 if smoke else 128,
        max_containers=32 if smoke else 64,
        scheduling_algo="priority",
    )


def fleet_bench(smoke: bool = False) -> list[dict]:
    """Lane-major core (unsharded + sharded) vs the deleted vmap path."""
    fleet_size = 32 if smoke else 64
    params = _fleet_params(smoke)
    seeds = list(range(fleet_size))
    horizon = params.horizon_ticks
    # smoke walls are ~0.1 s, so extra reps are cheap and the min-of-3
    # feeds the CI regression gate (which compares fused/vmap ratios)
    reps = 3
    n_dev = jax.local_device_count()

    legacy = _legacy_vmap_runner(params, "priority")

    # every path pays workload-batch construction inside the clock —
    # fleet_run has to rebuild per call (the batch is donated), so the
    # vmap baseline rebuilds too, keeping the fused/vmap ratio the CI
    # gate trusts a pure engine comparison
    runners = {
        "vmap": lambda: jax.block_until_ready(
            legacy(make_workload_batch(params, seeds)).done_count
        ),
        "fused": lambda: jax.block_until_ready(
            fleet_run(params, seeds, shard=None).done_count
        ),
        "sharded": lambda: jax.block_until_ready(
            fleet_run(params, seeds, shard="auto").done_count
        ),
    }

    rows = []
    for name, go in runners.items():
        t_min, t_mean = _time(go, reps=reps)
        rows.append(
            {
                "engine": f"fleet {name} x{fleet_size}",
                "fleet_engine": name,
                "fleet_size": fleet_size,
                "devices": n_dev if name == "sharded" else 1,
                "wall_s": round(t_mean, 4),
                "wall_s_min": round(t_min, 4),
                "ticks_per_s": round(fleet_size * horizon / t_min),
                "sim_s_per_wall_s": round(
                    fleet_size * params.duration / t_min, 2
                ),
            }
        )
    base = rows[0]["wall_s_min"]
    for r in rows[1:]:
        r["speedup_vs_vmap"] = round(base / r["wall_s_min"], 2)
    return rows


def trace_overhead_bench(smoke: bool = False, reps: int = 7) -> list[dict]:
    """Telemetry cost on the fused fleet path: the same bench as the
    ``fused`` row with ``trace=True`` at the default ring capacity,
    including the host-side decode. Off-path throughput is re-measured
    in the same call so ``trace_overhead_pct`` is a same-run ratio
    (machine speed and load normalise out, as in the CI smoke gate).
    Feeds the ``fused_traced`` row of BENCH_fleet.json; the <10%
    acceptance bar lives in EXPERIMENTS.md §Telemetry. This row gets
    min-of-7 (vs min-of-3 for the throughput rows): the overhead is a
    ratio of two ~0.1-0.5 s walls, so scheduler jitter that the
    absolute rows shrug off would dominate it at 3 reps.
    """
    from repro.core.telemetry.schema import DEFAULT_TRACE_CAPACITY

    fleet_size = 32 if smoke else 64
    params = _fleet_params(smoke)
    seeds = list(range(fleet_size))
    horizon = params.horizon_ticks

    def fused_off():
        return jax.block_until_ready(
            fleet_run(params, seeds, shard=None).done_count
        )

    def fused_on():
        states, traces = fleet_run(params, seeds, shard=None, trace=True)
        jax.block_until_ready(states.done_count)
        return traces

    t_off_min, _ = _time(fused_off, reps=reps)
    t_on_min, t_on_mean = _time(fused_on, reps=reps)
    traces = fused_on()
    overhead_pct = round((t_on_min / t_off_min - 1.0) * 100, 1)
    return [
        {
            "engine": f"fleet fused+trace x{fleet_size}",
            "fleet_engine": "fused_traced",
            "fleet_size": fleet_size,
            "devices": 1,
            "wall_s": round(t_on_mean, 4),
            "wall_s_min": round(t_on_min, 4),
            "ticks_per_s": round(fleet_size * horizon / t_on_min),
            "sim_s_per_wall_s": round(
                fleet_size * params.duration / t_on_min, 2
            ),
            "trace_capacity": DEFAULT_TRACE_CAPACITY,
            "events_recorded": int(sum(t.n for t in traces)),
            "events_dropped": int(sum(t.events_dropped for t in traces)),
            "untraced_wall_s_min": round(t_off_min, 4),
            "trace_overhead_pct": overhead_pct,
        }
    ]


def faults_overhead_bench(smoke: bool = False, reps: int = 7) -> list[dict]:
    """Chaos-layer cost on the fused fleet path (docs/faults.md): the
    ``fused`` bench re-measured with crash/outage/timeout injection and
    a retry budget on, against a faults-off run timed in the same call
    — ``faults_overhead_pct`` is a same-run ratio like the trace and CI
    smoke gates, so machine speed normalises out. The faults-ON run
    pays fault-trace generation inside the clock (it rides workload
    construction, which every fleet row pays). Feeds the
    ``fused_faults`` row of BENCH_fleet.json. Min-of-7 for the same
    reason as ``trace_overhead_bench``: a ratio of two short walls
    needs more reps than an absolute row."""
    fleet_size = 32 if smoke else 64
    params_off = _fleet_params(smoke)
    # moderate churn (~5 crashes + ~2 outages per lane-horizon): enough
    # to keep every chaos path hot without the extra *simulated* events
    # dwarfing the layer's fixed per-event cost in the ratio
    params_on = params_off.replace(
        crash_mtbf_ticks=20_000.0,
        outage_mtbf_ticks=50_000.0,
        outage_duration_ticks=2_000.0,
        straggler_prob=0.05,
        timeout_ticks=200_000,
        max_retries=3,
        base_backoff_ticks=100,
    )
    seeds = list(range(fleet_size))
    horizon = params_off.horizon_ticks

    def fused_off():
        return jax.block_until_ready(
            fleet_run(params_off, seeds, shard=None).done_count
        )

    def fused_on():
        return jax.block_until_ready(
            fleet_run(params_on, seeds, shard=None).done_count
        )

    t_off_min, _ = _time(fused_off, reps=reps)
    t_on_min, t_on_mean = _time(fused_on, reps=reps)
    states = fleet_run(params_on, seeds, shard=None)
    overhead_pct = round((t_on_min / t_off_min - 1.0) * 100, 1)
    return [
        {
            "engine": f"fleet fused+faults x{fleet_size}",
            "fleet_engine": "fused_faults",
            "fleet_size": fleet_size,
            "devices": 1,
            "wall_s": round(t_on_mean, 4),
            "wall_s_min": round(t_on_min, 4),
            "ticks_per_s": round(fleet_size * horizon / t_on_min),
            "sim_s_per_wall_s": round(
                fleet_size * params_on.duration / t_on_min, 2
            ),
            "fault_kills": int(jnp.sum(states.fault_kills)),
            "retries": int(jnp.sum(states.retry_events)),
            "timeouts": int(jnp.sum(states.timeout_events)),
            "unfaulted_wall_s_min": round(t_off_min, 4),
            "faults_overhead_pct": overhead_pct,
        }
    ]


def closed_loop_overhead_bench(smoke: bool = False, reps: int = 7) -> list[dict]:
    """Closed-loop-layer cost on the fused fleet path
    (docs/closed-loop.md): the ``fused`` bench re-measured with the
    client concurrency gate, client retries, and a queue-threshold
    admission policy on, against a loop-off run timed in the same call —
    ``closed_loop_overhead_pct`` is a same-run ratio like the trace and
    faults gates, so machine speed normalises out. Feeds the
    ``fused_closed_loop`` row of BENCH_fleet.json. Min-of-7 for the same
    reason as ``trace_overhead_bench``: a ratio of two short walls needs
    more reps than an absolute row."""
    fleet_size = 32 if smoke else 64
    params_off = _fleet_params(smoke)
    # a gate tight enough that admission actually rejects/defers (every
    # closed-loop path stays hot) but loose enough that the *simulated*
    # work doesn't collapse and skew the wall-clock ratio
    params_on = params_off.replace(
        client_max_inflight=6,
        client_think_ticks=200,
        client_max_retries=3,
        client_backoff_ticks=200,
        admission_policy="queue_threshold",
        admit_queue_limit=4,
    )
    seeds = list(range(fleet_size))
    horizon = params_off.horizon_ticks

    def loop_off():
        return jax.block_until_ready(
            fleet_run(params_off, seeds, shard=None).done_count
        )

    def loop_on():
        return jax.block_until_ready(
            fleet_run(params_on, seeds, shard=None).done_count
        )

    t_off_min, _ = _time(loop_off, reps=reps)
    t_on_min, t_on_mean = _time(loop_on, reps=reps)
    states = fleet_run(params_on, seeds, shard=None)
    overhead_pct = round((t_on_min / t_off_min - 1.0) * 100, 1)
    return [
        {
            "engine": f"fleet fused+closed-loop x{fleet_size}",
            "fleet_engine": "fused_closed_loop",
            "fleet_size": fleet_size,
            "devices": 1,
            "wall_s": round(t_on_mean, 4),
            "wall_s_min": round(t_on_min, 4),
            "ticks_per_s": round(fleet_size * horizon / t_on_min),
            "sim_s_per_wall_s": round(
                fleet_size * params_on.duration / t_on_min, 2
            ),
            "offered": int(jnp.sum(states.offered_total)),
            "shed": int(jnp.sum(states.shed_total)),
            "deferred": int(jnp.sum(states.deferred_total)),
            "client_retries": int(jnp.sum(states.client_retry_events)),
            "open_loop_wall_s_min": round(t_off_min, 4),
            "closed_loop_overhead_pct": overhead_pct,
        }
    ]


def scenario_fleet_bench(smoke: bool = False) -> list[dict]:
    """Scenario-family throughput rows (fused vs sharded) for
    BENCH_fleet.json: each family of the scenario library is drawn as a
    trace batch and replayed through ``fleet_run(workloads=...)``, so
    the perf record tracks the engine on *realistic skew* (diurnal
    ramps, bursts, heavy tails, priority storms) and not just seed
    variance — the regime event-density lane binning targets. Like the
    seed-fleet rows, every path pays workload construction (here: trace
    ingestion) inside the clock; the batch is donated, so it is rebuilt
    per call on both paths and the fused/sharded comparison stays fair.
    """
    from repro.core import workload_batch_from_traces
    from repro.core.scenarios import list_scenarios, scenario_lane_batch

    fleet_size = 8 if smoke else 32
    base = _fleet_params(smoke).replace(
        max_pipelines=0, max_ops_per_pipeline=0
    )
    n_dev = jax.local_device_count()
    rows = []
    for scen in list_scenarios():
        lanes = scenario_lane_batch(scen, base, fleet_size, seed=0)
        _, params = workload_batch_from_traces(lanes, base)
        horizon = params.horizon_ticks

        def replay(shard, params=params, lanes=lanes):
            wls, _ = workload_batch_from_traces(lanes, params)
            return jax.block_until_ready(
                fleet_run(params, workloads=wls, shard=shard).done_count
            )

        for engine, shard in (("fused", None), ("sharded", "auto")):
            t_min, t_mean = _time(lambda s=shard: replay(s), reps=3)
            rows.append(
                {
                    "scenario": scen,
                    "fleet_engine": engine,
                    "fleet_size": fleet_size,
                    "devices": n_dev if engine == "sharded" else 1,
                    "max_pipelines": params.max_pipelines,
                    "wall_s": round(t_mean, 4),
                    "wall_s_min": round(t_min, 4),
                    "ticks_per_s": round(fleet_size * horizon / t_min),
                    "sim_s_per_wall_s": round(
                        fleet_size * params.duration / t_min, 2
                    ),
                }
            )
    return rows


def selection_bench(n_rounds: int = 24, reps: int = 7) -> dict:
    """Scheduler-selection microbench: the seed three-pass helpers vs
    the fused ``sched_select.masked_lex_argmin``, replicating the
    engine's decision loop exactly — a sequential drain of the waiting
    queue on the shapes the 64-lane fleet batches ([64, MP] pipes +
    [64, MC] containers), where each slot's candidate mask excludes the
    pipes already tried and each victim leaves the live set. The whole
    drain runs inside one jitted ``lax.scan`` so the clock sees the
    selection chain's compute (it IS the critical path of a decision),
    not per-call dispatch. Feeds the ``selection`` row of
    BENCH_fleet.json and the EXPERIMENTS kernel speedup table.
    """
    F, MP, MC = 64, 128, 64
    K = 16  # max_assignments_per_tick: slots per drain
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    base = jax.random.bernoulli(ks[0], 0.3, (F, MP))
    prio = jax.random.randint(ks[1], (F, MP), 0, 3)
    entered = jax.random.randint(ks[2], (F, MP), 0, 100_000)
    live0 = jax.random.bernoulli(ks[3], 0.5, (F, MC))
    cprio = jax.random.randint(ks[4], (F, MC), 0, 3)
    cstart = jax.random.randint(ks[5], (F, MC), 0, 100_000)
    below = jnp.full((F,), 2, jnp.int32)
    rows = jnp.arange(F)

    def drain(select_pipe, select_vic):
        def slot(carry, _):
            tried, live, acc = carry
            pipe = select_pipe(base & ~tried)
            victim = select_vic(live)
            tried = tried.at[rows, jnp.maximum(pipe, 0)].set(True)
            live = live.at[rows, jnp.maximum(victim, 0)].set(False)
            return (tried, live, acc + pipe + victim), None

        def rounds(_, __):
            carry0 = (jnp.zeros((F, MP), bool), live0, jnp.zeros((F,), jnp.int32))
            (_, _, acc), _ = jax.lax.scan(slot, carry0, None, length=K)
            return acc, None

        acc, _ = jax.lax.scan(rounds, jnp.zeros((F,), jnp.int32), None,
                              length=n_rounds)
        return acc

    @jax.jit
    def three_pass():
        return drain(
            lambda m: jax.vmap(select_next_pipe)(m, prio, entered),
            lambda lv: jax.vmap(select_victim)(lv, cprio, cstart, below),
        )

    @jax.jit
    def fused():
        return drain(
            lambda m: masked_lex_argmin(m, (-prio, entered)),
            lambda lv: masked_lex_argmin(
                lv & (cprio < below[:, None]), (cprio, -cstart)
            ),
        )

    out = {}
    n_slots = n_rounds * K
    for name, fn in (("three_pass", three_pass), ("fused", fused)):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        out[f"{name}_us"] = round(min(ts) * 1e6 / n_slots, 2)
    out["speedup"] = round(out["three_pass_us"] / out["fused_us"], 2)
    # sanity: both selection chains agree before we publish a speedup
    assert bool(jnp.array_equal(three_pass(), fused()))
    return out


def apply_bench(n_rounds: int = 24, reps: int = 7) -> dict:
    """Decision-application microbench: the seed's fixed ``fori_loop``
    of per-slot ``lax.cond`` commits vs the fused early-exit rows loop
    + ``state_update.assign_gather`` landing, on the engine's own
    shapes — 64 lanes of real scheduler decisions applied to mid-flight
    states. Like ``selection_bench`` the whole drain runs inside one
    jitted ``lax.scan`` (the tick offset threads the carry so the body
    is not loop-invariant), so the clock sees the commit chain's
    compute rather than per-call dispatch. Feeds the ``apply`` row of
    BENCH_fleet.json.
    """
    params = _fleet_params(smoke=False)
    F = 64
    scheduler_fn = get_vector_scheduler("priority", early_exit=True)
    ss0 = broadcast_lanes(get_vector_scheduler_init("priority")(params), F)
    wls = make_workload_batch(params, list(range(F)))
    states = broadcast_lanes(init_state(params), F)
    # land the early arrivals so the scheduler has real work to hand out
    tick = jnp.full((F,), 2_000, jnp.int32)
    states = jax.jit(jax.vmap(executor.process_arrivals))(states, wls, tick)
    states = states._replace(tick=tick)
    _, decs = jax.jit(
        jax.vmap(lambda ss, s, w: scheduler_fn(ss, s, w, params))
    )(ss0, states, wls)

    def make(early_exit):
        @jax.jit
        def fn():
            def round_(tok, _):
                out = jax.vmap(
                    lambda s, w, d, t: executor.apply_decision(
                        s, w, d, t, params, early_exit=early_exit
                    )
                )(states, wls, decs, states.tick + tok)
                return tok + 1, jnp.sum(out.done_count) + jnp.sum(
                    out.ctr_pipe
                )
            _, outs = jax.lax.scan(
                round_, jnp.int32(0), None, length=n_rounds
            )
            return outs
        return fn

    legacy, fused = make(early_exit=False), make(early_exit=True)
    out = {}
    for name, fn in (("legacy", legacy), ("fused", fused)):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        out[f"{name}_us"] = round(min(ts) * 1e6 / n_rounds, 2)
    out["speedup"] = round(out["legacy_us"] / out["fused_us"], 2)
    # sanity: both commit paths agree before we publish a speedup
    assert bool(jnp.array_equal(legacy(), fused()))
    return out


def main(print_rows: bool = True, smoke: bool = False) -> list[dict]:
    rows = []
    params = SimParams(
        duration=0.05 if smoke else 1.0,
        waiting_ticks_mean=2500,
        op_base_seconds_mean=0.03,
        op_ram_gb_mean=2.0,
        max_pipelines=32 if smoke else 128,
        max_containers=32 if smoke else 64,
        scheduling_algo="priority",
    )
    wl = generate_workload(params)
    horizon = params.horizon_ticks

    def event_run():
        jax.block_until_ready(
            run(params, workload=wl, engine="event").state.done_count
        )

    t_event, t_event_mean = _time(event_run, reps=1 if smoke else 3)
    rows.append(
        {
            "engine": "lane-major core (F=1)",
            "wall_s": round(t_event_mean, 4),
            "wall_s_min": round(t_event, 4),
            "ticks_per_s": round(horizon / t_event),
            "sim_s_per_wall_s": round(params.duration / t_event, 2),
        }
    )

    # python reference engine (per-tick plain-object loop)
    t0 = time.perf_counter()
    run(params, workload=wl, engine="python")
    t_py = time.perf_counter() - t0
    rows.append(
        {
            "engine": "python (reference)",
            "wall_s": round(t_py, 4),
            "wall_s_min": round(t_py, 4),
            "ticks_per_s": round(horizon / t_py),
            "sim_s_per_wall_s": round(params.duration / t_py, 2),
            "speedup_core_vs_python": round(t_py / t_event, 1),
        }
    )

    rows.extend(fleet_bench(smoke=smoke))
    rows.extend(trace_overhead_bench(smoke=smoke))
    rows.extend(faults_overhead_bench(smoke=smoke))
    rows.extend(closed_loop_overhead_bench(smoke=smoke))
    if not smoke:
        # scheduler-selection microbench -> the `selection` row of
        # BENCH_fleet.json (three-pass helpers vs fused kernel)
        rows.append(
            {
                "engine": "selection microbench [64,128]+[64,64]",
                "fleet_engine": "selection",
                **selection_bench(),
            }
        )
        # decision-application microbench -> the `apply` row (legacy
        # fori_loop cond-commits vs the fused assign_gather landing)
        rows.append(
            {
                "engine": "apply microbench F=64",
                "fleet_engine": "apply",
                **apply_bench(),
            }
        )
    if print_rows:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    main()
