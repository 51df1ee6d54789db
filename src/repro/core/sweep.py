"""Simulation fleets: the device-scale payoff of the lane-major core.

The paper pitches Eudoxia as "a cheap mechanism for developers to
evaluate different scheduling algorithms against their infrastructure".
Because the whole simulator is one lane-major XLA program
(``engine._fleet_compiled``), *cheap* becomes *massively parallel*:

* a fleet of seeds is just more lanes in the batch axis — Monte-Carlo
  policy evaluation in a single compiled program (and with
  ``workloads=`` the lanes are recorded traces or scenario-library
  batches instead: replay yesterday's production day under four
  candidate policies in one call), and
* ``fleet_run(..., shard="auto")`` splits the fleet axis across every
  local device with ``shard_map``: each device runs the engine's shared
  while_loop on its own lanes and exits when *its* lanes drain, with no
  cross-device synchronisation at all (there are no collectives in the
  engine). Before sharding, lanes are *binned by event density*
  (``bin_lanes_by_density``): sorted by predicted event count so each
  device gets a contiguous block of similar drain time — the slow lanes
  share one device instead of dragging every device's max-over-lanes
  loops. Lanes are padded to a device multiple inside this module, and
  both the padding and the binning permutation are undone before
  returning.

``fleet_run`` is also what the serving layer uses to pick an admission /
preemption policy before it touches the real cluster (DESIGN.md §4).
"""
from __future__ import annotations

import functools
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.profiler import TraceAnnotation

from . import metrics
from .engine import _fleet_compiled, _quiet_partial_donation, scope_map
from .params import SimParams
from .state import INF_TICK, SimState, Workload
from .types import TICKS_PER_SECOND
from .workload import generate_workload, workload_batch_from_traces  # noqa: F401  (re-export: batch ingestion pairs with fleet_run)


def make_workload_batch(params: SimParams, seeds: Sequence[int]) -> Workload:
    """One seed-generated workload per fleet lane, built in one vmap.

    The key derivation is vmapped too (no per-seed Python round-trip),
    so fleets in the thousands construct host-loop-free; lane ``i`` is
    bitwise ``generate_workload(params, PRNGKey(seeds[i]))``.

    >>> from repro.core import SimParams, make_workload_batch
    >>> p = SimParams(max_pipelines=8, max_ops_per_pipeline=4)
    >>> batch = make_workload_batch(p, seeds=[0, 1, 2])
    >>> batch.arrival.shape, batch.op_ram.shape
    ((3, 8), (3, 8, 4))
    """
    with TraceAnnotation("eudoxia/workload/batch"):
        keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.uint32))
        return jax.vmap(lambda k: generate_workload(params, k))(keys)


def pad_lanes(wls: Workload, n_lanes: int) -> Workload:
    """Pad the fleet axis of ``wls`` up to ``n_lanes``.

    Padding lanes replicate lane 0's shapes but have every arrival at
    INF_TICK, so the engine retires them in a single event (no arrivals
    -> the first next-event jump lands on the horizon) — they cost one
    loop iteration, not a simulation.

    >>> import numpy as np
    >>> from repro.core import SimParams, make_workload_batch
    >>> from repro.core.sweep import pad_lanes
    >>> from repro.core.state import INF_TICK
    >>> p = SimParams(max_pipelines=8, max_ops_per_pipeline=4)
    >>> padded = pad_lanes(make_workload_batch(p, [0, 1]), 4)
    >>> padded.arrival.shape
    (4, 8)
    >>> bool((np.asarray(padded.arrival)[2:] == INF_TICK).all())
    True
    """
    F = wls.arrival.shape[0]
    pad = n_lanes - F
    if pad <= 0:
        return wls

    def pad_leaf(x):
        fill = jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])
        return jnp.concatenate([x, fill], axis=0)

    padded = jax.tree.map(pad_leaf, wls)
    padded = padded._replace(
        arrival=padded.arrival.at[F:].set(INF_TICK)
    )
    if padded.faults is not None:
        # padding lanes must stay single-event: a replicated fault trace
        # would wake them for every crash/outage of lane 0
        padded = padded._replace(
            faults=padded.faults._replace(
                crash_time=padded.faults.crash_time.at[F:].set(INF_TICK),
                outage_start=padded.faults.outage_start.at[F:].set(INF_TICK),
            )
        )
    return padded


def attach_policies(wls: Workload, policies) -> Workload:
    """Attach :class:`~repro.core.policy.PolicyParams` vectors to a
    workload batch for the dynamic ``"policy"`` scheduler family.

    ``policies`` is ``[F, P]`` (one policy per lane), a single ``[P]``
    vector broadcast to every lane, or a ``PolicyParams`` /sequence of
    them. The vectors ride the workload pytree, so ``pad_lanes``,
    ``bin_lanes_by_density`` and device sharding treat them like any
    other per-lane leaf — lane ``i`` always simulates under policy
    ``i``, whatever the binning or sharding.

    >>> import numpy as np
    >>> from repro.core import SimParams, make_workload_batch
    >>> from repro.core.policy import DEFAULT_POINTS
    >>> from repro.core.sweep import attach_policies
    >>> p = SimParams(max_pipelines=8, max_ops_per_pipeline=4)
    >>> wls = attach_policies(make_workload_batch(p, [0, 1]),
    ...                       DEFAULT_POINTS["sjf"])
    >>> wls.policy.shape
    (2, 15)
    """
    from .policy import N_POLICY_PARAMS, PolicyParams

    if isinstance(policies, PolicyParams):
        policies = policies.to_vector()
    elif isinstance(policies, (list, tuple)) and policies and isinstance(
        policies[0], PolicyParams
    ):
        policies = np.stack([p.to_vector() for p in policies])
    pol = jnp.asarray(policies, jnp.float32)
    F = wls.arrival.shape[0]
    if pol.ndim == 1:
        pol = jnp.broadcast_to(pol, (F, pol.shape[0]))
    if pol.shape != (F, N_POLICY_PARAMS):
        raise ValueError(
            f"policies must be [{F}, {N_POLICY_PARAMS}] (one PolicyParams "
            f"vector per lane) or a single [{N_POLICY_PARAMS}] vector, "
            f"got {pol.shape}"
        )
    return wls._replace(policy=pol)


def policy_grid_workloads(
    wls: Workload, policies
) -> tuple[Workload, int, int]:
    """Tile a scenario batch across a policy grid on the fleet axis.

    ``wls`` is an ``[S, ...]`` scenario batch (e.g. from
    ``scenario_fleet``), ``policies`` a ``[C, P]`` candidate grid (or a
    sequence of ``PolicyParams``). Returns ``(grid_wls, C, S)`` where
    ``grid_wls`` is the ``[C*S, ...]`` batch whose lane ``c*S + s``
    runs scenario ``s`` under candidate ``c`` — one ``fleet_run`` with
    ``scheduler_key="policy"`` evaluates the whole grid, sharded and
    lane-binned like any other fleet.

    >>> import numpy as np
    >>> from repro.core import SimParams, make_workload_batch
    >>> from repro.core.policy import DEFAULT_POINTS
    >>> from repro.core.sweep import policy_grid_workloads
    >>> p = SimParams(max_pipelines=8, max_ops_per_pipeline=4)
    >>> grid, C, S = policy_grid_workloads(
    ...     make_workload_batch(p, [0, 1, 2]),
    ...     [DEFAULT_POINTS["priority"], DEFAULT_POINTS["sjf"]])
    >>> grid.arrival.shape, (C, S)
    ((6, 8), (2, 3))
    >>> grid.policy.shape
    (6, 15)
    """
    from .policy import N_POLICY_PARAMS, PolicyParams

    if isinstance(policies, (list, tuple)) and policies and isinstance(
        policies[0], PolicyParams
    ):
        policies = np.stack([p.to_vector() for p in policies])
    pol = jnp.asarray(policies, jnp.float32)
    if pol.ndim != 2 or pol.shape[1] != N_POLICY_PARAMS:
        raise ValueError(
            f"policies must be a [C, {N_POLICY_PARAMS}] grid, got "
            f"{pol.shape}"
        )
    if wls.policy is not None:
        raise ValueError(
            "scenario batch already carries policy vectors; build the "
            "grid from a policy-free batch"
        )
    C = int(pol.shape[0])
    S = int(wls.arrival.shape[0])
    tiled = jax.tree.map(
        lambda x: jnp.tile(x, (C,) + (1,) * (x.ndim - 1)), wls
    )
    return tiled._replace(policy=jnp.repeat(pol, S, axis=0)), C, S


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "scheduler_key", "impl", "n_shards", "trace_capacity"
    ),
    donate_argnames=("workloads",),
)
def _fleet_sharded(
    params: SimParams,
    workloads: Workload,  # [F, ...] with F a multiple of n_shards
    scheduler_key: str,
    impl: str,
    n_shards: int,
    trace_capacity: int = 0,
):
    """shard_map the lane-major core over the fleet axis of a 1-D local
    device mesh. Each shard is an independent run of the same engine on
    F/n_shards lanes; per-lane results are bitwise those of the
    unsharded call (tests/test_fleet.py asserts it lane-for-lane).
    ``workloads`` is donated, as in ``engine._fleet_compiled``. With a
    positive (static) ``trace_capacity``, each shard also records its
    lanes' trace buffers and the return is ``(states, tbufs)``, both
    fleet-sharded."""
    mesh = jax.sharding.Mesh(
        np.asarray(jax.local_devices()[:n_shards]), ("fleet",)
    )
    spec = jax.sharding.PartitionSpec("fleet")

    def shard_fn(wls):
        out = _fleet_compiled(
            params, wls, scheduler_key, impl, trace_capacity=trace_capacity
        )
        if trace_capacity:
            states, _, tbufs = out
            return states, tbufs
        states, _ = out
        return states

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
        check_vma=False,
    )(workloads)


def predicted_lane_events(wls: Workload, params: SimParams) -> np.ndarray:
    """Per-lane predicted event count, the lane-binning sort key.

    The engine's work per lane is proportional to its event count, and
    (absent preemption storms) events are dominated by arrivals: each
    arrival inside the horizon admits once and retires once. The count
    of realised arrivals IS the lane's realised arrival density — the
    per-lane draw of the ``waiting_ticks_mean``-controlled arrival
    process — so it predicts drain time without running anything.
    """
    horizon = params.horizon_ticks
    return np.asarray(jnp.sum(wls.arrival < horizon, axis=-1))


def bin_lanes_by_density(
    wls: Workload, params: SimParams
) -> tuple[Workload, np.ndarray]:
    """Sort the fleet axis by predicted event count, heaviest first.

    Returns ``(sorted_wls, inverse_permutation)``. Device-sharding the
    *sorted* fleet gives each device a contiguous block of
    similar-drain-time lanes, so the per-device shared while_loop (and
    every early-exit scheduler loop inside it, whose vmapped trip count
    is the max over that device's lanes) stops as its own block drains
    instead of every device paying the global tail. The sort is stable,
    so equal-density lanes keep their order; padding lanes (appended
    after binning) are the lightest and land on the last device.

    >>> from repro.core import SimParams, make_workload_batch
    >>> from repro.core.sweep import bin_lanes_by_density
    >>> p = SimParams(max_pipelines=8, max_ops_per_pipeline=4)
    >>> sorted_wls, inv = bin_lanes_by_density(
    ...     make_workload_batch(p, [0, 1, 2]), p)
    >>> sorted_wls.arrival.shape, inv.shape
    ((3, 8), (3,))
    """
    score = predicted_lane_events(wls, params)
    order = np.argsort(-score, kind="stable")
    inv = np.argsort(order)
    return jax.tree.map(lambda x: x[order], wls), inv


@functools.partial(jax.jit, donate_argnames=("states",))
def _unbin_states(states: SimState, inv):
    """Undo the binning permutation (and drop padding lanes: ``inv``
    only addresses real lanes, which binning sorted ahead of the
    padding) in ONE compiled gather. Doing this eagerly — one host
    gather per SimState field on device-sharded arrays — costs more
    than the binning saves; compiled, it is a single fused reshard.
    ``states`` is donated: the binned-order copy dies here."""
    return jax.tree.map(lambda x: x[inv], states)


def _resolve_shards(shard, fleet_size: int) -> int:
    if shard is None:
        return 1
    n_dev = jax.local_device_count()
    n = n_dev if shard == "auto" else int(shard)
    if n > n_dev:
        raise ValueError(
            f"shard={shard!r} asks for {n} devices but only {n_dev} are "
            "local (hint for CPU testing: "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4)"
        )
    return max(1, min(n, fleet_size))


def fleet_run(
    params: SimParams,
    seeds: Sequence[int] | None = None,
    scheduler_key: str | None = None,
    *,
    workloads: Workload | None = None,
    shard: str | int | None = None,
    impl: str = "auto",
    bin_lanes: bool = True,
    fleet_engine: str | None = None,
    trace: bool = False,
    trace_capacity: int | None = None,
) -> SimState:
    """Run a fleet of simulations in parallel on the lane-major core.

    The fleet is either ``len(seeds)`` seed-generated lanes (Monte-Carlo
    policy evaluation) or, with ``workloads=``, a caller-built batch —
    e.g. one recorded trace per lane via ``workload_batch_from_traces``
    or the scenario library (``repro.core.scenarios``). Exactly one of
    ``seeds`` / ``workloads`` must be given; a ``workloads`` batch is
    treated as CONSUMED (it is donated to the compiled core — rebuild
    it if you need the arrays afterwards).

    ``shard=None`` (default) keeps the whole fleet on one device;
    ``shard="auto"`` splits the fleet axis across all local devices with
    ``shard_map`` (``shard=n`` for the first n). Lane padding to a
    device multiple is handled here and stripped from the result.
    Returns the batched final SimState (leading axis = fleet member),
    per-lane bitwise-identical whatever the sharding.

    ``bin_lanes`` (sharded runs only) sorts the fleet axis by predicted
    event count before sharding — each device gets lanes of similar
    drain time, cutting the tail iterations every max-over-lanes loop
    pays — and unpermutes the result, so lane ``i`` of the output is
    lane ``i`` of the input bitwise whatever the binning (lanes are
    independent; tests/test_sched_select.py asserts on-vs-off
    equality).

    ``fleet_engine`` is deprecated: the fused lane-major engine is the
    only simulation core (the legacy ``"vmap"`` path was deleted).

    ``trace=True`` records an on-device event trace per lane (capacity
    ``trace_capacity`` records each, default
    ``telemetry.DEFAULT_TRACE_CAPACITY``) and returns
    ``(states, traces)`` with ``traces[i]`` the lane-``i``
    :class:`repro.core.telemetry.TraceEvents`; per-lane states stay
    bitwise-identical to an untraced run, whatever the sharding or
    binning (traces ride the same unbinning permutation as the states).

    While a ``jax.profiler`` trace runs, host spans mark the parts of
    the call: ``eudoxia/fleet/prepare``, ``/bin``, ``/dispatch``,
    ``/unbin`` and ``/decode``, the last three only on the paths that
    run them (docs/observability.md).

    >>> from repro.core import SimParams, fleet_run, fleet_summary
    >>> p = SimParams(duration=0.01, max_pipelines=8, max_containers=8,
    ...               max_ops_per_pipeline=4, waiting_ticks_mean=300.0,
    ...               op_base_seconds_mean=0.002)
    >>> states = fleet_run(p, seeds=[0, 1])
    >>> int(states.done_count.shape[0])
    2
    >>> sorted(fleet_summary(states, p))[:2]
    ['admitted_fraction_mean', 'admitted_mean']
    """
    with TraceAnnotation("eudoxia/fleet/prepare"):
        scheduler_key, capacity, wls = _prepare_fleet(
            params, seeds, scheduler_key, workloads, fleet_engine, trace,
            trace_capacity,
        )
        F = wls.arrival.shape[0]
        n_shards = _resolve_shards(shard, F)
    if n_shards <= 1:
        with TraceAnnotation("eudoxia/fleet/dispatch"), _quiet_partial_donation():
            out = _fleet_compiled(
                params, wls, scheduler_key, impl, trace_capacity=capacity
            )
        if capacity:
            states, _, tbufs = out
            with TraceAnnotation("eudoxia/fleet/decode"):
                return states, _decode_traces(tbufs)
        states, _ = out
        return states
    inv = None
    if bin_lanes:
        with TraceAnnotation("eudoxia/fleet/bin"):
            wls, inv = bin_lanes_by_density(wls, params)
    F_pad = -(-F // n_shards) * n_shards
    with TraceAnnotation("eudoxia/fleet/dispatch"), _quiet_partial_donation():
        out = _fleet_sharded(
            params, pad_lanes(wls, F_pad), scheduler_key, impl, n_shards,
            trace_capacity=capacity,
        )
    states, tbufs = out if capacity else (out, None)
    if inv is not None or F_pad != F:
        with TraceAnnotation("eudoxia/fleet/unbin"):
            states, tbufs = _unbin(states, tbufs, inv, F)
    if capacity:
        with TraceAnnotation("eudoxia/fleet/decode"):
            return states, _decode_traces(tbufs)
    return states


def _prepare_fleet(
    params, seeds, scheduler_key, workloads, fleet_engine, trace,
    trace_capacity,
):
    """``fleet_run``'s checks and its lanes: the scheduler key, the
    static trace capacity (0: telemetry off) and the workload batch."""
    if (seeds is None) == (workloads is None):
        raise ValueError(
            "fleet_run needs exactly one of seeds= (generated lanes) or "
            "workloads= (a trace/scenario batch)"
        )
    if fleet_engine is not None:
        warnings.warn(
            "fleet_engine is deprecated and ignored unless it names the "
            "removed path: the fused lane-major engine is the only core",
            DeprecationWarning,
            stacklevel=3,
        )
        if fleet_engine != "fused":
            raise ValueError(
                f"fleet_engine={fleet_engine!r} was removed in the "
                "lane-major unification; the fused engine is the only path"
            )
    scheduler_key = scheduler_key or params.scheduling_algo
    if workloads is not None:
        # catch the returned-params footgun early: a batch built with
        # derived capacities must run with the params that carry them,
        # or the schedulers' [MP]-shaped masks break deep inside jit
        if workloads.arrival.ndim != 2:
            raise ValueError(
                f"workloads must be a BATCH (arrival [F, MP]), got "
                f"arrival shape {workloads.arrival.shape}; wrap a single "
                "trace with workload_batch_from_traces([records], params)"
            )
        got = (workloads.arrival.shape[-1], workloads.op_valid.shape[-1])
        want = (params.max_pipelines, params.max_ops_per_pipeline)
        if got != want:
            raise ValueError(
                f"workloads batch is shaped {got} "
                "(max_pipelines, max_ops_per_pipeline) but params say "
                f"{want}; run with the params returned by "
                "workload_batch_from_traces / scenario_fleet"
            )
    capacity = 0
    if trace:
        from .telemetry.schema import DEFAULT_TRACE_CAPACITY

        capacity = int(
            DEFAULT_TRACE_CAPACITY if trace_capacity is None else trace_capacity
        )
        if capacity <= 0:
            raise ValueError(
                f"trace_capacity must be positive, got {trace_capacity}"
            )
    wls = workloads if seeds is None else make_workload_batch(params, seeds)
    if scheduler_key.replace("-", "_").lower() == "policy" and wls.policy is None:
        raise ValueError(
            "scheduler 'policy' needs per-lane PolicyParams vectors on "
            "the workload batch; attach them with attach_policies(wls, "
            "policies) or build a grid with policy_grid_workloads"
        )
    if params.fault_trace_active and wls.faults is None:
        # trace/scenario batches carry no fault traces of their own;
        # derive the per-lane chaos schedule from params.seed so replays
        # under fault injection stay reproducible (docs/faults.md)
        from .faults import attach_fault_traces

        wls = attach_fault_traces(wls, params)
    return scheduler_key, capacity, wls


def _unbin(states, tbufs, inv, F: int):
    """A sharded call's lanes back in the caller's order, padding
    dropped; ``inv`` is the binning's inverse permutation, or None when
    the lanes were not binned."""
    if inv is not None:
        # one gather: unpermute AND strip padding (inv addresses only
        # real lanes; binning put the padding last). Trace buffers join
        # the states in one pytree so they ride the same permutation.
        inv = jnp.asarray(inv)
        if tbufs is not None:
            return _unbin_states((states, tbufs), inv)
        return _unbin_states(states, inv), None
    states = jax.tree.map(lambda x: x[:F], states)
    if tbufs is not None:
        tbufs = jax.tree.map(lambda x: x[:F], tbufs)
    return states, tbufs


def compiled_scope_map(
    params: SimParams,
    workloads: Workload,
    scheduler_key: str | None = None,
    *,
    shard: str | int | None = None,
    impl: str = "auto",
    trace_capacity: int = 0,
) -> dict[str, str]:
    """``{instruction name: engine scope}`` (``engine.scope_map``) of
    the program that ``fleet_run(params, workloads=workloads,
    scheduler_key=..., shard=..., impl=...)`` runs: ``_fleet_compiled``,
    or ``_fleet_sharded`` when the call shards. ``run(params)`` runs the
    unsharded program of a one-lane batch. ``trace_capacity`` is the
    static capacity the call compiled (0 with telemetry off).

    Only the shapes of ``workloads`` are read (arrays or
    ``jax.ShapeDtypeStruct``\\ s); the map comes from the same jit with
    the same static arguments, lowered and compiled ahead of time, which
    a warm compilation cache answers without compiling.
    """
    scheduler_key = scheduler_key or params.scheduling_algo
    F = workloads.arrival.shape[0]
    n_shards = _resolve_shards(shard, F)
    F_pad = -(-F // n_shards) * n_shards
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((F_pad,) + x.shape[1:], x.dtype),
        workloads,
    )
    with _quiet_partial_donation():
        if n_shards <= 1:
            lowered = _fleet_compiled.lower(
                params, shapes, scheduler_key, impl,
                trace_capacity=trace_capacity,
            )
        else:
            lowered = _fleet_sharded.lower(
                params, shapes, scheduler_key, impl, n_shards,
                trace_capacity=trace_capacity,
            )
    return scope_map(lowered.compile().as_text())


def _decode_traces(tbufs):
    import numpy as np

    from .telemetry.decode import decode_fleet

    # only ship the populated prefix to the host: slice the device-side
    # tables to the fleet's max count, rounded up to a power of two so
    # the slice shapes (and their compiled executables) stay cached
    counts = np.asarray(tbufs.count)
    cap = int(tbufs.records.shape[1])
    hi = int(counts.max(initial=0))
    keep = min(cap, 1 << max(hi - 1, 0).bit_length()) if hi else 0
    if keep < cap:
        tbufs = tbufs._replace(records=tbufs.records[:, :keep])
    return decode_fleet(tbufs, capacity=cap)


def fleet_summary(states: SimState, params: SimParams, traces=None) -> dict:
    """Aggregate fleet statistics (mean/std across fleet members).

    ``traces`` (the list returned by ``fleet_run(..., trace=True)``) is
    optional; when given, the summary also reports the fleet-total
    recorder overflow counter ``events_dropped_total``.
    """
    done = np.asarray(states.done_count)
    lat = np.asarray(states.sum_latency_s) / np.maximum(done, 1)
    util = np.asarray(states.util_cpu_s).sum(-1) / (
        params.total_cpus * params.duration
    )
    out = {
        "fleet_size": int(done.shape[0]),
        "throughput_per_s_mean": float(done.mean() / params.duration),
        "throughput_per_s_std": float(done.std() / params.duration),
        "mean_latency_s_mean": float(lat.mean()),
        "mean_latency_s_std": float(lat.std()),
        "cpu_utilization_mean": float(util.mean()),
        "oom_events_mean": float(np.asarray(states.oom_events).mean()),
        "preempt_events_mean": float(np.asarray(states.preempt_events).mean()),
        "cost_dollars_mean": float(np.asarray(states.cost_dollars).mean()),
        # ---- data plane (fleet means) -------------------------------------
        "cache_hit_gb_mean": float(np.asarray(states.cache_hit_gb).mean()),
        "bytes_moved_gb_mean": float(
            np.asarray(states.bytes_moved_gb).mean()
        ),
        "cache_hit_rate_mean": _fleet_hit_rate(states),
        "cold_starts_mean": float(np.asarray(states.cold_starts).mean()),
        "warm_starts_mean": float(np.asarray(states.warm_starts).mean()),
        # ---- chaos layer (fleet means, zero when faults are off) ----------
        "crash_events_mean": float(np.asarray(states.crash_events).mean()),
        "outage_events_mean": float(np.asarray(states.outage_events).mean()),
        "fault_kills_mean": float(np.asarray(states.fault_kills).mean()),
        "timeouts_mean": float(np.asarray(states.timeout_events).mean()),
        "retries_mean": float(np.asarray(states.retry_events).mean()),
        "failed_mean": float(np.asarray(states.failed_count).mean()),
        "wasted_work_s_mean": float(
            np.asarray(states.wasted_ticks).mean() / TICKS_PER_SECOND
        ),
        "pool_down_s_mean": float(np.asarray(states.pool_down_s).mean()),
    }
    # ---- closed loop / overload (fleet means, zero when the loop is off) --
    offered = np.asarray(states.offered_total, dtype=np.float64)
    admitted = np.asarray(states.admitted_total, dtype=np.float64)
    out.update(
        {
            "offered_mean": float(offered.mean()),
            "admitted_mean": float(admitted.mean()),
            "shed_mean": float(np.asarray(states.shed_total).mean()),
            "deferred_mean": float(np.asarray(states.deferred_total).mean()),
            "client_retries_mean": float(
                np.asarray(states.client_retry_events).mean()
            ),
            "admitted_fraction_mean": float(
                (admitted[offered > 0] / offered[offered > 0]).mean()
            )
            if np.any(offered > 0)
            else float("nan"),
            # Jain's index over per-lane completed work: how evenly the
            # fleet's lanes were served (docs/closed-loop.md)
            "fairness_jain_done": metrics._jain(done),
        }
    )
    if traces is not None:
        out["events_dropped_total"] = int(
            sum(t.events_dropped for t in traces)
        )
    return out


def _fleet_hit_rate(states: SimState) -> float:
    hit = np.asarray(states.cache_hit_gb, dtype=np.float64)
    moved = np.asarray(states.bytes_moved_gb, dtype=np.float64)
    total = hit + moved
    rates = np.where(total > 0, hit / np.maximum(total, 1e-12), 0.0)
    return float(rates.mean())


__all__ = [
    "attach_policies",
    "compiled_scope_map",
    "fleet_run",
    "fleet_summary",
    "make_workload_batch",
    "policy_grid_workloads",
    "workload_batch_from_traces",
    "pad_lanes",
    "bin_lanes_by_density",
    "predicted_lane_events",
    "_fleet_compiled",
]
