"""Workload generation (paper §3.2.1) and trace ingestion.

"In a real setup, various users submit pipelines to the system at random
intervals." The generator materialises the *entire* arrival table up
front from a single PRNG key — a pre-pass rather than per-tick sampling —
so every engine (tick, event-skip, Python, vmap fleet) replays the exact
same deterministic workload. Per-tick sampling and a pre-materialised
arrival table are observationally equivalent for an open-loop arrival
process, and the pre-pass vectorises.

Every random quantity is "drawn from a distribution centered at one of
the user-provided (or system default) parameters" (§3.2.1):

* inter-arrival ticks   ~ Exponential(mean = waiting_ticks_mean)
* ops per pipeline      ~ 1 + Poisson(mean_ops_per_pipeline - 1), clipped
* DAG shape             ~ each op chains (new level) w.p. chain_prob else
                          joins the previous level (parallel fan-out)
* op RAM                ~ LogNormal centred at op_ram_gb_mean
* op base runtime       ~ LogNormal centred at op_base_seconds_mean
* op output dataset     ~ LogNormal centred at op_out_gb_mean, with its
                          log-domain noise correlated (out_runtime_corr)
                          with the runtime draw: long ops tend to emit
                          large intermediates (data plane, cf. Bauplan)
* CPU-scaling alpha     ~ Categorical(alpha_choices, alpha_probs)
* priority              ~ Categorical(priority_probs); interactive/query
                          pipelines are scaled shorter & smaller.

Dataset sizes are quantised to MiB granularity (multiples of 2**-10 GB)
so that every cache-occupancy sum the engines compute is exact in f32 —
the compiled and Python engines then agree bit-for-bit on cache state
regardless of reduction order.

Traces: ``load_trace`` accepts a JSON or TOML file with explicit
pipelines (``workload_from_trace_records`` the in-memory list-of-dicts
form) — the TPC-H validation benchmark and the scenario library
(``repro.core.scenarios``) use this path.  The schema is specified in
docs/trace-format.md; ``workload_to_trace_records`` is the exact
inverse, so any ``Workload`` round-trips through trace records
losslessly (bitwise, including the MiB-grid ``out_gb`` sizes), and
``workload_batch_from_traces`` ingests one trace per fleet lane for
``fleet_run(..., workloads=...)``.
"""
from __future__ import annotations

import json
import pathlib
import tomllib
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .params import SimParams
from .state import INF_TICK, Workload
from .types import Pipeline, Operator, Priority, TICKS_PER_SECOND


GB_QUANTUM = 1.0 / 1024.0  # cache sizes live on a MiB grid (see module doc)


def _quantize_gb(x: jax.Array) -> jax.Array:
    """Snap dataset sizes onto the MiB grid; exact in f32 below ~16 TB."""
    return jnp.maximum(jnp.round(x * 1024.0) / 1024.0, jnp.float32(GB_QUANTUM))


def generate_workload(params: SimParams, key: jax.Array | None = None) -> Workload:
    """Vectorised random workload table (host span
    ``eudoxia/workload/generate`` while a profiler trace runs)."""
    with TraceAnnotation("eudoxia/workload/generate"):
        return _draw_workload(params, key)


def _draw_workload(params: SimParams, key: jax.Array | None) -> Workload:
    if key is None:
        key = jax.random.PRNGKey(params.seed)
    MP, MO = params.max_pipelines, params.max_ops_per_pipeline
    k_arr, k_prio, k_nops, k_chain, k_ram, k_base, k_alpha = jax.random.split(key, 7)
    # data-plane key is folded in (not split) so the seven draws above are
    # bit-identical to the pre-data-plane generator — backward compat.
    k_out = jax.random.fold_in(key, 7)

    # --- arrivals ----------------------------------------------------------
    gaps = jax.random.exponential(k_arr, (MP,)) * params.waiting_ticks_mean
    arrival = jnp.cumsum(gaps).astype(jnp.int32)
    horizon = params.horizon_ticks
    in_horizon = arrival < horizon
    arrival = jnp.where(in_horizon, arrival, INF_TICK)

    # --- priorities --------------------------------------------------------
    pprobs = jnp.asarray(params.priority_probs, jnp.float32)
    pprobs = pprobs / jnp.sum(pprobs)
    prio = jax.random.categorical(k_prio, jnp.log(pprobs), shape=(MP,)).astype(
        jnp.int32
    )

    # --- DAG shapes ---------------------------------------------------------
    lam = max(params.mean_ops_per_pipeline - 1.0, 0.0)
    n_ops = 1 + jax.random.poisson(k_nops, lam, (MP,)).astype(jnp.int32)
    n_ops = jnp.clip(n_ops, 1, MO)
    op_idx = jnp.arange(MO, dtype=jnp.int32)[None, :]
    op_valid = op_idx < n_ops[:, None]
    chains = jax.random.bernoulli(k_chain, params.chain_prob, (MP, MO))
    chains = chains.at[:, 0].set(True)  # first op opens level 0
    op_level = jnp.cumsum(chains.astype(jnp.int32), axis=1) - 1
    op_level = jnp.where(op_valid, op_level, 0)

    # --- per-priority scale factors (interactive queries are small/short) --
    scale = jnp.asarray(
        [1.0, params.query_scale, params.interactive_scale], jnp.float32
    )[prio][:, None]

    # --- op RAM / runtime / scaling ----------------------------------------
    ram = (
        jnp.exp(jax.random.normal(k_ram, (MP, MO)) * params.op_ram_gb_sigma)
        * params.op_ram_gb_mean
        * scale
    )
    ram = jnp.maximum(ram, 0.05)
    z_base = jax.random.normal(k_base, (MP, MO))
    base_s = (
        jnp.exp(z_base * params.op_base_seconds_sigma)
        * params.op_base_seconds_mean
        * scale
    )
    base = jnp.maximum(base_s * TICKS_PER_SECOND, 1.0)

    # --- intermediate output sizes (data plane) -----------------------------
    # log-domain mix of the runtime noise and fresh noise => corr knob
    rho = float(np.clip(params.out_runtime_corr, -1.0, 1.0))
    z_out = jax.random.normal(k_out, (MP, MO))
    z_mix = rho * z_base + np.sqrt(max(1.0 - rho * rho, 0.0)) * z_out
    out = (
        jnp.exp(z_mix * params.op_out_gb_sigma)
        * params.op_out_gb_mean
        * scale
    )
    out = _quantize_gb(out)
    aprobs = jnp.asarray(params.alpha_probs, jnp.float32)
    aprobs = aprobs / jnp.sum(aprobs)
    alpha_ix = jax.random.categorical(k_alpha, jnp.log(aprobs), shape=(MP, MO))
    alpha = jnp.asarray(params.alpha_choices, jnp.float32)[alpha_ix]

    # --- fault trace (chaos layer; fold-in 8..12, see faults.py) ------------
    # generated from the SAME key, so the draws above stay bitwise-identical
    # whether faults are on or off (faults=None when every knob is 0).
    faults = None
    if params.fault_trace_active:
        from .faults import generate_fault_trace

        faults = generate_fault_trace(params, key)

    zero_f = jnp.zeros((MP, MO), jnp.float32)
    op_out = jnp.where(op_valid, out, zero_f).astype(jnp.float32)
    return Workload(
        arrival=arrival,
        prio=prio,
        n_ops=n_ops,
        op_valid=op_valid,
        op_level=op_level,
        op_ram=jnp.where(op_valid, ram, zero_f),
        op_base=jnp.where(op_valid, base, zero_f),
        op_alpha=jnp.where(op_valid, alpha, zero_f),
        op_out=op_out,
        pipe_out=jnp.sum(op_out, axis=1, dtype=jnp.float32),
        faults=faults,
    )


# ---------------------------------------------------------------------------
# Trace ingestion (paper §3.2.1: "this interface allows users to format
# existing traces and feed them into the simulator").
# ---------------------------------------------------------------------------
def workload_from_pipelines(
    pipelines: Sequence[Pipeline], params: SimParams
) -> Workload:
    MP, MO = params.max_pipelines, params.max_ops_per_pipeline
    if len(pipelines) > MP:
        raise ValueError(f"trace has {len(pipelines)} pipelines > capacity {MP}")
    arrival = np.full((MP,), INF_TICK, np.int32)
    prio = np.zeros((MP,), np.int32)
    n_ops = np.zeros((MP,), np.int32)
    op_valid = np.zeros((MP, MO), bool)
    op_level = np.zeros((MP, MO), np.int32)
    op_ram = np.zeros((MP, MO), np.float32)
    op_base = np.zeros((MP, MO), np.float32)
    op_alpha = np.zeros((MP, MO), np.float32)
    op_out = np.zeros((MP, MO), np.float32)
    for i, p in enumerate(pipelines):
        if len(p.ops) > MO:
            raise ValueError(f"pipeline {p.pid} has {len(p.ops)} ops > {MO}")
        arrival[i] = p.arrival_tick
        prio[i] = int(p.priority)
        n_ops[i] = len(p.ops)
        for j, o in enumerate(p.ops):
            op_valid[i, j] = True
            op_level[i, j] = o.level
            op_ram[i, j] = o.ram_gb
            op_base[i, j] = o.base_ticks
            op_alpha[i, j] = o.alpha
            op_out[i, j] = _op_out_gb_quantized(o.out_gb)
    return Workload(
        arrival=jnp.asarray(arrival),
        prio=jnp.asarray(prio),
        n_ops=jnp.asarray(n_ops),
        op_valid=jnp.asarray(op_valid),
        op_level=jnp.asarray(op_level),
        op_ram=jnp.asarray(op_ram),
        op_base=jnp.asarray(op_base),
        op_alpha=jnp.asarray(op_alpha),
        op_out=jnp.asarray(op_out),
        pipe_out=jnp.asarray(op_out.sum(axis=1, dtype=np.float32)),
    )


# --- record-field parsing, shared by the single-lane (Pipeline-object)
# --- and batched (array-filling) ingestion paths so both compute the
# --- exact same float32/int32 bits for every field.
def _rec_arrival_tick(rec: dict[str, Any]) -> int:
    """``arrival_tick`` (authoritative, exact) wins over ``arrival_s``.

    ``arrival_tick >= INF_TICK`` (2**31 - 1) marks a reserved slot that
    never arrives — emitted by :func:`workload_to_trace_records` so
    generated workloads round-trip bitwise (dead slots keep their drawn
    ops tables even though the simulation never admits them).
    """
    if "arrival_tick" in rec:
        return min(int(rec["arrival_tick"]), int(INF_TICK))
    # same INF clamp as the tick path: a recorded day in real seconds
    # can exceed the int32 tick range, which means "never arrives"
    return min(
        int(round(float(rec["arrival_s"]) * TICKS_PER_SECOND)), int(INF_TICK)
    )


def _rec_priority(rec: dict[str, Any]) -> Priority:
    pri = rec.get("priority", "QUERY")
    if isinstance(pri, str):
        pri = Priority[pri.upper()]
    return Priority(int(pri))


def _op_base_ticks(o: dict[str, Any]) -> float:
    """``base_ticks`` (exact f32 ticks) wins over second-resolution
    ``base_s`` — generated runtimes are fractional-tick float32 values,
    so a seconds round-trip would quantise them."""
    if "base_ticks" in o:
        return float(o["base_ticks"])
    return float(int(round(float(o["base_s"]) * TICKS_PER_SECOND)))


def _op_out_gb_quantized(out_gb: float) -> float:
    """MiB quantisation (see module doc); 0 stays 0 so data-plane-free
    traces remain inert. Exact inverse of the emitted grid values."""
    if out_gb > 0:
        return max(round(out_gb * 1024.0) / 1024.0, GB_QUANTUM)
    return 0.0


def load_trace(path: str | pathlib.Path, params: SimParams) -> Workload:
    """Load a trace file: JSON (default) or TOML (``.toml`` suffix).

    JSON traces are a list of records ``[{arrival_s, priority, ops:
    [{ram_gb, base_s, alpha, level, out_gb}]}]``; TOML traces spell the
    same records as repeated ``[[pipeline]]`` tables with nested
    ``[[pipeline.ops]]`` tables (parsed with the stdlib ``tomllib``).
    ``out_gb`` (intermediate dataset size) is optional and defaults to 0
    (data plane inert for that op). Full schema: docs/trace-format.md.
    """
    p = pathlib.Path(path)
    text = p.read_text()
    if p.suffix.lower() == ".toml":
        raw = tomllib.loads(text)
        records = raw.get("pipeline", raw.get("pipelines"))
        if records is None:
            raise ValueError(
                f"TOML trace {p} has no [[pipeline]] tables"
            )
    else:
        raw = json.loads(text)
        if isinstance(raw, dict):
            records = raw.get("pipeline", raw.get("pipelines"))
            if records is None:
                raise ValueError(
                    f"JSON trace {p} is an object without a 'pipeline(s)' "
                    "key (expected a list of records or {'pipeline': [...]})"
                )
        else:
            records = raw
    return workload_from_trace_records(records, params)


def workload_from_trace_records(
    records: Sequence[dict[str, Any]], params: SimParams
) -> Workload:
    """One trace (a sequence of pipeline records) -> a single-lane
    :class:`Workload` shaped by ``params``' capacity knobs."""
    pipelines = []
    for i, rec in enumerate(records):
        ops = [
            Operator(
                ram_gb=float(o["ram_gb"]),
                base_ticks=_op_base_ticks(o),
                alpha=float(o.get("alpha", 0.5)),
                level=int(o.get("level", j)),
                out_gb=float(o.get("out_gb", 0.0)),
            )
            for j, o in enumerate(rec["ops"])
        ]
        pipelines.append(
            Pipeline(
                pid=i,
                priority=_rec_priority(rec),
                arrival_tick=_rec_arrival_tick(rec),
                ops=ops,
            )
        )
    return workload_from_pipelines(pipelines, params)


def workload_to_trace_records(wl: Workload) -> list[dict[str, Any]]:
    """The exact inverse of trace ingestion: ``Workload`` -> records.

    Emits both the human-readable seconds fields (``arrival_s``,
    ``base_s``) and the authoritative exact fields (``arrival_tick``,
    ``base_ticks`` — fractional f32 ticks) the ingestion path prefers,
    so ``workload_from_trace_records(workload_to_trace_records(wl), p)``
    reproduces every array of ``wl`` bitwise (tests/test_traces.py
    asserts it for generated workloads and every scenario family).
    Slots whose arrival is ``INF_TICK`` but that still carry drawn ops
    (a generator's beyond-horizon slots) are emitted with
    ``arrival_tick = 2**31 - 1``; fully-empty trailing slots (ingestion
    padding) are trimmed.

    >>> from repro.core import SimParams, generate_workload
    >>> params = SimParams(max_pipelines=4, max_ops_per_pipeline=2)
    >>> recs = workload_to_trace_records(generate_workload(params))
    >>> len(recs)
    4
    >>> sorted(recs[0]) == ['arrival_s', 'arrival_tick', 'ops', 'priority']
    True
    >>> sorted(recs[0]['ops'][0]) == [
    ...     'alpha', 'base_s', 'base_ticks', 'level', 'out_gb', 'ram_gb']
    True
    """
    arrival = np.asarray(wl.arrival)
    prio = np.asarray(wl.prio)
    n_ops = np.asarray(wl.n_ops)
    op_level = np.asarray(wl.op_level)
    op_ram = np.asarray(wl.op_ram)
    op_base = np.asarray(wl.op_base)
    op_alpha = np.asarray(wl.op_alpha)
    op_out = np.asarray(wl.op_out)

    live = (arrival < INF_TICK) | (n_ops > 0) | (prio != 0)
    last = int(np.max(np.nonzero(live)[0])) if live.any() else -1
    records: list[dict[str, Any]] = []
    for i in range(last + 1):
        ops = []
        for j in range(int(n_ops[i])):
            base = float(op_base[i, j])
            ops.append(
                {
                    "ram_gb": float(op_ram[i, j]),
                    "base_s": base / TICKS_PER_SECOND,
                    "base_ticks": base,
                    "alpha": float(op_alpha[i, j]),
                    "level": int(op_level[i, j]),
                    "out_gb": float(op_out[i, j]),
                }
            )
        tick = int(arrival[i])
        records.append(
            {
                "arrival_s": tick / TICKS_PER_SECOND,
                "arrival_tick": tick,
                "priority": Priority(int(prio[i])).name,
                "ops": ops,
            }
        )
    return records


def workload_batch_from_traces(
    records_per_lane: Sequence[Sequence[dict[str, Any]]],
    params: SimParams,
) -> tuple[Workload, SimParams]:
    """Vectorised batch ingestion: one trace per fleet lane.

    Fills the whole ``[L, MP, MO]`` ops tables in a single host pass
    (no per-lane ``Pipeline`` object graphs) and returns ``(workloads,
    params)`` ready for ``fleet_run(params, workloads=workloads)``.
    Every lane is padded to the batch capacity; a padded slot is
    identical to what single-lane ingestion would produce, so lane
    ``i`` of the batch is bitwise ``workload_from_trace_records
    (records_per_lane[i], params)``.

    Capacity: ``params.max_pipelines`` / ``params.max_ops_per_pipeline``
    set to ``0`` mean "derive from the traces" (the returned params
    carry the derived values — use those for the runs); positive values
    are validated against the batch maxima.

    >>> from repro.core import SimParams
    >>> recs = [{"arrival_s": 0.0, "priority": "QUERY",
    ...          "ops": [{"ram_gb": 1.0, "base_s": 0.01, "alpha": 1.0,
    ...                   "level": 0}]}]
    >>> wls, p = workload_batch_from_traces(
    ...     [recs, recs * 3], SimParams(max_pipelines=0,
    ...                                 max_ops_per_pipeline=0))
    >>> wls.arrival.shape, (p.max_pipelines, p.max_ops_per_pipeline)
    ((2, 3), (3, 1))
    """
    lanes = [list(recs) for recs in records_per_lane]
    L = len(lanes)
    if L == 0:
        raise ValueError("records_per_lane is empty: a batch needs >= 1 lane")
    need_mp = max(1, max(len(recs) for recs in lanes))
    need_mo = max(
        1,
        max((len(r["ops"]) for recs in lanes for r in recs), default=1),
    )
    MP = params.max_pipelines if params.max_pipelines > 0 else need_mp
    MO = (
        params.max_ops_per_pipeline
        if params.max_ops_per_pipeline > 0
        else need_mo
    )
    if need_mp > MP:
        raise ValueError(
            f"a lane has {need_mp} pipelines > capacity {MP} "
            "(set max_pipelines=0 to derive it from the traces)"
        )
    if need_mo > MO:
        raise ValueError(
            f"a pipeline has {need_mo} ops > capacity {MO} "
            "(set max_ops_per_pipeline=0 to derive it from the traces)"
        )
    if (MP, MO) != (params.max_pipelines, params.max_ops_per_pipeline):
        params = params.replace(max_pipelines=MP, max_ops_per_pipeline=MO)

    arrival = np.full((L, MP), INF_TICK, np.int32)
    prio = np.zeros((L, MP), np.int32)
    n_ops = np.zeros((L, MP), np.int32)
    op_level = np.zeros((L, MP, MO), np.int32)
    op_ram = np.zeros((L, MP, MO), np.float32)
    op_base = np.zeros((L, MP, MO), np.float32)
    op_alpha = np.zeros((L, MP, MO), np.float32)
    op_out = np.zeros((L, MP, MO), np.float32)
    for lane, recs in enumerate(lanes):
        for i, rec in enumerate(recs):
            arrival[lane, i] = _rec_arrival_tick(rec)
            prio[lane, i] = int(_rec_priority(rec))
            # "ops" is required (docs/trace-format.md): a typoed key
            # must fail loudly, not ingest as zero-op pipelines
            ops = rec["ops"]
            n_ops[lane, i] = len(ops)
            for j, o in enumerate(ops):
                op_level[lane, i, j] = int(o.get("level", j))
                op_ram[lane, i, j] = float(o["ram_gb"])
                op_base[lane, i, j] = _op_base_ticks(o)
                op_alpha[lane, i, j] = float(o.get("alpha", 0.5))
                op_out[lane, i, j] = _op_out_gb_quantized(
                    float(o.get("out_gb", 0.0))
                )
    op_idx = np.arange(MO, dtype=np.int32)[None, None, :]
    return (
        Workload(
            arrival=jnp.asarray(arrival),
            prio=jnp.asarray(prio),
            n_ops=jnp.asarray(n_ops),
            op_valid=jnp.asarray(op_idx < n_ops[:, :, None]),
            op_level=jnp.asarray(op_level),
            op_ram=jnp.asarray(op_ram),
            op_base=jnp.asarray(op_base),
            op_alpha=jnp.asarray(op_alpha),
            op_out=jnp.asarray(op_out),
            pipe_out=jnp.asarray(op_out.sum(axis=-1, dtype=np.float32)),
        ),
        params,
    )


def get_workload(params: SimParams) -> Workload:
    if params.trace_path:
        return load_trace(params.trace_path, params)
    return generate_workload(params)


__all__ = [
    "generate_workload",
    "workload_from_pipelines",
    "workload_from_trace_records",
    "workload_to_trace_records",
    "workload_batch_from_traces",
    "load_trace",
    "get_workload",
]
