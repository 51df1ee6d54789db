"""Pure-jnp oracle for the fused executor state-update passes (phase 3).

After the phase-1 read fusion (``kernels/sim_tick``) and the scheduler
selection fusion (``kernels/sched_select``), the remaining hot path of
the lane-major engine was the executor's *write* side: the chain of
per-pass ``.at[].set/add`` scatters that land retirements on the
pipeline table (``_apply_retirements``) and the per-slot ``lax.cond``
commit inside ``apply_decision``'s assignment loop, which selected the
ENTIRE SimState once per slot. This module fuses both landings:

* :func:`retire_land_ref` — the retirement landing: for each pipeline,
  did one of its containers OOM / complete / time out this event, what
  is the completion tick, and the latency / priority-bucket sums.
* :func:`assign_gather_ref` — the decision landing: the per-slot
  assignment *rows* collected by the (now tiny) early-exit loop are
  landed on the container and pipeline tables in one pass, instead of
  ~20 ``.at[slot].set`` writes under a full-state ``lax.cond`` per
  slot.

The reference computes the landings as masked one-hot reductions and
selects over ``[MC, MP]`` — NOT the seed's ``.at[idx].add/max/set``
scatters, and NOT dynamic-index gathers. Both dynamic forms are slow
under the engine's per-lane ``vmap``, each on its own backend:

* on XLA:CPU a dynamic-index scatter lowers to a serialized ``while``
  thunk per scatter (~180us fixed cost each);
* on a TPU v5e a batched ``gather`` runs one output element after
  another, ~8.8 ns each: the landing's two gathers of ``[64, 16]``
  grant rows onto ``[64, 2048]`` pipeline tables took 1,153 us per
  loop trip each, 69% of the ``mc_fleet`` benchmark cell's device time.

Both landings reduce a one-hot membership: the retirement landing a
``[MC, MP]`` one over containers, the decision landing a ``[K, MC]`` /
``[K, MP]`` one over its ``K`` rows (``K`` is static), with the rows
entering whole as ``[K]`` vectors. Each lowers to fused compare, select
and reduce, with no gather. (A select-fold over the rows, reading row
``k`` at a static index, gathered nothing either; but at one lane XLA
made each row value a scalar operand of the table-wide fusion, ~35 per
landed field, and on a TPU v5e each such fusion took ~11.5 us per loop
trip.) The reductions are bitwise identical to the seed's scatters:

* int/bool scatters with unique indices == one-hot masked reductions,
  exactly;
* the decision landing sums the int32 *bits* of its values, with at
  most one row per output element, so it is exact for every dtype and
  value, ``-0.0`` included;
* the retirement landing's f32 latency terms (and the Pallas kernels'
  one-hot sums) have at most one nonzero term per output element, and
  ``x + 0.0 == x`` bitwise for every ``x != -0.0`` (allocations and
  latencies are never ``-0.0``), so those sums are fp-exact too;
* order-sensitive f32 accumulators (pool frees, cache bytes) are NOT
  landed here — the executor carries them sequentially, preserving the
  seed's left-fold association.

Property-tested in tests/test_state_update.py against the sequential
oracles (``executor.process_*`` and the ``early_exit=False`` commit
loop), with ``interpret=True`` pinning kernel == ref on CPU CI.

Shapes (unbatched | batched): retire_land: ctr_* ``[MC] | [F, MC]``,
arrival/prio ``[MP] | [F, MP]``, tick ``[] | [F]``; assign_gather:
rows ``[K] | [F, K]``. The explicit batched form (what the kernel
tiles) dispatches through ``jax.vmap`` of the per-lane landing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INF_TICK = 2**31 - 1
TICKS_PER_SECOND = 100_000  # types.TICK_SECONDS = 10 us (paper §3.2)
N_PRIO = 3


def _retire_land_1d(
    ctr_pipe, ctr_end, ctr_start, oomed, done, timed,
    arrival, prio, tick, timeout_on: bool,
):
    i32 = jnp.int32
    MP = arrival.shape[0]

    retired = oomed | done
    if timeout_on:
        timed = done & timed
        done_eff = done & ~timed
    else:
        timed = jnp.zeros_like(done)
        done_eff = done

    # the landing as one-hot reductions over [MC, MP] instead of the
    # seed's ``.at[pid].add/max`` scatters: batched scatters serialize
    # on XLA:CPU under the engine's per-lane vmap, while these lower to
    # elementwise ops + reduces. Aggregation semantics are preserved
    # bitwise — the hit counts are int sums (``> 0`` == any), ``end_of``
    # is an int max-fold — so duplicate ``ctr_pipe`` rows (several
    # containers of one pipeline retiring together) land exactly like
    # the scatters did.
    pid = jnp.where(retired, ctr_pipe, MP)
    oh = pid[:, None] == jnp.arange(MP, dtype=i32)[None, :]  # [MC, MP]
    oom_hit = jnp.any(oh & oomed[:, None], axis=0)
    done_hit = jnp.any(oh & done_eff[:, None], axis=0)
    end_of = jnp.max(
        jnp.where(
            oh & done_eff[:, None], ctr_end[:, None], jnp.int32(0)
        ),
        axis=0,
        initial=0,
    )
    if timeout_on:
        timed_hit = jnp.any(oh & timed[:, None], axis=0)
        timed_wasted = jnp.sum(jnp.where(timed, tick - ctr_start, 0)).astype(
            i32
        )
    else:
        timed_hit = jnp.zeros_like(done_hit)
        timed_wasted = jnp.zeros((), i32)

    lat_s = (end_of - arrival).astype(jnp.float32) / TICKS_PER_SECOND
    lat_s = jnp.where(done_hit, lat_s, 0.0)
    prio_oh = prio[None, :] == jnp.arange(N_PRIO, dtype=i32)[:, None]
    lat_sum = jnp.sum(lat_s)
    lat_prio = jnp.sum(jnp.where(prio_oh, lat_s[None, :], 0.0), axis=-1)
    done_prio = jnp.sum(prio_oh & done_hit[None, :], axis=-1).astype(i32)
    n_done = jnp.sum(done_hit).astype(i32)
    n_oom = jnp.sum(oom_hit).astype(i32)
    return (
        oom_hit, done_hit, timed_hit, end_of, timed_wasted,
        lat_sum, lat_prio, done_prio, n_done, n_oom,
    )


@functools.partial(jax.jit, static_argnames=("timeout_on",))
def retire_land_ref(
    ctr_pipe, ctr_end, ctr_start, oomed, done, timed,
    arrival, prio, tick, *, timeout_on: bool = False,
):
    """Land container retirements on the pipeline axis.

    ``timed`` (``done & ctr_timed``: the deadline kills) is consumed
    only when ``timeout_on``; pass any placeholder (e.g. ``done``)
    otherwise. Returns ``(oom_hit, done_hit, timed_hit, end_of,
    timed_wasted, lat_sum, lat_prio, done_prio, n_done, n_oom)`` —
    ``timed_hit``/``timed_wasted`` are zeros when ``timeout_on`` is
    False.
    """
    fn = functools.partial(_retire_land_1d, timeout_on=timeout_on)
    if ctr_pipe.ndim == 2:
        return jax.vmap(
            lambda cp, ce, cs, o, d, td, a, p, t: fn(
                cp, ce, cs, o, d, td, a, p, t
            )
        )(ctr_pipe, ctr_end, ctr_start, oomed, done, timed, arrival, prio,
          tick)
    return fn(ctr_pipe, ctr_end, ctr_start, oomed, done, timed, arrival,
              prio, tick)


def _onehot_land(idx, n: int, fields):
    """Land ``fields[f][k]`` at position ``idx[k]`` of ``n`` outputs.

    A masked one-hot reduction over the ``K`` rows: ``hit`` marks the
    positions some row names; ``outs[f]`` holds that row's value there
    and zero elsewhere. With at most one row per position the int32 sum
    of the values' bits is exact for every dtype and value (``-0.0``
    and NaN payloads included). The rows enter whole, as ``[K]``
    vectors: no read at a traced index (under ``vmap`` a batched
    ``gather``) and no row sliced out to a scalar operand, and XLA fuses
    compare, select and reduce into one pass per field.
    """
    m = idx[:, None] == jnp.arange(n, dtype=idx.dtype)[None, :]  # [K, n]
    outs = []
    for x in fields:
        if x.dtype == jnp.bool_:
            outs.append(jnp.any(m & x[:, None], axis=0))
        else:
            bits = jax.lax.bitcast_convert_type(x, jnp.int32)
            s = jnp.sum(jnp.where(m, bits[:, None], 0), axis=0,
                        dtype=jnp.int32)
            outs.append(jax.lax.bitcast_convert_type(s, x.dtype))
    return jnp.any(m, axis=0), outs


def _assign_gather_1d(
    valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed,
    max_containers: int, max_pipelines: int,
):
    i32, f32 = jnp.int32, jnp.float32
    # valid rows carry unique slots/pipes (the loop consumes each empty
    # slot and waiting pipeline it assigns), so every output element has
    # at most one contributing row, whose bits land unchanged.
    # Invalid rows point one past the end and land nowhere.
    sv = jnp.where(valid, slot, max_containers)
    pv = jnp.where(valid, pipe, max_pipelines)
    hit_c, (l_pipe, l_pool, l_cpus, l_ram, l_end, l_oom, l_prio, l_warm,
            l_timed) = _onehot_land(sv, max_containers, (
                pipe.astype(i32), pool.astype(i32), cpus.astype(f32),
                ram.astype(f32), end.astype(i32), oom.astype(i32),
                prio.astype(i32), warm.astype(bool), timed.astype(bool)))
    hit_p, (l_pcpus, l_pram) = _onehot_land(
        pv, max_pipelines, (cpus.astype(f32), ram.astype(f32)))
    return (
        hit_c, l_pipe, l_pool, l_cpus, l_ram, l_end, l_oom, l_prio,
        l_warm, l_timed, hit_p, l_pcpus, l_pram,
    )


@functools.partial(
    jax.jit, static_argnames=("max_containers", "max_pipelines")
)
def assign_gather_ref(
    valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed,
    *, max_containers: int, max_pipelines: int,
):
    """Land the collected assignment rows on the container/pipeline axes.

    Rows (``[.., K]``) come from the executor's early-exit loop; valid
    rows carry unique ``slot``/``pipe`` indices (the loop consumes each
    empty slot and waiting pipeline it assigns), so every output element
    has at most one contributing row. Each axis is a one-hot reduction
    over the ``K`` rows (:func:`_onehot_land`): no gather, exact for
    every dtype and value.

    Returns ``(hit_c, l_pipe, l_pool, l_cpus, l_ram, l_end, l_oom,
    l_prio, l_warm, l_timed, hit_p, l_pcpus, l_pram)``: the container-
    axis landing (``hit_c`` [.., MC] plus the per-slot values) and the
    pipeline-axis landing (``hit_p`` [.., MP] plus the last-allocation
    values); the executor applies them with one ``where`` per field.
    """
    fn = functools.partial(
        _assign_gather_1d,
        max_containers=max_containers,
        max_pipelines=max_pipelines,
    )
    args = (valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed)
    if valid.ndim == 2:
        return jax.vmap(lambda *a: fn(*a))(*args)
    return fn(*args)
