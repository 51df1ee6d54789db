"""Dispatch wrappers for the fused state-update landings (Pallas phase 3).

Same convention as ``sim_tick`` and ``sched_select`` (documented once
in docs/architecture.md §"Kernel subsystems"): ``impl="auto"`` picks
the Pallas kernel on TPU for explicit lane-major 2-D batches and the
bitwise-equivalent jnp reference everywhere else. The per-lane form —
what the executor traces under the engine's ``vmap`` — always lowers
through the reference: under vmap its one-hot reductions batch into
exactly the shapes the kernel tiles, so the hot path is identical
maths either way and the vmapped while_loop stays free of pallas
batching constraints. The sequential seed passes remain exported as
the property-tested oracles (``executor.process_*`` and the
``early_exit=False`` commit loop).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import use_pallas
from .kernel import assign_gather_kernel, retire_land_kernel
from .ref import assign_gather_ref, retire_land_ref


def retire_land(
    ctr_pipe, ctr_end, ctr_start, oomed, done, timed, arrival, prio, tick,
    *, timeout_on: bool = False, impl: str = "auto", interpret: bool = False,
):
    """Fused retirement landing: per-pipeline OOM/done/timeout hit
    masks, completion ticks, and the latency/priority reductions, in
    one masked one-hot pass (see ``ref.retire_land_ref`` for the
    bitwise contract vs ``executor._apply_retirements``).

    ``timed`` may be ``None`` when ``timeout_on`` is False.
    """
    if timed is None:
        timed = jnp.zeros_like(done)
    if use_pallas(impl, batched=ctr_pipe.ndim == 2):
        return retire_land_kernel(
            ctr_pipe, ctr_end, ctr_start, oomed, done, timed, arrival,
            prio, tick, timeout_on=timeout_on, interpret=interpret,
        )
    return retire_land_ref(
        ctr_pipe, ctr_end, ctr_start, oomed, done, timed, arrival, prio,
        tick, timeout_on=timeout_on,
    )


def assign_gather(
    valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed,
    *, max_containers: int, max_pipelines: int, impl: str = "auto",
    interpret: bool = False,
):
    """Fused decision landing: land the collected assignment rows on
    the container/pipeline axes as one-hot reductions over the rows,
    with no gather (see ``ref.assign_gather_ref`` for the bitwise
    contract vs the per-slot ``lax.cond`` commits of
    ``apply_decision``)."""
    if use_pallas(impl, batched=valid.ndim == 2):
        return assign_gather_kernel(
            valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm,
            timed, max_containers=max_containers,
            max_pipelines=max_pipelines, interpret=interpret,
        )
    return assign_gather_ref(
        valid, slot, pipe, pool, cpus, ram, end, oom, prio, warm, timed,
        max_containers=max_containers, max_pipelines=max_pipelines,
    )


__all__ = ["retire_land", "assign_gather"]
