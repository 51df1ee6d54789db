"""The traffic generator's library: the seeds, counts and checks that
every kind of call shares.

A traffic mix file (``traffic/<mix>.json``) names a ``kind`` and its
parameters. The kind is a module of its own, ``kinds/<kind>.py``, found
by that name (``cell.py``); it says how one call is built and issued
and defines ``Traffic(params, traffic, seed)``. The kinds here:

- ``fleet``: ``fleet_run`` of ``lanes_per_call`` seed lanes, built with
  ``make_workload_batch`` (bitwise what ``fleet_run(seeds=)`` builds);
- ``single``: one lane per call through ``run(params, workload=...)``.

Both draw a window's lanes from a fixed pool of ``pool_lanes`` workload
seeds, cut into groups of one call each: ``--seed n`` chooses the order
of the groups (a new order each time the pool is used up) and the order
of lanes inside a call, and the window runs whole passes over the pool,
so that every seed does the same work in another order. The warm-up
call's lanes come from ``(n, WARMUP)`` and carry the top bit, which no
pool lane carries, so the two never share a lane. Every call ends in
``block_until_ready`` on what it returns.
"""
from __future__ import annotations

import dataclasses

import numpy as np

WARMUP = -1
_TOP = np.uint32(0x80000000)


def lane_seeds(seed: int, call: int, n: int) -> np.ndarray:
    """``n`` workload seeds for call ``call`` of a run with ``seed``."""
    tag = 0xFFFFFFFF if call == WARMUP else call
    ss = np.random.SeedSequence(
        [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, tag])
    words = ss.generate_state(n, np.uint32)
    return words | _TOP if call == WARMUP else words & ~_TOP


@dataclasses.dataclass
class CallCount:
    lanes: int          # lanes the call simulated
    sim_seconds: float  # lanes x simulated horizon
    failed: int         # lanes of the call that failed a sanity check
    events: int         # simulated pipeline events (information only)


@dataclasses.dataclass
class Job:
    """One lane's program answer and what the reference needs to redo it."""

    params: dict
    workload: dict       # the lane's workload arrays
    program: dict        # the program's final state, host arrays


WL_FIELDS = ("arrival", "prio", "op_valid", "op_level", "op_ram", "op_base",
             "op_alpha", "pipe_out")
STATE_FIELDS = (
    "pipe_status", "pipe_completion", "pipe_fails", "pipe_preempts",
    "done_count", "failed_count", "oom_events", "preempt_events",
    "cache_hits", "cache_lookups", "cold_starts", "warm_starts",
    "cold_start_tick_total", "cache_hit_gb", "bytes_moved_gb",
    "util_cpu_s", "cost_dollars",
)


def _lane_summary(states, horizon):
    """Per-lane counts and sanity flags of a batched final state, on
    the host: the tick reached the horizon and the float accumulators
    are finite."""
    import jax
    import jax.numpy as jnp

    def finite(x):
        return jnp.all(jnp.isfinite(x).reshape(x.shape[0], -1), axis=1)

    s = jax.device_get({
        "ok": (states.tick == horizon) & finite(states.util_cpu_s)
        & finite(states.sum_latency_s) & finite(states.cost_dollars),
        "arrived": jnp.sum(states.pipe_status != 0, axis=1),
        "events": jnp.sum(states.pipe_status != 0, axis=1)
        + states.done_count + states.oom_events + states.preempt_events,
    })
    return s


class LaneTraffic:
    """Calls whose answers are batched final lane states, of which a
    sample drawn from the seed is checked. A kind subclasses it with
    ``build(seeds)``, the call's batched workload, and ``issue(wls)``,
    the batched final state."""

    lanes_per_call = 1

    def __init__(self, params, traffic: dict, seed: int):
        self.params = params
        self.traffic = traffic
        self.seed = seed
        self.horizon = params.horizon_ticks
        self.pool = lane_seeds(int(traffic["pool_seed"]), 0,
                               int(traffic["pool_lanes"]))
        self.check_per_call = int(traffic["check_lanes_per_call"])
        self.check_max = int(traffic["check_lanes_max"])
        self.sampled: list = []  # (call, lane indices, device states)

    @property
    def calls_per_pass(self) -> int:
        """Calls that use every lane of the pool once."""
        return len(self.pool) // self.lanes_per_call

    def seeds(self, call: int) -> np.ndarray:
        """The workload seeds of call ``call``: a group of the pool."""
        L = self.lanes_per_call
        if call == WARMUP:
            return lane_seeds(self.seed, WARMUP, L)
        groups = self.calls_per_pass
        cycle, pos = divmod(call, groups)
        order = np.random.default_rng(
            lane_seeds(self.seed, cycle, 4)).permutation(groups)
        group = self.pool[order[pos] * L:(order[pos] + 1) * L]
        return np.random.default_rng(
            lane_seeds(self.seed, call, 4) ^ 0x5A5A5A5A).permutation(group)

    def build(self, seeds):
        raise NotImplementedError

    def issue(self, wls):
        raise NotImplementedError

    def call(self, k: int, span) -> CallCount:
        import jax

        with span("workload_build"):
            wls = jax.block_until_ready(self.build(self.seeds(k)))
        with span("engine_call"):
            states = jax.block_until_ready(self.issue(wls))
        with span("reduce"):
            s = _lane_summary(states, self.horizon)
            self._keep_sample(k, states, s)
        L = int(s["ok"].shape[0])
        return CallCount(
            lanes=L,
            sim_seconds=L * self.params.duration,
            failed=int(L - s["ok"].sum()),
            events=int(s["events"].sum()),
        )

    def _keep_sample(self, k, states, s):
        """Keep the compared fields of this call's sampled lanes: the
        lane with the most arrivals, and others drawn from the seed.
        Every call gathers as many lanes, the warm-up's too, so that no
        gather compiles inside the window."""
        import jax.numpy as jnp

        L = s["arrived"].shape[0]
        n = min(self.check_per_call, L)
        if k != WARMUP and sum(len(ix) for _, ix, _ in self.sampled) + n > (
                self.check_max):
            return
        rng = np.random.default_rng(lane_seeds(self.seed, k, 4))
        longest = int(np.argmax(s["arrived"]))
        rest = [i for i in rng.permutation(L).tolist() if i != longest]
        ix = ([longest] + rest)[:n]
        sel = jnp.asarray(ix)
        kept = {f: getattr(states, f)[sel] for f in STATE_FIELDS}
        if k != WARMUP:
            self.sampled.append((k, ix, kept))

    def jobs(self, params_dict: dict) -> list[Job]:
        """After the window: rebuild each sampled call's workload batch
        on the device it ran on (the same program from the same seeds
        gives the same arrays) and pair each sampled lane with its
        final state."""
        import jax

        out = []
        for k, ix, dev_states in self.sampled:
            wls = jax.device_get(self.build(self.seeds(k)))
            states = jax.device_get(dev_states)
            for j, i in enumerate(ix):
                out.append(Job(
                    params=params_dict,
                    workload={f: np.asarray(getattr(wls, f))[i]
                              for f in WL_FIELDS},
                    program={f: np.asarray(v)[j] for f, v in states.items()},
                ))
        return out
