"""``search``: one full-fidelity evaluation of a group of candidate
policies per call, through ``repro.search.evaluate_policies``, as one
rung of a cross-entropy search scores its population.

A call runs ``lanes_per_call`` lanes: ``S = min(scenario_lanes,
lanes_per_call)`` fixed scenario lanes under each of ``C =
lanes_per_call // S`` candidates, in one ``fleet_run`` under the
``policy`` scheduler, with the mix's ``shard``. The scenario lanes are
``S`` open-loop lanes of the configuration from the mix's
``pool_seed``, the same in every call, rebuilt with
``make_workload_batch`` for each call as a search rebuilds its batch.

The pool is of candidates, ``pool_lanes // S`` of them: the named
points of the policy family, then uniform draws of the search box from
``pool_seed``, as ``cem_search``'s first generation draws them. It is
cut into groups of ``C``, one per call; ``--seed`` chooses the order of
the groups (a new one each pass) and the order of the candidates in a
call. The warm-up call runs ``S`` lanes and ``C`` candidates that no
window call uses.
"""
from __future__ import annotations

import dataclasses
import types

import jax
import numpy as np

# the program's entry points are looked up at call time, so that a test
# can put a broken program underneath
import repro.core as core
import repro.search.grid as grid
from repro.core.policy import DEFAULT_POINTS
from repro.search import PolicySpace

from generator import WARMUP, WL_FIELDS, Job, LaneTraffic, lane_seeds


@dataclasses.dataclass
class Draw:
    """What one call evaluates: its scenario lanes' workload seeds and
    its candidates' policy vectors ``[C, 15]``."""

    scenarios: np.ndarray
    candidates: np.ndarray


def candidate_pool(n: int, pool_seed: int) -> np.ndarray:
    """``[n, 15]`` policy vectors: the named points, then uniform draws."""
    named = [p.to_vector() for p in DEFAULT_POINTS.values()][:n]
    space = PolicySpace()
    drawn = space.denormalize(space.sample_uniform(
        jax.random.PRNGKey(pool_seed), n - len(named)))
    return np.concatenate([np.stack(named), drawn]).astype(np.float32)


class Traffic(LaneTraffic):
    def __init__(self, params, traffic, seed):
        super().__init__(params, traffic, seed)
        lanes = int(traffic["lanes_per_call"])
        self.S = min(int(traffic["scenario_lanes"]), lanes)
        self.C = lanes // self.S
        self.lanes_per_call = self.C * self.S
        self.shard = traffic.get("shard")
        pool_seed = int(traffic["pool_seed"])
        self.scenarios = lane_seeds(pool_seed, 0, self.S)
        self.candidates = candidate_pool(
            int(traffic["pool_lanes"]) // self.S, pool_seed)

    @property
    def calls_per_pass(self) -> int:
        return len(self.candidates) // self.C

    def seeds(self, call: int) -> Draw:
        if call == WARMUP:
            space = PolicySpace()
            key = jax.random.PRNGKey(int(lane_seeds(self.seed, WARMUP, 1)[0]))
            return Draw(lane_seeds(self.seed, WARMUP, self.S),
                        space.denormalize(space.sample_uniform(key, self.C)))
        # the pool lanes' group order and in-call order of the base, over
        # candidate indices in groups of C
        groups = types.SimpleNamespace(
            pool=np.arange(len(self.candidates)), lanes_per_call=self.C,
            calls_per_pass=self.calls_per_pass, seed=self.seed)
        return Draw(self.scenarios,
                    self.candidates[LaneTraffic.seeds(groups, call)])

    def build(self, draw: Draw):
        return (core.make_workload_batch(self.params, draw.scenarios),
                draw.candidates)

    def issue(self, built):
        wls, candidates = built
        res = grid.evaluate_policies(lambda: (wls, self.params), candidates,
                                     shard=self.shard)
        return res["states"]

    def jobs(self, params_dict: dict) -> list[Job]:
        """After the window: each sampled lane ``i`` is scenario ``i %
        S`` under candidate ``i // S``, its policy vector carried in
        its workload."""
        out = []
        for k, ix, dev_states in self.sampled:
            wls, candidates = jax.device_get(self.build(self.seeds(k)))
            states = jax.device_get(dev_states)
            for j, i in enumerate(ix):
                s, c = i % self.S, i // self.S
                wl = {f: np.asarray(getattr(wls, f))[s] for f in WL_FIELDS}
                wl["policy"] = np.asarray(candidates[c], np.float32)
                out.append(Job(
                    params=params_dict, workload=wl,
                    program={f: np.asarray(v)[j] for f, v in states.items()},
                ))
        return out
