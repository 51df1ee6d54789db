"""``fleet``: one ``fleet_run`` of ``lanes_per_call`` seed lanes per
call, built with ``make_workload_batch`` (bitwise what
``fleet_run(seeds=)`` builds) and run with the mix's ``shard`` and
``bin_lanes``."""
from __future__ import annotations

# the program's entry points are looked up at call time, so that a test
# can put a broken program underneath
import repro.core as core

from generator import LaneTraffic


class Traffic(LaneTraffic):
    def __init__(self, params, traffic, seed):
        self.lanes_per_call = int(traffic["lanes_per_call"])
        super().__init__(params, traffic, seed)
        self.shard = traffic.get("shard")
        self.bin_lanes = bool(traffic.get("bin_lanes", True))

    def build(self, seeds):
        return core.make_workload_batch(self.params, seeds)

    def issue(self, wls):
        return core.fleet_run(self.params, workloads=wls, shard=self.shard,
                              bin_lanes=self.bin_lanes)
