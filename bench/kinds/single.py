"""``single``: one lane per call through ``run(params, workload=...)``,
as a developer debugging a policy calls it."""
from __future__ import annotations

# the program's entry points are looked up at call time, so that a test
# can put a broken program underneath
import repro.core as core

from generator import LaneTraffic


class Traffic(LaneTraffic):
    def build(self, seeds):
        import jax

        from repro.core.workload import generate_workload

        # a batch of one, so the rebuild after the window reads alike
        wl = generate_workload(self.params, jax.random.PRNGKey(int(seeds[0])))
        return jax.tree.map(lambda x: x[None], wl)

    def issue(self, wls):
        import jax

        wl = jax.tree.map(lambda x: x[0], wls)
        state = core.run(self.params, workload=wl).state
        return jax.tree.map(lambda x: x[None], state)
