"""``correct`` comes out false when it should: for the control (the
reference in bfloat16 put in the program's place) and for each fault
of the timed path that a cell can have, planted underneath a whole run
on the CPU with the look for a chip skipped."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import cell as cells
import check
import repro.core
import run
from repro.core import SimParams
from repro.core import sweep
from repro.core.state import broadcast_lanes, init_state

LANE_CELLS = ("mc_fleet", "single_run")


def _measure(root, name, seed=2**31 + 99):
    c = cells.load_cell(name, root)
    res, rows = run.measure(c, seed, 0.3, False, jax.devices()[:1], workers=1)
    return res


@pytest.mark.parametrize("name", LANE_CELLS)
def test_the_reference_agrees_with_the_program(tiny_root, name):
    res = _measure(tiny_root, name)
    assert res["correct"] is True, res["checked"]


@pytest.mark.parametrize("name", LANE_CELLS)
def test_control_in_bfloat16_is_not_correct(tiny_root, name):
    """The reference computed in bfloat16 in place of the program's
    float32 fails the comparison on every seed tried."""
    c = cells.load_cell(name, tiny_root)
    limits = dict(c.config["limits"], lanes_compared=1)
    for seed in (3, 4, 2**31 + 7):
        params = SimParams.from_dict(c.params)
        traffic = c.make_traffic(params, seed)
        spans = run.Spans()
        for k in range(3):
            traffic.call(k, spans)
        jobs = traffic.jobs(dict(c.params))
        for job, out in zip(jobs, check.run_reference(
                c.reference, jobs, 1, "bfloat16")):
            job.program = out
        ok, rows = check.verdict(check.compare(
            c.reference, jobs, check.run_reference(c.reference, jobs, 1)),
            limits, 0)
        assert not ok, rows


def _unchanged(real):
    """The engine returns every lane's state as it started."""
    def broken(params, *a, workloads=None, **kw):
        F = workloads.arrival.shape[0]
        return broadcast_lanes(init_state(params), F)
    return broken


def _half_batch(real):
    """The engine runs the first half of the lanes only; the other half
    report the state of a lane that ran."""
    def broken(params, *a, workloads=None, **kw):
        F = workloads.arrival.shape[0]
        h = max(F // 2, 1)
        half = real(params, *a, workloads=jax.tree.map(
            lambda x: x[:h], workloads), **kw)
        idx = jnp.arange(F) % h
        return jax.tree.map(lambda x: x[idx], half)
    return broken


def _altered(real):
    """Every lane's answer is altered where it is produced: the first
    completed pipeline finishes a tick later."""
    def broken(params, *a, **kw):
        st = real(params, *a, **kw)
        done = st.pipe_status == 5
        first = jnp.argmax(done, axis=1)
        bump = (jnp.arange(done.shape[1])[None, :] == first[:, None]) & done
        return st._replace(pipe_completion=st.pipe_completion + bump)
    return broken


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


# half of a batch of one is the whole batch: single_run cannot have it
CASES = [(n, f) for n in LANE_CELLS for f in sorted(FAULTS)
         if (n, f) != ("single_run", "half_batch")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_under_the_timed_path_is_not_correct(
        tiny_root, monkeypatch, name, fault):
    real = sweep.fleet_run
    broken = FAULTS[fault](real)
    if name == "single_run":
        import repro.core.engine as engine

        def run_broken(params, workload=None, **kw):
            wls = jax.tree.map(lambda x: x[None], workload)
            st = broken(params, workloads=wls)
            return engine.SimResult(
                state=jax.tree.map(lambda x: x[0], st), workload=workload,
                params=params, sched_state=None)
        monkeypatch.setattr(repro.core, "run", run_broken)
    else:
        monkeypatch.setattr(repro.core, "fleet_run", broken)
    res = _measure(tiny_root, name)
    assert res["correct"] is False, res["checked"]
