"""The check that decides ``correct``: the program's answers against the
plain reference on the same inputs.

The reference is the module the configuration names
(``references/<reference>.py``). It defines ``simulate(job, dtype)``,
the reference's answer to one job that a kind of call made
(``generator.Job``), and ``compare(jobs, answers)``, the numbers
compared, each against the configuration's limit of the same name.

The reference runs after the window, on the host, in worker processes
that never import JAX, so the process that holds the chip stays the
only one on it.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import pathlib

import numpy as np

from cell import load_module


def simulate(args):
    path, job, dtype_name = args
    dtype = np.float32
    if dtype_name == "bfloat16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    return load_module(pathlib.Path(path), "reference").simulate(job, dtype)


def run_reference(reference: pathlib.Path, jobs, workers: int,
                  dtype_name: str = "float32"):
    """The reference's answer to every job, in job order."""
    tasks = [(str(reference), j, dtype_name) for j in jobs]
    if workers <= 1 or len(tasks) <= 1:
        return [simulate(t) for t in tasks]
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(
        max_workers=min(workers, len(tasks)), mp_context=ctx
    ) as pool:
        return list(pool.map(simulate, tasks))


def default_workers() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 2))


def compare(reference: pathlib.Path, jobs, answers) -> dict:
    """The numbers compared, over all jobs (none when there are none)."""
    if not jobs:
        return {}
    return load_module(reference, "reference").compare(jobs, answers)


def verdict(nums: dict, limits: dict, failed: int) -> tuple[bool, list]:
    """``(correct, rows)``, each row ``(name, number, relation, limit)``.

    A run is correct when no call failed, at least the configured number
    of lanes was compared, and every limit has a number within it."""
    rows = [("failed", failed, "<=", 0),
            ("lanes_compared", nums.get("lanes_compared", 0), ">=",
             limits["lanes_compared"])]
    rows += [(k, nums.get(k), "<=", lim) for k, lim in limits.items()
             if k != "lanes_compared"]
    ok = all(v is not None and (v >= lim if rel == ">=" else v <= lim)
             for _, v, rel, lim in rows)
    return ok, rows
