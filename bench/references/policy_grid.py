"""Plain reference of a policy-grid lane: the open-loop reference
(``open_loop.py``) run under the lane's own policy vector, which the
``search`` kind of call puts in each sampled lane's workload
(``workload["policy"]``). Its numbers compared are ``open_loop``'s.
It imports nothing of the program."""
from __future__ import annotations

import pathlib

import numpy as np

from cell import load_module

_base = load_module(pathlib.Path(__file__).with_name("open_loop.py"),
                    "reference")
compare = _base.compare


def simulate(job, dtype=np.float32) -> dict:
    """The reference's final state of one sampled lane under its policy."""
    return _base.simulate_lane(job.params, job.workload,
                               policy=job.workload["policy"], dtype=dtype)
