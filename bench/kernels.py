"""The chip's published peaks (``peaks.json``), and the bytes of the
program's Pallas kernels' operands and results, from their shapes."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

# sim_tick's block of lanes (kernels/sim_tick/kernel.py, block_fleet)
SIM_TICK_BLOCK = 256


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def sim_tick_bytes(lanes: int, max_containers: int, max_pipelines: int,
                   num_pools: int) -> int:
    """Bytes of one ``sim_tick`` launch's operands and results.

    Inside the engine's loop the compiler keeps most of them in the
    core's on-chip memory from trip to trip, so this is not the
    kernel's HBM traffic, and bytes over HBM bandwidth do not bound it.

    The fleet axis is padded to whole blocks of lanes. Per padded lane
    it reads six container rows (status, end, oom, cpus, ram, pool),
    three pipeline rows (status, arrival, release) and an 8-wide tick
    row, and writes three container rows (oomed, done, new status), two
    freed-resource rows of ``num_pools``, two pipeline rows (fresh,
    released) and two 8-wide register rows; every element is 4 bytes.
    """
    fb = min(SIM_TICK_BLOCK, lanes)
    padded = lanes + (-lanes) % fb
    per_lane = (9 * max_containers + 5 * max_pipelines + 3 * 8
                + 2 * num_pools)
    return 4 * padded * per_lane
