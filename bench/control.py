"""Readings that set the limits of ``correct``, for one cell, on the chip.

    python3 bench/control.py --workload mc_fleet --seeds 1,2,3 --seconds 20

For each seed, in one process: a window of the cell at its own size and
load, then the numbers ``check.py`` compares, twice: for the program's
answers (the lower readings), and for the control, the reference
computed in bfloat16 and put in the program's place (the upper
readings). One JSON line per seed. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import cell as cells  # noqa: E402
import check  # noqa: E402
import run  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    import generator
    from repro.core import SimParams

    params = SimParams.from_dict(cell.params)
    traffic = cell.make_traffic(params, seed)
    spans = run.Spans()
    traffic.call(generator.WARMUP, spans)
    win = run.window(traffic, seconds, spans, run.Compiles())
    jobs = traffic.jobs(dataclasses.asdict(params))
    workers = check.default_workers()
    ref = check.run_reference(cell.reference, jobs, workers)
    program = check.compare(cell.reference, jobs, ref)
    control = check.run_reference(cell.reference, jobs, workers, "bfloat16")
    for job, out in zip(jobs, control):
        job.program = out
    return {"seed": seed, "calls": len(win.counts), "program": program,
            "control": check.compare(cell.reference, jobs, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = cells.load_cell(args.workload, run.ROOT)
    run.device_check(cell.chips)
    run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
