"""Run one benchmark cell once, on the chips of this machine, and print
its result as the last line of standard output.

    python3 bench/run.py --workload mc_fleet --seed 7 --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first
call, from the benchmark's host spans and from JAX's compile events.

A run: check that the cell's TPU chips are there (no CPU fallback);
turn on the persistent compilation cache; set up (build and warm up one
call of the cell's own shapes, with lanes no window call uses); issue
calls back to back while less than ``--seconds`` have passed and on to
the end of the pass over the cell's pool of lanes in progress, each
ending in ``block_until_ready``; close the window when the last call
is ready; read the device's peak memory; then compare a sample of what
the window produced with the configuration's plain reference
(``check.py``), and print each number compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import cell as cells  # noqa: E402
import check  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a set-up that found its programs in the cache spends about 1 s in
# compile events on a TPU v5e host; a cold one spends tens of seconds
COLD_COMPILE_S = 5.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_check(chips: int):
    """The TPU devices of this machine; raise unless it has ``chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's first device is {devices[0].platform!r}; "
            "the benchmark runs on the chip only")
    if len(devices) < chips:
        raise RuntimeError(
            f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def peak_bytes(device) -> int:
    """The device's peak bytes in use so far (0 where the backend keeps
    no such count, as the CPU's does not)."""
    stats = device.memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else 0


def enable_cache() -> str:
    """The program's persistent compilation cache (its fixed directory
    inside the checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every
    program cached however small or quick to compile, so that the eager
    ops of workload generation are found again too."""
    import jax

    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Spans:
    """Host spans of the benchmark's own calls into the program; while
    the profiler runs they also go into its trace."""

    def __init__(self):
        self.spans: list = []  # (name, start_s, end_s), perf_counter
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))


class Compiles:
    """XLA compiles seen while it is registered (JAX's compile events)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs


def trace_call(traffic, spans: Spans, trace_dir: str):
    """Issue call 0 under the profiler, host Python tracing off; return
    its count. The profiler's own cost falls outside the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    spans.annotate = True
    try:
        return traffic.call(0, spans)
    finally:
        spans.annotate = False
        jax.profiler.stop_trace()


def window(traffic, seconds: float, spans: Spans, compiles: Compiles,
           first: int = 0):
    """Issue calls ``first, first + 1, ...`` back to back, at least one,
    while less than ``seconds`` have passed, and on to the end of the
    pass over the traffic's pool in progress, so that every seed does
    the same work; the window closes when the last call is ready."""
    counts = []
    n_before = compiles.count
    per_pass = traffic.calls_per_pass
    t0 = time.perf_counter()
    k = first
    while (k == first or time.perf_counter() - t0 < seconds
           or k % per_pass):
        try:
            counts.append(traffic.call(k, spans))
        except Exception as e:  # noqa: BLE001 -- a failed call is counted
            print(f"call {k} raised: {e!r}", file=sys.stderr)
            n = traffic.lanes_per_call
            counts.append(types.SimpleNamespace(
                lanes=n, sim_seconds=0.0, failed=n, events=0))
        k += 1
    t1 = time.perf_counter()
    return types.SimpleNamespace(
        counts=counts, start=t0, end=t1, seconds=t1 - t0,
        compiles=compiles.count - n_before)


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            workers: int | None = None) -> tuple[dict, list]:
    """Set up, run the window and check it; return the result object
    and the rows compared (name, number, relation, limit)."""
    import jax

    import generator
    from repro.core import SimParams

    params = SimParams.from_dict(cell.params)
    traffic = cell.make_traffic(params, seed)
    spans = Spans()
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        traffic.call(generator.WARMUP, spans)  # warm-up: the cell's shapes
        setup_s = time.perf_counter() - T_START
        print(f"setup: {setup_s} s, {compiles.count} XLA compiles taking "
              f"{compiles.seconds} s"
              + (" (cache cold: first set-up in this checkout)"
                 if compiles.seconds > COLD_COMPILE_S else ""),
              file=sys.stderr)
        spans.spans.clear()
        reduced = None
        if trace:
            import trace_reduce

            # the traced call comes first and is checked like the others;
            # the window that follows gives the per-call host spans
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            try:
                traced = trace_call(traffic, spans, trace_dir)
                reduced = trace_reduce.reduce_dir(trace_dir, cell.chips)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            spans.spans.clear()
        win = window(traffic, seconds, spans, compiles, first=int(trace))
        peak = [peak_bytes(d) for d in devices]
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)

    sim_s = sum(c.sim_seconds for c in win.counts)
    events = sum(c.events for c in win.counts)
    print(f"window: {len(win.counts)} calls, {sim_s} simulated s in "
          f"{win.seconds} s; {events} simulated pipeline events "
          f"({events / win.seconds} events/s); {win.compiles} compiles in "
          "the window", file=sys.stderr)
    counts = ([traced] if trace else []) + win.counts
    attempted = sum(c.lanes for c in counts)
    failed = sum(c.failed for c in counts)

    # the check, once the window has closed and the peak has been read
    jobs = traffic.jobs(dataclasses.asdict(params))
    del traffic
    refs = check.run_reference(
        cell.reference, jobs,
        check.default_workers() if workers is None else workers)
    nums = check.compare(cell.reference, jobs, refs)
    limits = dict(cell.config["limits"],
                  lanes_compared=cell.traffic["check_lanes_min"])
    correct, rows = check.verdict(nums, limits, failed)

    dev = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": max(peak)},
    }
    if not trace:
        values = {"sim_s_per_s": sim_s / win.seconds,
                  "hbm_peak_gb": sum(peak) / 1e9,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        ctx = types.SimpleNamespace(
            trace=reduced, spans=[s for s in spans.spans
                                  if win.start <= s[1] <= win.end],
            calls=len(win.counts), compiles_in_window=win.compiles,
            cell=cell, params=params, device_kind=dev.device_kind)
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
        print(f"trace: {reduced['kernel_launches']} sim_tick launches, "
              f"device busy {reduced['busy_s']} s of {reduced['window_s']} s, "
              f"idle by host span {reduced['idle_by_span_s']}",
              file=sys.stderr)
    result["checked"] = {name: {"value": v, "limit": lim, "rel": rel}
                         for name, v, rel, lim in rows}
    return result, rows


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    cell = cells.load_cell(args.workload, ROOT)
    devices = device_check(cell.chips)
    print(f"cache: {enable_cache()}", file=sys.stderr)
    result, rows = measure(cell, args.seed, args.seconds, bool(args.trace),
                           devices)
    for name, v, rel, lim in rows:
        print(f"check {name}: {v} (limit {rel} {lim})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
