"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark plus the
section tables used by EXPERIMENTS.md, and writes machine-readable
fleet-throughput results to ``BENCH_fleet.json`` so the perf trajectory
is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--fast | --smoke]

``--smoke`` runs only a tiny fleet bench and validates the JSON output
(used by CI to keep the benchmark code from rotting).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import time

BENCH_JSON = pathlib.Path("BENCH_fleet.json")
# smoke runs validate the same machinery but must not clobber the
# committed cross-PR perf record
BENCH_JSON_SMOKE = pathlib.Path("BENCH_fleet.smoke.json")
# the COMMITTED smoke baseline the CI regression gate compares against
# (BENCH_fleet.smoke.json itself is gitignored scratch); re-record
# deliberately with --record-smoke-baseline
SMOKE_BASELINE = pathlib.Path(__file__).resolve().parent / "smoke_baseline.json"


def _csv(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")


ENGINE_ROWS = ("vmap", "fused", "sharded")
# small Perfetto trace written by --smoke runs; uploaded as a CI
# artifact next to BENCH_fleet.smoke.json (docs/observability.md)
SMOKE_PERFETTO = pathlib.Path("BENCH_trace.perfetto.json")


def write_fleet_json(
    rows: list[dict],
    smoke: bool,
    scenario_rows: list[dict] | None = None,
    search_rows: list[dict] | None = None,
    search_history: dict | None = None,
) -> dict:
    """Persist the fleet-engine rows; returns the validated payload.

    The ``vmap`` row is the benchmark-local reconstruction of the
    deleted legacy fleet path (see ``engine_throughput``), kept so the
    lane-major core's speedup stays tracked across PRs; ``sharded`` is
    the same core shard_mapped over every local device (event-density
    lane binning on). The ``selection`` row is the scheduler-selection
    microbench (three-pass helpers vs the fused ``sched_select``
    kernel), which feeds EXPERIMENTS.md §Scheduler-Perf. ``scenario_rows``
    (``engine_throughput.scenario_fleet_bench``) track fused/sharded
    throughput per scenario family — realistic-skew numbers for future
    binning/engine PRs, not just seed-batch variance. ``search_rows``
    (``benchmarks.policy_search``) track policy-search throughput in
    candidates/s, and ``search_history`` is the acceptance run's
    candidate-history artifact (docs/policy-search.md).
    """
    path = BENCH_JSON_SMOKE if smoke else BENCH_JSON
    fleet_rows = [r for r in rows if "fleet_engine" in r]
    by_engine = {r["fleet_engine"]: r for r in fleet_rows}
    payload = {
        "benchmark": "fleet_engine_throughput",
        "smoke": smoke,
        "fleet_size": next(
            (r["fleet_size"] for r in fleet_rows if "fleet_size" in r), 0
        ),
        "devices": by_engine.get("sharded", {}).get("devices", 1),
        "rows": fleet_rows,
        "speedup_fused_vs_vmap": by_engine.get("fused", {}).get(
            "speedup_vs_vmap"
        ),
        "speedup_sharded_vs_vmap": by_engine.get("sharded", {}).get(
            "speedup_vs_vmap"
        ),
    }
    traced = by_engine.get("fused_traced")
    if traced is not None:
        # telemetry cost on the fused path (engine_throughput.
        # trace_overhead_bench): tracked across PRs with a <10% bar
        # (EXPERIMENTS.md §Telemetry)
        payload["trace_overhead_pct"] = traced.get("trace_overhead_pct")
    faulted = by_engine.get("fused_faults")
    if faulted is not None:
        # chaos-layer cost on the fused path (engine_throughput.
        # faults_overhead_bench, EXPERIMENTS.md §Scheduler-Resilience)
        payload["faults_overhead_pct"] = faulted.get("faults_overhead_pct")
    closed = by_engine.get("fused_closed_loop")
    if closed is not None:
        # closed-loop-layer cost on the fused path (engine_throughput.
        # closed_loop_overhead_bench, docs/closed-loop.md)
        payload["closed_loop_overhead_pct"] = closed.get(
            "closed_loop_overhead_pct"
        )
    if scenario_rows is not None:
        payload["scenario_rows"] = scenario_rows
    if search_rows is not None:
        payload["search_rows"] = search_rows
    if search_history is not None:
        payload["search_history"] = search_history
    path.write_text(json.dumps(payload, indent=2) + "\n")
    # read-back validation: well-formed JSON with the tracked metrics
    loaded = json.loads(path.read_text())
    assert loaded["benchmark"] == "fleet_engine_throughput"
    assert loaded["rows"], "no fleet rows recorded"
    recorded = {r["fleet_engine"] for r in loaded["rows"]}
    assert recorded >= set(ENGINE_ROWS), "missing fleet path rows"
    if not smoke:
        assert "selection" in recorded, "missing selection microbench row"
        assert "apply" in recorded, "missing apply microbench row"
    for r in loaded["rows"]:
        if r["fleet_engine"] not in ENGINE_ROWS:
            continue
        for key in ("fleet_engine", "fleet_size", "wall_s", "wall_s_min",
                    "ticks_per_s", "sim_s_per_wall_s"):
            assert key in r, f"missing {key} in {r}"
    if scenario_rows is not None:
        recorded_scen = {
            (r["scenario"], r["fleet_engine"])
            for r in loaded["scenario_rows"]
        }
        scens = {s for s, _ in recorded_scen}
        assert len(scens) >= 4, f"expected >= 4 scenario families: {scens}"
        for s in scens:
            assert {(s, "fused"), (s, "sharded")} <= recorded_scen, (
                f"scenario {s} missing a fused/sharded row"
            )
        for r in loaded["scenario_rows"]:
            for key in ("scenario", "fleet_engine", "wall_s_min",
                        "ticks_per_s"):
                assert key in r, f"missing {key} in {r}"
    if search_rows is not None:
        assert loaded["search_rows"], "no search rows recorded"
        for r in loaded["search_rows"]:
            for key in ("search", "candidates", "evaluations",
                        "candidates_per_s", "front_size", "champion"):
                assert key in r, f"missing {key} in {r}"
    if search_history is not None:
        sh = loaded["search_history"]
        for key in ("seed", "objectives", "generations", "baselines",
                    "pareto_objectives", "champion", "evaluations"):
            assert key in sh, f"missing {key} in search_history"
        assert sh["champion"] is not None, (
            "acceptance search_history recorded without a champion"
        )
    print(f"wrote {path} "
          f"(speedup vs vmap baseline: fused "
          f"{loaded['speedup_fused_vs_vmap']}, sharded "
          f"{loaded['speedup_sharded_vs_vmap']} "
          f"on {loaded['devices']} device(s))")
    return loaded


def _fused_vs_vmap(payload: dict) -> float | None:
    rows = {r["fleet_engine"]: r for r in payload.get("rows", [])}
    fused, vmap = rows.get("fused"), rows.get("vmap")
    if not fused or not vmap:
        return None
    return fused["ticks_per_s"] / max(vmap["ticks_per_s"], 1)


def _faults_ratio(payload: dict) -> float | None:
    """Faults-ON / faults-OFF wall ratio (same-run, machine-neutral)."""
    pct = payload.get("faults_overhead_pct")
    if pct is None:
        return None
    return 1.0 + pct / 100.0


def _closed_loop_ratio(payload: dict) -> float | None:
    """Closed-loop-ON / OFF wall ratio (same-run, machine-neutral)."""
    pct = payload.get("closed_loop_overhead_pct")
    if pct is None:
        return None
    return 1.0 + pct / 100.0


def check_smoke_regression(loaded: dict, baseline: dict | None) -> bool | None:
    """One gate measurement: did fused throughput regress >20% vs the
    *committed* smoke baseline?

    Absolute ticks/s is not comparable across runs — CI runners and
    this container differ in speed and background load by more than
    any real regression — so the gate compares the fused engine
    against the vmap baseline *measured in the same run*: the
    fused/vmap throughput ratio normalises machine speed out, leaving
    the hot-path code as the only variable. Returns True when the
    ratio holds ≥80% of the recorded baseline's, False when it drops
    below, None when no baseline is available (gate skipped). The
    caller retries a False — a real regression fails every attempt, a
    runner load spike does not.
    """
    if not baseline or baseline.get("benchmark") != "fleet_engine_throughput":
        print("no recorded smoke baseline - regression gate skipped")
        return None
    base_ratio = _fused_vs_vmap(baseline)
    new_ratio = _fused_vs_vmap(loaded)
    if base_ratio is None or new_ratio is None:
        print("smoke baseline lacks fused/vmap rows - regression gate skipped")
        return None
    rel = new_ratio / base_ratio
    verdict = "OK" if rel >= 0.8 else "REGRESSED"
    print(f"fused/vmap smoke ratio: {new_ratio:.2f} vs recorded "
          f"{base_ratio:.2f} ({rel:.2f}x) {verdict}")
    ok = rel >= 0.8
    # second gate, same normalisation trick: the faults-ON/faults-OFF
    # wall ratio is measured in one run, so machine speed cancels and a
    # >20% regression means the chaos layer's hot-path cost grew (e.g.
    # the nxt_fault register gate stopped eliding the fault pass).
    # Skipped when the committed baseline predates the metric.
    base_fr = _faults_ratio(baseline)
    new_fr = _faults_ratio(loaded)
    if base_fr is None or new_fr is None:
        print("no recorded faults ratio - faults-overhead gate skipped")
        return ok
    frel = new_fr / base_fr
    fverdict = "OK" if frel <= 1.2 else "REGRESSED"
    print(f"faults-on/off smoke ratio: {new_fr:.2f} vs recorded "
          f"{base_fr:.2f} ({frel:.2f}x) {fverdict}")
    ok = ok and frel <= 1.2
    # third gate, same trick again: the closed-loop-ON/OFF wall ratio is
    # a same-run measurement, so a >20% rise means the admission/client
    # pass itself got slower (e.g. its static gate stopped compiling the
    # layer out of loop-off programs). Skipped when the committed
    # baseline predates the metric.
    base_cl = _closed_loop_ratio(baseline)
    new_cl = _closed_loop_ratio(loaded)
    if base_cl is None or new_cl is None:
        print("no recorded closed-loop ratio - closed-loop gate skipped")
        return ok
    crel = new_cl / base_cl
    cverdict = "OK" if crel <= 1.2 else "REGRESSED"
    print(f"closed-loop on/off smoke ratio: {new_cl:.2f} vs recorded "
          f"{base_cl:.2f} ({crel:.2f}x) {cverdict}")
    return ok and crel <= 1.2


def _maybe_profile(trace_dir: str | None):
    """Opt-in ``jax.profiler.trace`` around the benchmark body, viewable
    in TensorBoard or https://ui.perfetto.dev. The program's host spans
    (``eudoxia/*``) label the host timeline; a device op carries only
    its HLO instruction's name, which ``engine.scope_map`` maps to the
    engine phase (docs/observability.md, "Profiling the program")."""
    if not trace_dir:
        return contextlib.nullcontext()
    import jax

    print(f"profiling to {trace_dir} (open in Perfetto or TensorBoard)")
    return jax.profiler.trace(trace_dir)


def _chaos_smoke() -> None:
    """CI chaos smoke (docs/faults.md): the spot_churn scenario under
    two schedulers must finish with ZERO user-visible failures when the
    retry budget is on, and with nonzero FAILED pipelines when
    ``max_retries=0`` — both sides of the retry contract, asserted on
    the real fused engine every CI run."""
    import numpy as np

    from repro.core import SimParams, fleet_run
    from repro.core.scenarios import scenario_fleet, spot_churn_params

    base = SimParams(
        duration=0.05,
        max_pipelines=0,
        max_ops_per_pipeline=0,
        max_containers=32,
        waiting_ticks_mean=400.0,
        op_base_seconds_mean=0.004,
        num_pools=2,
    )
    for algo in ("priority", "priority_pool"):
        wls, params = scenario_fleet(
            "spot_churn", base.replace(scheduling_algo=algo), 4
        )
        chaos = spot_churn_params(params)
        lenient = fleet_run(chaos, workloads=wls)
        kills = int(np.asarray(lenient.fault_kills).sum())
        failed = int(np.asarray(lenient.failed_count).sum())
        retries = int(np.asarray(lenient.retry_events).sum())
        assert kills > 0, f"{algo}: chaos smoke injected no kills"
        assert failed == 0, (
            f"{algo}: {failed} pipelines FAILED despite a retry budget"
        )
        assert retries > 0, f"{algo}: kills absorbed without any retries"

        wls, params = scenario_fleet(
            "spot_churn", base.replace(scheduling_algo=algo), 4
        )
        strict = fleet_run(
            spot_churn_params(params, max_retries=0), workloads=wls
        )
        failed0 = int(np.asarray(strict.failed_count).sum())
        assert failed0 > 0, (
            f"{algo}: max_retries=0 chaos run failed no pipelines"
        )
        print(
            f"chaos smoke {algo}: kills={kills} retries={retries} "
            f"failed(budget)=0 failed(no-budget)={failed0}"
        )
    print("chaos smoke OK")


def _overload_smoke() -> None:
    """CI overload smoke (docs/closed-loop.md): the retry_storm scenario
    must produce a reproducible retry storm that a queue-threshold
    admission policy survives and ``admit_all`` does not. Both arms
    replay the SAME surge tapes (quiet tail after the surge, two early
    pool outages); the treatment arm rejects at the gate — client
    retries amplify its offered load and the excess is shed, but the
    backlog drains back to its pre-fault level on every lane. The
    control arm admits everything: amplification stays 1.0 (nothing for
    clients to retry) yet the backlog never recovers — the metastable
    signature, asserted on the real fused engine every CI run."""
    import numpy as np

    from repro.core import SimParams, fleet_run, workload_batch_from_traces
    from repro.core.scenarios import retry_storm_params, scenario_lane_batch
    from repro.core.state import INF_TICK

    base = SimParams(
        duration=0.08,
        max_pipelines=0,
        max_ops_per_pipeline=0,
        max_containers=16,
        waiting_ticks_mean=150.0,
        op_base_seconds_mean=0.008,
        op_base_seconds_sigma=1.0,
        num_pools=2,
        total_cpus=4,
        total_ram_gb=8,
        scheduling_algo="priority_pool",
    )
    n_lanes = 4
    # tape stops at 0.06s: a quiet tail the backlog COULD drain into —
    # whether it does is exactly what separates the two arms
    lanes = scenario_lane_batch(
        "retry_storm", base.replace(duration=0.06), n_lanes,
        seed=3, surge_factor=6.0,
    )

    def arm(policy: str, limit: int = 0):
        wls, params = workload_batch_from_traces(lanes, base)
        p = retry_storm_params(
            params,
            admission_policy=policy,
            admit_queue_limit=limit,
            outage_mtbf_s=0.02,
            outage_duration_s=0.006,
            client_max_retries=3,
        ).replace(max_fault_events=2)  # outages stop early, tail is calm
        st = fleet_run(p, workloads=wls)
        offered = int(np.asarray(st.offered_total).sum())
        unique = int(np.asarray(st.offered_unique).sum())
        return {
            "amp": offered / max(unique, 1),
            "shed": int(np.asarray(st.shed_total).sum()),
            "client_retries": int(np.asarray(st.client_retry_events).sum()),
            "faulted": int(np.sum(np.asarray(st.last_fault_tick) < INF_TICK)),
            "drained": int(np.sum(np.asarray(st.drain_tick) < INF_TICK)),
        }

    control = arm("admit_all")
    treated = arm("queue_threshold", limit=3)
    for name, r in (("admit_all", control), ("queue_threshold", treated)):
        assert r["faulted"] == n_lanes, (
            f"{name}: only {r['faulted']}/{n_lanes} lanes saw an outage"
        )
    # the storm is real: client retries amplify the treated arm's load
    assert treated["amp"] > 1.5, (
        f"queue_threshold: no retry storm (amplification {treated['amp']:.2f})"
    )
    assert treated["shed"] > 0, "queue_threshold: policy never shed load"
    assert control["amp"] == 1.0 and control["shed"] == 0, (
        f"admit_all rejected something: amp={control['amp']:.2f} "
        f"shed={control['shed']}"
    )
    # ...and the policy survives it while admit_all goes metastable
    assert treated["drained"] == n_lanes, (
        f"queue_threshold: backlog stuck above the pre-fault level on "
        f"{n_lanes - treated['drained']}/{n_lanes} lanes"
    )
    assert control["drained"] < n_lanes, (
        "admit_all drained every lane - the smoke config no longer "
        "overloads the fleet"
    )
    print(
        f"overload smoke: admit_all amp={control['amp']:.2f} "
        f"drained={control['drained']}/{n_lanes} | queue_threshold "
        f"amp={treated['amp']:.2f} shed={treated['shed']} "
        f"retries={treated['client_retries']} "
        f"drained={treated['drained']}/{n_lanes}"
    )
    print("overload smoke OK")


def _write_smoke_perfetto() -> None:
    """A small real Perfetto trace for the CI artifact: one traced
    single-sim run, exported with ``telemetry.to_perfetto_json``."""
    from repro.core import SimParams, run, to_perfetto_json

    params = SimParams(
        duration=0.05,
        scheduling_algo="priority_pool",
        num_pools=2,
        max_pipelines=32,
        max_containers=32,
        waiting_ticks_mean=400.0,
        op_base_seconds_mean=0.004,
        cache_gb_per_pool=4.0,
        scan_ticks_per_gb=50.0,
        cold_start_ticks=40,
        container_warm_ticks=2_000,
    )
    res = run(params, trace=True)
    SMOKE_PERFETTO.write_text(to_perfetto_json(res.trace, res.params))
    print(f"wrote {SMOKE_PERFETTO} ({res.trace.n} events, "
          f"{res.trace.events_dropped} dropped)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the slower benches (tick engine, fleet)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fleet bench only; asserts BENCH_fleet.json "
                         "is produced and well-formed, and fails if fused "
                         "throughput or the faults-on/off overhead ratio "
                         "regressed >20% vs the recorded smoke baseline (CI)")
    ap.add_argument("--no-regression-gate", action="store_true",
                    help="skip the --smoke fused-throughput regression gate")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="wrap the benchmark body in jax.profiler.trace(DIR) "
                         "(view in Perfetto / TensorBoard); map device ops "
                         "to engine phases with repro.core.engine.scope_map")
    ap.add_argument("--record-smoke-baseline", action="store_true",
                    help="with --smoke: run the smoke bench three times and "
                         "record the LOWEST fused/vmap ratio as the committed "
                         "baseline (benchmarks/smoke_baseline.json) instead "
                         "of gating — a conservative floor, so load spikes "
                         "on the recording host don't set an unbeatable bar")
    args = ap.parse_args()
    if (args.record_smoke_baseline or args.no_regression_gate) and not args.smoke:
        ap.error("--record-smoke-baseline / --no-regression-gate only "
                 "apply to --smoke runs")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.smoke:
        from benchmarks import engine_throughput

        baseline = None
        if SMOKE_BASELINE.exists():
            try:
                baseline = json.loads(SMOKE_BASELINE.read_text())
            except json.JSONDecodeError:
                pass
        if args.record_smoke_baseline:
            # conservative floor: lowest fused/vmap ratio of three runs,
            # so one quiet-host run doesn't set a bar the gate's 20%
            # margin can't absorb under normal runner load
            candidates = []
            faults_ratios = []
            closed_ratios = []
            for i in range(3):
                rows = engine_throughput.fleet_bench(smoke=True)
                rows += engine_throughput.faults_overhead_bench(smoke=True)
                rows += engine_throughput.closed_loop_overhead_bench(
                    smoke=True
                )
                loaded = write_fleet_json(rows, smoke=True)
                ratio = _fused_vs_vmap(loaded)
                fr = _faults_ratio(loaded)
                cr = _closed_loop_ratio(loaded)
                print(f"recording run {i + 1}/3: fused/vmap {ratio:.2f}, "
                      f"faults on/off {fr:.2f}, closed-loop on/off {cr:.2f}")
                candidates.append((ratio, loaded))
                faults_ratios.append(fr)
                closed_ratios.append(cr)
            _, floor = min(candidates, key=lambda c: c[0])
            # the faults/closed-loop gates fail on ratios ABOVE baseline,
            # so their conservative record is the highest of the three runs
            frs = [fr for fr in faults_ratios if fr is not None]
            if frs:
                floor["faults_overhead_pct"] = round(
                    (max(frs) - 1.0) * 100, 1
                )
            crs = [cr for cr in closed_ratios if cr is not None]
            if crs:
                floor["closed_loop_overhead_pct"] = round(
                    (max(crs) - 1.0) * 100, 1
                )
            SMOKE_BASELINE.write_text(json.dumps(floor, indent=2) + "\n")
            print(f"recorded smoke baseline (floor of 3) -> {SMOKE_BASELINE}")
            print("benchmarks smoke OK")
            return
        with _maybe_profile(args.profile):
            rows = engine_throughput.fleet_bench(smoke=True)
            rows += engine_throughput.trace_overhead_bench(smoke=True)
            rows += engine_throughput.faults_overhead_bench(smoke=True)
            rows += engine_throughput.closed_loop_overhead_bench(smoke=True)
        for r in rows:
            print(r)
        from benchmarks import policy_search

        search_rows = policy_search.search_smoke()
        loaded = write_fleet_json(rows, smoke=True, search_rows=search_rows)
        _write_smoke_perfetto()
        _chaos_smoke()
        _overload_smoke()
        if not args.no_regression_gate:
            ok = check_smoke_regression(loaded, baseline)
            attempts = 1
            while ok is False and attempts < 3:
                # re-measure before failing: a real hot-path regression
                # reproduces on every run, a runner load spike does not
                print(f"re-measuring (attempt {attempts + 1}/3)...")
                rows = engine_throughput.fleet_bench(smoke=True)
                rows += engine_throughput.faults_overhead_bench(smoke=True)
                rows += engine_throughput.closed_loop_overhead_bench(
                    smoke=True
                )
                loaded = write_fleet_json(rows, smoke=True)
                ok = check_smoke_regression(loaded, baseline)
                attempts += 1
            if ok is False:
                raise SystemExit(
                    "smoke gate failed in 3/3 measurements: fused/vmap "
                    "throughput down >20%, or the faults-on/off or "
                    "closed-loop-on/off overhead ratio up >20% "
                    "vs the recorded baseline; if intentional, re-record the "
                    "committed baseline with `--smoke "
                    "--record-smoke-baseline` "
                    "(benchmarks/smoke_baseline.json), or pass "
                    "--no-regression-gate"
                )
        print("benchmarks smoke OK")
        return

    print("== tpch_validation (paper Fig. 3) ==")
    from benchmarks import tpch_validation

    t0 = time.time()
    out = tpch_validation.main(print_rows=False)
    _csv(
        "tpch_validation",
        (time.time() - t0) * 1e6 / max(out["n_queries"], 1),
        f"mean_err={out['mean_err_pct']:.2f}%_paper_band=0.44-3.08%",
    )

    print("== scheduler_comparison (paper §4.1.2) ==")
    from benchmarks import scheduler_comparison

    rows = scheduler_comparison.main(print_rows=False)
    for r in rows:
        _csv(
            f"sched_{r['scheduler']}",
            r["wall_s"] * 1e6,
            f"thr={r['throughput_per_s']}/s_p99={r['p99_latency_s']}s"
            f"_pre={r['preempt_events']}",
        )

    if not args.fast:
        print("== cache_sensitivity (data plane, EXPERIMENTS.md) ==")
        rows = scheduler_comparison.cache_sensitivity(print_rows=False)
        for r in rows:
            _csv(
                f"cache_{r['scheduler']}_{r['cache_gb_per_pool']:g}gb",
                r["wall_s"] * 1e6,
                f"hit={r['cache_hit_rate']}_moved={r['bytes_moved_gb']}gb"
                f"_lat={r['mean_latency_s']}s_cold={r['cold_starts']}",
            )

        print("== scenario_comparison (scenario library, docs/scenarios.md) ==")
        rows = scheduler_comparison.scenario_comparison(print_rows=False)
        for r in rows:
            _csv(
                f"scenario_{r['scenario']}_{r['scheduler']}",
                r["wall_s"] * 1e6,
                f"thr={r['throughput_per_s']}/s_lat={r['mean_latency_s']}s"
                f"_pre={r['preempt_events']}_hit={r['cache_hit_rate']}",
            )

        print("== resilience_comparison (chaos layer, docs/faults.md) ==")
        rows = scheduler_comparison.resilience_comparison(print_rows=False)
        for r in rows:
            _csv(
                f"resilience_{r['scheduler']}",
                r["wall_s"] * 1e6,
                f"goodput={r['goodput_per_s']}/s"
                f"_degr={r['goodput_degradation_pct']}%"
                f"_retries={r['retries']}_failed={r['failed']}"
                f"_wasted={r['wasted_work_s']}s",
            )

    print("== interleaving (paper §2.2 / Table 1) ==")
    from benchmarks import interleaving

    out = interleaving.main(print_rows=False)
    for k, v in out.items():
        _csv(
            f"interleave_{k}",
            0.0,
            f"thr={v['throughput_per_s']:.1f}/s"
            f"_interlat={v['interactive_latency_s']:.4f}s"
            f"_util={v['cpu_utilization']:.3f}",
        )

    print("== engine_throughput (§Perf + §Fleet-Perf headline) ==")
    from benchmarks import engine_throughput

    if not args.fast:
        with _maybe_profile(args.profile):
            rows = engine_throughput.main(print_rows=False)
        for r in rows:
            if r.get("fleet_engine") == "selection":
                _csv("engine_selection_microbench", r["fused_us"],
                     f"three_pass={r['three_pass_us']}us_"
                     f"speedup={r['speedup']}x")
                continue
            if r.get("fleet_engine") == "apply":
                _csv("engine_apply_microbench", r["fused_us"],
                     f"legacy={r['legacy_us']}us_speedup={r['speedup']}x")
                continue
            _csv(
                f"engine_{r['engine'].split()[0]}_{r.get('fleet_engine', '')}"
                .rstrip("_"),
                r["wall_s"] * 1e6,
                f"ticks/s={r['ticks_per_s']}",
            )
        scenario_rows = engine_throughput.scenario_fleet_bench()
        for r in scenario_rows:
            _csv(
                f"engine_scenario_{r['scenario']}_{r['fleet_engine']}",
                r["wall_s"] * 1e6,
                f"ticks/s={r['ticks_per_s']}",
            )

        print("== policy_search (docs/policy-search.md acceptance) ==")
        from benchmarks import policy_search

        search_rows, search_history = policy_search.acceptance_search()
        write_fleet_json(rows, smoke=False, scenario_rows=scenario_rows,
                         search_rows=search_rows,
                         search_history=search_history)

    print("== kernels ==")
    from benchmarks import kernels_bench

    rows = kernels_bench.main(print_rows=False)
    for r in rows:
        _csv(r["name"], r["us_per_call"], r.get("derived", ""))

    print("== serving policy pick (bridge) ==")
    from repro.serving.bridge import ServeRequest, evaluate_policies, pick_policy
    import numpy as np

    rng = np.random.default_rng(0)
    trace = [
        ServeRequest(
            arrival_s=float(i * 0.2),
            prompt_tokens=int(rng.integers(64, 512)),
            new_tokens=64,
            interactive=bool(rng.random() < 0.5),
        )
        for i in range(32)
    ]
    from repro.configs.registry import get_arch

    t0 = time.time()
    res = evaluate_policies(trace, get_arch("gemma3_12b").model)
    pol = pick_policy(res)
    _csv("serving_policy_eval", (time.time() - t0) * 1e6 / 3, f"picked={pol}")

    print("benchmarks complete")


if __name__ == "__main__":
    main()
