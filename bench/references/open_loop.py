"""Plain reference of the simulator's semantics, for the benchmark's check.

A straightforward numpy re-statement of what one lane of the simulator
does under the benchmark's configurations: open-loop arrivals, the
parameterised scheduling-policy family (``priority`` is one point of
it), OOM retries, preemption, and the executor's container runtimes.
It imports nothing of the program and needs no JAX, so worker
processes can run it beside the process that holds the chip.

A configuration names its reference (``"reference": "open_loop"``);
the check calls its :func:`simulate` on each lane the window sampled
and its :func:`compare` on the program's answers and its own.
This one keeps faults, closed-loop clients, the zero-copy cache, scan
charges and cold starts off: :func:`simulate_lane` refuses parameters
that turn any of them on rather than simulate them wrongly. A
configuration that turns one on names a reference file of its own.

``dtype`` is the precision of every resource and runtime quantity. The
simulator states float32; the benchmark's control runs this same
reference in bfloat16 and must come out as not correct.
"""
from __future__ import annotations

import numpy as np

TICKS_PER_SECOND = 100_000
INF_TICK = 2**31 - 1
EPS = 1e-5

# pipeline status codes; priorities run BATCH 0 < QUERY 1 < INTERACTIVE 2
EMPTY, PENDING, WAITING, RUNNING, SUSPENDED, DONE, FAILED = range(7)

# the order of a policy vector's 15 knobs
POLICY_FIELDS = (
    "chunk_frac", "cap_frac", "retry_mult", "size_weight", "prio_weight",
    "age_weight", "preempt", "preempt_min_prio", "victim_prio_gap",
    "multi_pool", "cache_pin", "locality_bonus", "exclusive", "grab_all",
    "ram_gate",
)
# the named schedulers the configurations use, as points of the family:
# 10% chunks, OOM doubling capped at 50%, preemption, one pool
NAMED_POLICIES = {
    "priority": (0.10, 0.50, 2.0, 0.0, 0.0, 0.0, 1.0, 0.0, 0.0,
                 0.0, 0.0, 0.0, 0.0, 0.0, 1.0),
}

# knobs whose mechanisms this reference does not model: each must be 0
OFF_KNOBS = (
    "cache_gb_per_pool", "scan_ticks_per_gb", "cold_start_ticks",
    "crash_mtbf_ticks", "outage_mtbf_ticks", "straggler_prob",
    "timeout_ticks", "client_max_inflight", "client_think_ticks",
    "client_max_retries", "client_backoff_ticks",
)

# outputs compared against the program's final state, one lane each
EXACT_FIELDS = (
    "pipe_status", "pipe_completion", "pipe_fails", "pipe_preempts",
    "done_count", "failed_count", "oom_events", "preempt_events",
    "cache_hits", "cache_lookups", "cold_starts", "warm_starts",
    "cold_start_tick_total", "cache_hit_gb", "bytes_moved_gb",
)


def check_params(params: dict) -> None:
    """Raise ``ValueError`` when ``params`` need a mechanism not modelled."""
    on = [k for k in OFF_KNOBS if params.get(k, 0)]
    adm = str(params.get("admission_policy", "admit_all"))
    if adm.replace("-", "_").lower() != "admit_all":
        on.append("admission_policy")
    if on:
        raise ValueError(f"the reference does not model {on}")


def speedup(c, alpha, dt):
    """``c ** alpha`` for the scaling exponents the generator draws."""
    if alpha == 0.0:
        return dt(1.0)
    if alpha == 1.0:
        return dt(c)
    if alpha == 0.5:
        return dt(np.sqrt(np.float32(c)))
    return dt(np.power(np.float32(c), np.float32(alpha)))


class Ctr:
    __slots__ = ("slot", "pid", "pool", "cpus", "ram", "start", "end", "oom")

    def __init__(self, slot, pid, pool, cpus, ram, start, end, oom):
        self.slot, self.pid, self.pool = slot, pid, pool
        self.cpus, self.ram = cpus, ram
        self.start, self.end, self.oom = start, end, oom


class Pipes:
    """The lane's pipelines: fixed columns from the workload, and the
    retry bookkeeping the scheduler reads (last grant, earlier OOM)."""

    def __init__(self, wl: dict, dt):
        arrival = np.asarray(wl["arrival"])
        valid = np.asarray(wl["op_valid"])
        level = np.asarray(wl["op_level"])
        ram = np.asarray(wl["op_ram"], np.float32)
        base = np.asarray(wl["op_base"], np.float32)
        alpha = np.asarray(wl["op_alpha"], np.float32)
        self.n = arrival.shape[0]
        self.arrival = arrival.astype(np.int64)
        self.prio = np.asarray(wl["prio"]).astype(np.int64)
        self.n_ops = valid.sum(axis=1)
        self.out_gb = np.asarray(wl["pipe_out"], np.float32)
        # per pipeline, per DAG level in order: [(base, alpha, ram), ...]
        self.levels = []
        for i in range(self.n):
            lv: dict[int, list] = {}
            for j in np.flatnonzero(valid[i]):
                lv.setdefault(int(level[i, j]), []).append(
                    (dt(base[i, j]), float(alpha[i, j]), dt(ram[i, j]))
                )
            self.levels.append([lv[k] for k in sorted(lv)])
        self.failed_before = np.zeros(self.n, bool)
        self.last_cpus = np.zeros(self.n, dt)
        self.last_ram = np.zeros(self.n, dt)


def container_schedule(levels, cpus, ram, dt):
    """Ticks a container of ``cpus``/``ram`` takes to run a pipeline of
    ``levels``, and the tick offset at which it runs out of memory
    (None: it fits). Levels run one after another; a level's ops split
    the CPUs evenly, run side by side, and need their summed RAM."""
    duration = 0
    oom_offset = None
    cum = dt(0.0)
    one = dt(1.0)
    for ops in levels:
        c_eff = max(dt(dt(cpus) / max(dt(len(ops)), one)), dt(1e-6))
        t_level = dt(0.0)
        ram_level = dt(0.0)
        for base, alpha, op_ram in ops:
            t_level = max(t_level, dt(base / speedup(c_eff, alpha, dt)))
            ram_level = dt(ram_level + op_ram)
        t_level = dt(np.ceil(np.float32(max(t_level, one))))
        if oom_offset is None and ram_level > dt(dt(ram) + dt(1e-6)):
            oom_offset = max(int(cum), 1)
        cum = dt(cum + t_level)
        duration += int(t_level)
    duration = max(duration, 1)
    if oom_offset is not None:
        oom_offset = min(oom_offset, duration)
    return duration, oom_offset


def decide(pol, pipes, waiting, entered, running, free_cpu, free_ram,
           cap_cpu_pool, cap_ram_pool, K, dt):
    """One scheduler decision of the policy family.

    Returns ``(rejects, suspends, grants)``: pids failed back to the
    user, pids preempted, and ``(pid, pool, cpus, ram)`` grants, in the
    order the executor applies them."""
    (chunk_frac, cap_frac, retry_mult, size_w, prio_w, age_w, preempt,
     preempt_min_prio, victim_gap, multi_pool, _cache_pin, _loc_bonus,
     exclusive, grab_all, ram_gate) = (dt(v) for v in pol)
    eps = dt(EPS)
    total_cpu = dt(np.sum(cap_cpu_pool, dtype=np.float32))
    total_ram = dt(np.sum(cap_ram_pool, dtype=np.float32))
    chunk_cpu, chunk_ram = dt(chunk_frac * total_cpu), dt(chunk_frac * total_ram)
    cap_cpu, cap_ram = dt(cap_frac * total_cpu), dt(cap_frac * total_ram)
    preempt_on, excl_on = preempt > 0.5, exclusive > 0.5
    grab_on, gate_on, multi_on = grab_all > 0.5, ram_gate > 0.5, multi_pool > 0.5

    # queue order: the policy's lead key, then priority desc, entry
    # asc, pid asc; the lead is (size_w*n_ops + age_w*entered) - prio_w*prio
    w = np.fromiter(waiting, np.int64, len(waiting))
    prio = pipes.prio[w]
    lead = ((size_w * pipes.n_ops[w].astype(dt)
             + age_w * entered[w].astype(dt)).astype(dt)
            - (prio_w * prio.astype(dt)).astype(dt)).astype(dt)
    order = w[np.lexsort((w, entered[w], -prio, lead))]
    gate = pipes.last_ram[order] >= dt(cap_ram - eps)
    rej = pipes.failed_before[order] & (gate if gate_on else True)
    rejects = order[rej].tolist()
    order = order[~rej].tolist()

    free_cpu, free_ram = free_cpu.copy(), free_ram.copy()
    live = dict(running)
    idle0 = not running
    assigned = False
    suspends, grants = [], []
    for pid in order[:K]:
        if pipes.failed_before[pid]:
            want_cpu = min(dt(retry_mult * pipes.last_cpus[pid]), cap_cpu)
            want_ram = min(dt(retry_mult * pipes.last_ram[pid]), cap_ram)
        elif pipes.last_ram[pid] > 0.0:
            want_cpu, want_ram = pipes.last_cpus[pid], pipes.last_ram[pid]
        else:
            want_cpu, want_ram = chunk_cpu, chunk_ram
        pool = 0
        if multi_on:
            # the emptiest pool by free share (no pool caches anything
            # here, so neither the cache pin nor the locality bonus acts)
            score = (free_cpu / np.maximum(cap_cpu_pool, eps)).astype(dt) + (
                free_ram / np.maximum(cap_ram_pool, eps)).astype(dt)
            pool = int(np.argmax(score))
        if grab_on:
            want_cpu, want_ram = cap_cpu_pool[pool], cap_ram_pool[pool]
        if excl_on:
            # one grant, and only on an idle cluster, with no fits test
            if idle0 and not assigned:
                grants.append((pid, pool, want_cpu, want_ram))
                free_cpu[pool] = dt(free_cpu[pool] - want_cpu)
                free_ram[pool] = dt(free_ram[pool] - want_ram)
                assigned = True
            continue
        if (free_cpu[pool] >= dt(want_cpu - eps)
                and free_ram[pool] >= dt(want_ram - eps)):
            grants.append((pid, pool, want_cpu, want_ram))
            free_cpu[pool] = dt(free_cpu[pool] - want_cpu)
            free_ram[pool] = dt(free_ram[pool] - want_ram)
            assigned = True
            continue
        p_prio = dt(pipes.prio[pid])
        if not preempt_on or not p_prio > preempt_min_prio:
            continue
        thresh = dt(p_prio - victim_gap)
        victims = [c for c in live.values() if dt(pipes.prio[c.pid]) < thresh]
        if not victims:
            continue
        # the lowest priority, then the latest started, then the lowest slot
        v = min(victims, key=lambda c: (pipes.prio[c.pid], -c.start, c.slot))
        f_cpu2, f_ram2 = free_cpu.copy(), free_ram.copy()
        f_cpu2[v.pool] = dt(f_cpu2[v.pool] + v.cpus)
        f_ram2[v.pool] = dt(f_ram2[v.pool] + v.ram)
        pool2 = v.pool if multi_on else pool
        if (f_cpu2[pool2] >= dt(want_cpu - eps)
                and f_ram2[pool2] >= dt(want_ram - eps)):
            suspends.append(v.pid)
            del live[v.pid]
            free_cpu, free_ram = f_cpu2, f_ram2
            grants.append((pid, pool2, want_cpu, want_ram))
            free_cpu[pool2] = dt(free_cpu[pool2] - want_cpu)
            free_ram[pool2] = dt(free_ram[pool2] - want_ram)
            assigned = True
    return rejects, suspends, grants


def simulate_lane(params: dict, wl: dict, policy=None, dtype=np.float32) -> dict:
    """Run one lane to the horizon; return its final statistics.

    ``params`` holds the configuration's ``SimParams`` fields, ``wl``
    the lane's workload arrays, ``policy`` its 15 knobs when the
    scheduler is ``policy``. The loop jumps from event to event:
    arrivals, retirements (completion or OOM), releases of preempted
    pipelines, and the tick after any scheduler action."""
    check_params(params)
    dt = dtype
    algo = params["scheduling_algo"]
    if algo == "policy":
        if policy is None:
            raise ValueError("scheduler 'policy' needs a policy vector")
        pol = tuple(float(v) for v in np.asarray(policy, np.float32))
    elif algo in NAMED_POLICIES:
        pol = NAMED_POLICIES[algo]
    else:
        raise ValueError(f"the reference does not model scheduler {algo!r}")
    horizon = int(round(params["duration"] * TICKS_PER_SECOND))
    NP = params["num_pools"]
    MC = params["max_containers"]
    K = params["max_assignments_per_tick"]
    factor = params["cloud_scale_max_factor"] if params["cloud_scaling"] else 1.0
    pool_cpus = params["total_cpus"] / NP
    cap_cpu = np.full(NP, pool_cpus * factor, np.float32).astype(dt)
    cap_ram = np.full(NP, params["total_ram_gb"] / NP * factor,
                      np.float32).astype(dt)
    free_cpu, free_ram = cap_cpu.copy(), cap_ram.copy()
    warm_ticks = params["container_warm_ticks"]

    pipes = Pipes(wl, dt)
    MP = pipes.n
    status = np.full(MP, PENDING, np.int64)
    entered = np.full(MP, INF_TICK, np.int64)
    waiting: set[int] = set()
    running: dict[int, Ctr] = {}
    release: dict[int, int] = {}
    free_slots = set(range(MC))
    slot_warm_pool = np.full(MC, -1, np.int64)
    slot_warm_until = np.zeros(MC, np.int64)
    completion = np.full(MP, INF_TICK, np.int64)
    fails = np.zeros(MP, np.int64)
    preempts = np.zeros(MP, np.int64)
    n = dict(done_count=0, failed_count=0, oom_events=0, preempt_events=0,
             cache_lookups=0, cold_starts=0, warm_starts=0)
    bytes_moved = np.float32(0.0)
    util_cpu_s = np.zeros(NP)
    cost = 0.0
    schedules: dict = {}

    def retire(c, t):
        free_cpu[c.pool] = dt(free_cpu[c.pool] + c.cpus)
        free_ram[c.pool] = dt(free_ram[c.pool] + c.ram)
        free_slots.add(c.slot)
        slot_warm_pool[c.slot] = c.pool
        slot_warm_until[c.slot] = t + warm_ticks
        del running[c.pid]

    arrivals = sorted((int(a), i) for i, a in enumerate(pipes.arrival)
                      if a < horizon)
    arr_ix = 0
    tick = 0
    while tick < horizon:
        while arr_ix < len(arrivals) and arrivals[arr_ix][0] <= tick:
            t_arr, pid = arrivals[arr_ix]
            arr_ix += 1
            status[pid] = WAITING
            waiting.add(pid)
            entered[pid] = t_arr
        for pid in [q for q, r in release.items() if r <= tick]:
            status[pid] = WAITING
            waiting.add(pid)
            entered[pid] = release.pop(pid)
        for c in list(running.values()):
            fire_oom = c.oom is not None and c.oom <= tick
            if not (fire_oom or c.end <= tick):
                continue
            retire(c, tick)
            pid = c.pid
            if fire_oom:
                status[pid] = WAITING
                waiting.add(pid)
                entered[pid] = tick
                pipes.failed_before[pid] = True
                fails[pid] += 1
                n["oom_events"] += 1
            else:
                status[pid] = DONE
                completion[pid] = c.end
                n["done_count"] += 1

        rejects, suspends, grants = decide(
            pol, pipes, waiting, entered, running, free_cpu, free_ram,
            cap_cpu, cap_ram, K, dt)
        acted = bool(rejects or suspends or grants)
        for pid in rejects:
            status[pid] = FAILED
            waiting.discard(pid)
            completion[pid] = tick
            n["failed_count"] += 1
        for pid in suspends:
            retire(running[pid], tick)
            status[pid] = SUSPENDED
            release[pid] = tick + 1
            preempts[pid] += 1
            n["preempt_events"] += 1
        for pid, pool, cpus, ram in grants:
            if status[pid] != WAITING or not free_slots:
                continue
            slot = min(free_slots)
            free_slots.discard(slot)
            warm = bool(slot_warm_pool[slot] == pool
                        and tick < slot_warm_until[slot])
            out_gb = pipes.out_gb[pid]
            # nothing is cached, so every input byte is read
            bytes_moved = np.float32(bytes_moved + out_gb)
            n["cache_lookups"] += int(out_gb > 0)
            n["cold_starts"] += int(not warm)
            n["warm_starts"] += int(warm)
            key = (pid, float(cpus), float(ram))
            if key not in schedules:
                schedules[key] = container_schedule(
                    pipes.levels[pid], cpus, ram, dt)
            dur, oom_off = schedules[key]
            running[pid] = Ctr(slot, pid, pool, dt(cpus), dt(ram), tick,
                               tick + dur,
                               None if oom_off is None else tick + oom_off)
            status[pid] = RUNNING
            waiting.discard(pid)
            pipes.last_cpus[pid], pipes.last_ram[pid] = cpus, ram
            pipes.failed_before[pid] = False
            free_cpu[pool] = dt(free_cpu[pool] - cpus)
            free_ram[pool] = dt(free_ram[pool] - ram)

        nxt = horizon
        if arr_ix < len(arrivals):
            nxt = min(nxt, arrivals[arr_ix][0])
        for c in running.values():
            nxt = min(nxt, c.end if c.oom is None else min(c.end, c.oom))
        for r in release.values():
            nxt = min(nxt, r)
        if acted:
            nxt = min(nxt, tick + 1)
        nxt = min(max(nxt, tick + 1), horizon)

        # integrate CPU use and its cost over [tick, nxt)
        dt_s = (nxt - tick) / TICKS_PER_SECOND
        used_cpu = np.zeros(NP)
        for c in running.values():
            used_cpu[c.pool] += float(c.cpus)
        util_cpu_s += used_cpu * dt_s
        over = np.maximum(used_cpu - pool_cpus, 0.0)
        cost += (float(np.sum(np.minimum(used_cpu, pool_cpus)
                              + params["cloud_premium_factor"] * over))
                 * params["cloud_cost_per_cpu_second"] * dt_s)
        tick = nxt

    # a pipeline that never arrived is an empty slot to the program
    status[status == PENDING] = EMPTY
    return {
        "pipe_status": status.astype(np.int32),
        "pipe_completion": completion.astype(np.int32),
        "pipe_fails": fails.astype(np.int32),
        "pipe_preempts": preempts.astype(np.int32),
        **{k: np.int32(v) for k, v in n.items()},
        "cache_hits": np.int32(0),
        "cold_start_tick_total": np.int32(0),
        "cache_hit_gb": np.float32(0.0),
        "bytes_moved_gb": bytes_moved,
        "util_cpu_s": util_cpu_s.astype(np.float32),
        "cost_dollars": np.float32(cost),
    }


def simulate(job, dtype=np.float32) -> dict:
    """The reference's final state of one sampled lane (a
    ``generator.Job``)."""
    return simulate_lane(job.params, job.workload, dtype=dtype)


def _rel_gap(a, b) -> float:
    a, b = float(a), float(b)
    return abs(a - b) / max(abs(b), 1e-30)


def compare(jobs, answers) -> dict:
    """The numbers compared, over every sampled lane:

    - ``state_mismatch``: elements of :data:`EXACT_FIELDS` that differ
      (exact: limit 0);
    - ``util_rel_gap`` / ``cost_rel_gap``: the widest relative gap of a
      lane's CPU-seconds and dollars, float sums that the program and
      the reference add up in different orders."""
    mism = 0
    util_gap = cost_gap = 0.0
    for j, r in zip(jobs, answers):
        for f in EXACT_FIELDS:
            mism += int(np.sum(np.asarray(j.program[f]) != np.asarray(r[f])))
        util_gap = max(util_gap, _rel_gap(
            np.sum(j.program["util_cpu_s"], dtype=np.float64),
            np.sum(r["util_cpu_s"], dtype=np.float64)))
        cost_gap = max(cost_gap, _rel_gap(j.program["cost_dollars"],
                                          r["cost_dollars"]))
    return dict(lanes_compared=len(jobs), state_mismatch=mism,
                util_rel_gap=util_gap, cost_rel_gap=cost_gap)
