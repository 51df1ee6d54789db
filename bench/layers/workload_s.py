"""workload_s: host seconds per call spent building the call's workload
(the benchmark's ``workload_build`` spans, each ending in
``block_until_ready``): ``make_workload_batch`` or
``generate_workload``, as the cell's kind of call builds it."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1 in ctx.spans
                if name == "workload_build")
    if ctx.calls == 0 or total == 0:
        return None
    return total / ctx.calls
