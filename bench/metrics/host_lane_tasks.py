"""Tasks per iteration that the streamed executor's host lane ran on
the CPU (``hetero["host_tasks_executed"]``, a count over the plan's
life, differenced across the window)."""


def read(run):
    key = "host_tasks_executed"
    before = run.warm_stats.get("hetero", {}).get(key)
    after = run.trials[-1].stats.get("hetero", {}).get(key)
    if before is None or after is None or not run.iterations:
        return None
    return (after - before) / run.iterations
