"""Share of the last timed trial's levels that ran the pull step, in
percent, from the direction controller's decisions
(``schedule_stats["direction"]``).  Nothing to read where the plan
reports no direction block."""


def read(run):
    st = run.trials[-1].stats.get("direction") if run.trials else None
    if not st or not st.get("decisions"):
        return None
    return 100.0 * st["pull_iterations"] / len(st["decisions"])
