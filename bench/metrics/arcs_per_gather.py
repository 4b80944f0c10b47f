"""Arcs per element the in-core sparse kernel's source gather reads
(``schedule_stats["sparse_gather"]`` of the last timed trial): the mean
run of equal sources where the context holds runs, 1 where it gathers
once per arc.  Nothing to read where the plan reports no such block."""


def read(run):
    st = run.trials[-1].stats.get("sparse_gather") if run.trials else None
    if not st or not st.get("gathered"):
        return None
    return st["arcs"] / st["gathered"]
