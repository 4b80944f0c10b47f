"""Host seconds per iteration that the streamed executor spent
assembling wave slabs (``phase_seconds["assemble"]``), over the window's
trials."""


def read(run):
    asm = [t.stats.get("streaming", {}).get("phase_seconds", {})
           .get("assemble") for t in run.trials]
    if None in asm or not run.iterations:
        return None
    return sum(asm) / run.iterations
