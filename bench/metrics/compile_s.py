"""Host seconds of ``compile_plan`` and the warm trials, which compile
(or fetch from the persistent cache) every program the window runs."""


def read(run):
    return run.compile_s
