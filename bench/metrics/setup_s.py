"""Seconds from process start to the first timed trial: generator,
blocking, plan compilation and the warm trials."""


def read(run):
    return run.setup_s
