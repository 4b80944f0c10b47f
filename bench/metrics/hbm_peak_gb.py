"""The device allocator's peak bytes in use after the window, on the
fullest chip, in GB (1e9 bytes)."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
