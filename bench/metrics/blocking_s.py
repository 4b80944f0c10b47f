"""Host seconds of the program's ``build_block_store`` on the graph."""


def read(run):
    return run.blocking_s
