"""Set-up: from the start of the process to the window's first arrival."""


def read(run):
    return run.setup_s
