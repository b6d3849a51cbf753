"""Process start to the window's start: building, compiling, warming up."""


def read(run):
    return run.setup_s
