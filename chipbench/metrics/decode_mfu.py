"""Model FLOPs of the decode steps over their span time x peak."""


def read(run):
    return run.mfu_of("decode")
