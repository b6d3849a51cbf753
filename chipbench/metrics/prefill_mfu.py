"""Model FLOPs of the prefill-chunk steps over their span time x peak."""


def read(run):
    return run.mfu_of("chunk")
