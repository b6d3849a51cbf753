"""Mean device time of one run of the decode step program
(``jit_decode_step`` in the trace's XLA modules)."""


def read(run):
    return run.layer("decode_device_ms")
