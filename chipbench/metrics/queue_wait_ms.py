"""90th percentile over submitted requests of (start of the engine.step
call that admitted it - its submit time); one not admitted by the
window's end enters at its age."""
from chipbench.layers import queue_wait_ms


def read(run):
    return queue_wait_ms(run.window, run.records, run.window.step_start)
