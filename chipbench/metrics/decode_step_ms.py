"""Mean host span of the engine.step calls that ran a batched decode."""


def read(run):
    return run.step_ms("decode")
