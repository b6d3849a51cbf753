"""Mean host span of the engine.step calls that ran a prefill chunk."""


def read(run):
    return run.step_ms("chunk")
