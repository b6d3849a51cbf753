"""Share of the time inside engine.step spans with no device op running
(profiler trace), for the cells that serve arrivals."""


def read(run):
    return run.trace.get("step_idle_pct") if run.trace else None
