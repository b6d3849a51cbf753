"""Mean self time of the program's ``step.observe`` span (pricing the
step, feeding the placement controller) less its ``step.migrate``
children."""


def read(run):
    return run.layer("control_ms")
