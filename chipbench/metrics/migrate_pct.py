"""Share of ``engine.step`` time spent in the program's ``step.migrate``
spans (expert moves after a placement update)."""


def read(run):
    return run.layer("migrate_pct")
