"""Share of the traced window of a single-statement query cell in which no operation ran
on the device."""


def read(run):
    return run.idle_percent() if run.kind == "query" else None
