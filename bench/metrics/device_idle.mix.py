"""Share of the traced window of a mixed query cell in which no operation ran
on the device."""


def read(run):
    return run.idle_percent() if run.kind == "query" else None
