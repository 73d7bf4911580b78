"""Share of the device's busy time that a single-statement cell's queries
need at least (``Measured.roofline_percent``)."""


def read(run):
    return run.roofline_percent() if run.kind == "query" else None
