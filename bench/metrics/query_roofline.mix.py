"""Share of the device's busy time that a mixed cell's queries, of every
operation type, need at least (``Measured.roofline_percent``)."""


def read(run):
    return run.roofline_percent() if run.kind == "query" else None
