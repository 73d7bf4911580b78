"""Median ``StageFinished.write_s``: a stage's artifact writes.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.write_s for e in run.events_of("StageFinished") if hasattr(e, "write_s")]
    if not values:
        return None
    return 1e3 * statistics.median(values)
