"""Median ``QueryExecuted.copy_s``: from the enqueue of the inputs' copy to
the device until they are ready.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.copy_s for e in run.events_of("QueryExecuted") if hasattr(e, "copy_s")]
    if not values:
        return None
    return 1e3 * statistics.median(values)
