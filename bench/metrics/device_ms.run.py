"""Median ``StageFinished.device_s``: a stage's program, from its call until
its outputs are ready on the device (the executor's timed attempt).

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.device_s for e in run.events_of("StageFinished") if hasattr(e, "device_s")]
    if not values:
        return None
    return 1e3 * statistics.median(values)
