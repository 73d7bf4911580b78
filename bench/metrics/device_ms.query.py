"""Median ``QueryExecuted.device_s``: the call of the compiled program until
its outputs are ready on the device.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.device_s for e in run.events_of("QueryExecuted") if hasattr(e, "device_s")]
    if not values:
        return None
    return 1e3 * statistics.median(values)
