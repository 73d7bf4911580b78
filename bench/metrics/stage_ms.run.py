"""Median ``StageFinished.exec_s``: a stage's scan, execution and
artifact writes."""
import statistics


def read(run):
    events = run.events_of("StageFinished")
    if not events:
        return None
    return 1e3 * statistics.median(e.exec_s for e in events)
