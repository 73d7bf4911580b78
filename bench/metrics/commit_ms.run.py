"""Median ``StageCommitted.commit_s``: a stage's catalog commit."""
import statistics


def read(run):
    events = run.events_of("StageCommitted")
    if not events:
        return None
    return 1e3 * statistics.median(e.commit_s for e in events)
