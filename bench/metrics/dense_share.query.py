"""Share (%) of the window's ``QueryExecuted`` events whose group-by ran
on the dense path (``group_path == "dense"``): over a static slot axis
bounded by shard statistics, with no sort.

A program whose events lack the field reports nothing."""


def read(run):
    paths = [e.group_path for e in run.events_of("QueryExecuted") if hasattr(e, "group_path")]
    if not paths:
        return None
    return 100.0 * sum(p == "dense" for p in paths) / len(paths)
