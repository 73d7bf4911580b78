"""Seconds per ``Client.run``: from the window's open to the return of
the last run started in it, over the number of runs."""


def read(run):
    if run.kind != "run" or not run.requests:
        return None
    return (run.requests[-1].end - run.window_open) / len(run.requests)
