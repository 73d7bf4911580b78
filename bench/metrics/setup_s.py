"""Set-up: process start to the first timed request, compiling included."""


def read(run):
    return run.setup_s
