"""Set-up: process start to the first timed request (weights, install,
compilation or cache load, warm-up)."""


def read(run):
    return run.setup_s
