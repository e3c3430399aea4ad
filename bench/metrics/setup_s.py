"""Set-up: process start to the first measured step (host clock)."""


def read(run, peaks):
    return run.setup_s
