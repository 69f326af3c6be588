"""Set-up: process start to window start (data generation, compile or
cache load, warm-up).  Host clock."""


def read(run):
    return run.setup_s
