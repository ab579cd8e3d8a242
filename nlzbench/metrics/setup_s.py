"""Set-up: process start to window start (loading, data, warm-up and, in a
run that compiles, compilation)."""


def read(run):
    return run.setup_s
