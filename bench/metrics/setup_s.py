"""Set-up time: process start to the first due request (loading,
weights, compilation or cache loads, warm-up)."""


def read(run):
    return run.setup_s
