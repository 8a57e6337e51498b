"""Set-up: process start to the first timed operation (JAX and TPU
start-up, cache loads, the data set the traffic needs, warm-up)."""


def read(run):
    return run.setup_s
