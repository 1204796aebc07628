"""Process start to the first timed solve: data on the device, warm-up
solve, compilations."""


def read(run):
    return run.setup_s
