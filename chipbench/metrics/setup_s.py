"""Set-up time: process start until the window opens (loading, weights,
compiles or cache loads, warm-up and the mix's lead-in traffic)."""


def read(run, trace):
    return run.setup_s
