"""Seconds from the process's start to the window's: imports, weights and
inputs made on the device, the replica's compile or cache load, warm-up."""


def read(run):
    return run.setup_s
