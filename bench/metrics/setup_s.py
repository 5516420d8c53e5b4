"""Set-up: process start to the window's start — inputs and weights made
from the seed, every program compiled (or read from the compile cache)
and warmed up."""


def read(run):
    return run.setup_s
