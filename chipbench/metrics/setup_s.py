"""Set-up: process start to the window's opening (weights, deploy,
compile or compile-cache load, warm-up)."""


def read(run):
    return run.setup_s
