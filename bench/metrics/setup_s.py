"""Set-up seconds: process start to the window's start (imports, device
start, the seeded weights, engine build, warm-up and any compiling)."""


def read(run):
    return run.setup_s
