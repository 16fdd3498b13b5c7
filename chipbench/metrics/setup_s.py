"""Set-up seconds: from the start of the process's run to the first request
of the window: imports, device start, weights, compiles (or compile-cache
loads) and the warm-up runs of every program the window uses."""


def read(run):
    return run.setup_s
