"""Set-up: process start to the first instant of the timed window
(imports, building the stack, weights and tables, warm-up, compiles)."""


def read(run):
    return run.setup_s
