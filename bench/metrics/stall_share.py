"""Share of the traced window the serving thread spent waiting for the
prefetch thread's next window (WindowResult.stall_ms)."""


def read(run):
    stall = sum(w.result.stall_ms for w in run.windows)
    return 100.0 * stall / 1e3 / run.seconds
