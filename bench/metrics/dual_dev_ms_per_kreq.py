"""Device time of the nearline dual update (Algorithm 1, compiled as
``jit_dual_update``) per 1,000 requests served."""


def read(run):
    if run.trace is None or run.requests == 0:
        return None
    s = run.trace.program_s("jit_dual_update")
    if s <= 0:
        return None
    return s * 1e3 / (run.requests / 1e3)
