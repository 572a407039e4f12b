"""Device time of the serving pipeline's programs (the fused pass and the
nearline dual update, both compiled as ``jit_fn``) per 1,000 requests."""


def read(run):
    if run.trace is None or run.requests == 0:
        return None
    s = run.trace.program_s("jit_fn")
    if s <= 0:
        return None
    return s * 1e3 / (run.requests / 1e3)
