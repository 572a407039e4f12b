"""Device time of the serving pipeline's online pass (reward model,
Eq. 10, router, guard, execution; compiled as ``jit_fused_pass``) per
1,000 requests served."""


def read(run):
    if run.trace is None or run.requests == 0:
        return None
    s = run.trace.program_s("jit_fused_pass")
    if s <= 0:
        return None
    return s * 1e3 / (run.requests / 1e3)
