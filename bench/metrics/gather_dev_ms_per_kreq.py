"""Device time of the replay source's row gathers (the program
``replay_gather``, compiled as ``jit_replay_gather``) per 1,000
requests served."""


def read(run):
    if run.trace is None or run.requests == 0:
        return None
    s = run.trace.program_s("jit_replay_gather")
    if s <= 0:
        return None
    return s * 1e3 / (run.requests / 1e3)
