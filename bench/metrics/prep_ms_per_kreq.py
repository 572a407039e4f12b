"""Host time of the program's ``prep`` spans (arrival sampling, user
rows, scoring dispatch, table compaction) per 1,000 requests served."""


def read(run):
    if run.trace is None or run.requests == 0:
        return None
    prep = run.trace.host_s.get("prep")
    if prep is None:
        return None
    return prep * 1e3 / (run.requests / 1e3)
