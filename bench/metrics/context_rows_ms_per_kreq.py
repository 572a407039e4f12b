"""Host time of the program's ``context_rows`` spans (the replay
source's host gather of the window's context rows, under ``prep``) per
1,000 requests served."""
from bench import spans


def read(run):
    return spans.ms_per_kreq(run, "context_rows")
