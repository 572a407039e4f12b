"""Host time of the program's ``gather_dispatch`` spans (the replay
source's user-id upload and its two ``replay_gather`` dispatches, under
``prep``) per 1,000 requests served."""
from bench import spans


def read(run):
    return spans.ms_per_kreq(run, "gather_dispatch")
