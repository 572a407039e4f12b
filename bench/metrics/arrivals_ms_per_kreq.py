"""Host time of the program's ``arrivals`` spans (the request source's
arrival sampling, under ``prep`` on the prefetch thread) per 1,000
requests served."""
from bench import spans


def read(run):
    return spans.ms_per_kreq(run, "arrivals")
