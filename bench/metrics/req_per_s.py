"""Requests whose answers were on the host inside the timed window, per
second of the window."""


def read(run):
    return run.requests / run.seconds
