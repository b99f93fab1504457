"""Queries answered per second: every query admitted in the window over
the window's length, which runs from its start to its last answer."""


def read(run):
    return run.answered / run.window_s
