"""Seconds from process start to the window's start: start-up, data,
build, compact, set-up writes, warm-up and, on a cold cache, compiling."""


def read(run):
    return run.setup_s
