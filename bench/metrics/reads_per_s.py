"""Reads completed per second: every read the window completed, over
all of the window's time (it ends when the last work started inside it
completes)."""


def read(run):
    return run.record["reads"] / run.window_s
