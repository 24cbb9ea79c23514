"""Seconds from process start to the first timed request: loading,
input generation, compiling or loading programs, and warm-up."""


def read(run):
    return run.setup_s
