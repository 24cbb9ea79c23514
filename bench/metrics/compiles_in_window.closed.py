"""Programs built inside the measured window: compiled, or loaded from
the persistent cache (none is the aim: every program should be built in
set-up)."""


def read(run):
    return float(run.compiles_in_window)
