"""Programs built while the service finalizes groups in the window: the
stat `builds` summed over the `rapidx.serve.finalize` spans that overlap
it (a span that crosses an edge counts whole), from the program's spans
in the trace."""

import spans

NAME = "rapidx.serve.finalize"


def read(run):
    if run.trace is None or not any(sp[3] == NAME
                                    for sp in run.trace["spans"]):
        return None
    return spans.stat_sum(run.trace["spans"], NAME, "builds")
