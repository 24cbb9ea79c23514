"""Host milliseconds per completed read that the service's dispatcher
spends fetching a group's results, its wait on the device included: the
self time of `rapidx.serve.fetch` in the window, from the program's
spans in the trace."""

import spans


def read(run):
    return spans.self_ms_per_read(run, "rapidx.serve.fetch")
