"""Host milliseconds per completed read that the service's dispatcher
spends enqueuing groups on the device, the programs it builds for them
included: the self time of `rapidx.serve.enqueue` in the window, from
the program's spans in the trace."""

import spans


def read(run):
    return spans.self_ms_per_read(run, "rapidx.serve.enqueue")
