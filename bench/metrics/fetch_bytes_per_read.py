"""Device-to-host bytes the service fetched in the window per completed
read (delta of its `bytes_fetched` counter)."""


def read(run):
    return run.record["counters"]["bytes_fetched"] / max(
        run.record["reads"], 1)
