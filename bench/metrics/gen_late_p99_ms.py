"""99th percentile of how late the open-loop sender put a request in,
after its scheduled time, in ms (host clock): a starved sender is not a
fast server."""


def read(run):
    return run.counters.get("gen_late_p99_ms")
