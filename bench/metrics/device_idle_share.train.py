"""Share of the traced training window in which no operation ran on the
device, in %: 1 minus the union of the device's op intervals over the
window, averaged over the chips."""


def read(run):
    t = run.reduced_trace
    if t is None or not any(t.ops.values()):
        return None
    return 100.0 * t.idle_share
