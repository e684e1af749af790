"""Real (unpadded) share of the batch slots that the ``FleetEngine``
launched in the window, in %, from the engine's own counters."""


def read(run):
    return run.counters.get("serve_batch_fill")
