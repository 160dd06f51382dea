"""State runtime: bytes the tiers moved in the window (``TransferMeter``
``bytes_host + bytes_fabric`` delta), in MB (1e6 bytes) per second of
window."""


def read(run):
    return run.counters["tier_bytes"] / run.seconds / 1e6
