"""Bytes the placement scan's device windows move per window: the
host->device transfer of its streams, carry seeds and tables plus the
device->host read of its carry and outputs, from the program's own
counters (``ops.WINDOW_STATS``, reset at the window's start)."""


def read(run):
    from repro.kernels.placement import ops

    st = ops.WINDOW_STATS
    if "h2d_bytes" not in st or st["device"] == 0:
        return None
    return (st["h2d_bytes"] + st["d2h_bytes"]) / st["device"]
