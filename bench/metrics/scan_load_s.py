"""Set-up spent in the placement scan's first call per shape signature: a
backend compile or a load from the persistent compile cache, plus one
execution (``ops.COMPILE_STATS["seconds"]`` after the run; the warm-up
makes every first call)."""


def read(run):
    from repro.kernels.placement import ops

    secs = ops.COMPILE_STATS.get("seconds", 0.0)
    return secs if secs > 0 else None
