"""Device time of the placement scan's program (``_greedy_scan``) per run
of it, from the profiler trace's program events."""

SCAN = "_greedy_scan"


def scan_runs(run):
    """(device seconds, runs) of the scan program in the traced window."""
    if run.trace is None:
        return 0.0, 0
    secs = sum(v for k, v in run.trace.module_s.items() if SCAN in k)
    n = sum(v for k, v in run.trace.module_n.items() if SCAN in k)
    return secs, n


def read(run):
    secs, n = scan_runs(run)
    if n == 0:
        return None
    return 1e3 * secs / n
