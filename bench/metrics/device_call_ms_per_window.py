"""The ``greedy_window`` span per window that made the call: host->device
transfer, dispatch, the scan and the readback (host spans of a traced
run)."""


def read(run):
    calls = [w for w in run.spans if w.device_calls]
    if not calls:
        return None
    return 1e3 * sum(w.device_s for w in calls) / len(calls)
