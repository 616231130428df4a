"""Engine bookkeeping per window: the ``OnlineEngine.flush`` span minus
the engine's own ``scheduling_s`` (host spans of a traced run)."""


def read(run):
    if not run.spans:
        return None
    return 1e3 * sum(w.flush_s - w.sched_s for w in run.spans) / len(run.spans)
