"""Scheduler host work per window: ``scheduling_s`` minus the span of the
device call ``greedy_window`` — window prep, normalizers, orders, the
winner's recompute and write-back; a window handed to the host engine
counts whole (host spans of a traced run)."""


def read(run):
    if not run.spans:
        return None
    return 1e3 * sum(w.sched_s - w.device_s for w in run.spans) / len(run.spans)
