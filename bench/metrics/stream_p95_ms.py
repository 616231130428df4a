"""95th percentile of submit-to-placement over every task due in the
window: from the task's due time to the return of the flush that placed
it, tasks placed after the close included (host clock).  A task never
placed counts to the end of the run."""
import numpy as np


def read(run):
    if run.latencies_s is None or len(run.latencies_s) == 0:
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
