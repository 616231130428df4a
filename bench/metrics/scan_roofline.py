"""The placement scan's share of its roofline: the least time the chip
could place the traced device windows in (``bench/work.py``, from the
true shapes: heuristics x tasks x endpoints) over the scan program's
device time in the trace."""
from bench import work
from bench.metrics.scan_device_ms_per_window import scan_runs


def read(run):
    secs, n = scan_runs(run)
    tasks = sum(w.tasks for w in run.spans if w.device_calls)
    if n == 0 or secs <= 0 or tasks == 0:
        return None
    flops, nbytes = work.scan_work(run.heuristics, tasks, run.endpoints)
    t, _ = work.roofline_s(flops, nbytes, run.peak)
    return 100.0 * t / secs
