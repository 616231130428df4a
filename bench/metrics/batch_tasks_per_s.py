"""Tasks placed by the whole windows of a closed loop, over the time from
the window's start to the return of its last window (host clock)."""


def read(run):
    if run.kind != "closed_batch" or run.window_s <= 0:
        return None
    return run.placed_in_window / run.window_s
