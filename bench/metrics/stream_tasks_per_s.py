"""Tasks whose flush returned before the window closed, over the window's
seconds (host clock).  Below the knee it equals the offered rate."""


def read(run):
    if run.kind != "open_poisson":
        return None
    return run.placed_in_window / run.seconds
