"""Backend compile requests inside the measured window, persistent-cache
hits included, from a ``jax.monitoring`` listener of the harness."""


def read(run):
    if not run.traced:
        return None
    return run.compiles_in_window
