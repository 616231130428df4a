"""Set-up: process start to the start of the measured window (host clock).
Holds imports, reaching the chip, loading or compiling the programs, the
warm-up windows and building the traffic."""


def read(run):
    return run.setup_s
