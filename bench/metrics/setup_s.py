"""Set-up time: process start to the window's opening. It holds the weights'
making, every compile (or compile-cache load), the engine's calibration, the
warm-up and the lead-in that fills the batch."""


def read(run):
    return run.setup_s
