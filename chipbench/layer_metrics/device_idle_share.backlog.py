"""Device: percentage of the traced window in which no operation ran on
the device (1 - union of operation intervals over the window)."""


def read(run):
    return run.idle_share()
