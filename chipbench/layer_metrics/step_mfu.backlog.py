"""Model step: model FLOPs of every real token in the traced window over
the window's length, as a percentage of the chip's int8 peak."""


def read(run):
    return run.window_mfu()
