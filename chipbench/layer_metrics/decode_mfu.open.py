"""Model step: model FLOPs of the real rows of the traced decode passes
over their device time, as a percentage of the chip's int8 peak."""


def read(run):
    return run.pass_mfu(("decode",))
