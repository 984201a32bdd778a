"""Model step: mean device time of one decode pass, in ms (trace: the
engine's decode executable, launch by launch)."""


def read(run):
    return run.mean_pass_ms("decode")
