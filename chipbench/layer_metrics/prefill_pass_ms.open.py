"""Model step: mean device time of one prefill pass of the largest bucket
(every slot padded to it), in ms: the stall every decoding slot rides
through (trace: the engine's prefill executable, launch by launch)."""


def read(run):
    if run.trace is None:
        return None
    return run.mean_pass_ms("prefill",
                            max(run.cfg["engine"]["prefill_chunks"]))
