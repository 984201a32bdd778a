"""Kernels: roofline share of the int8-KV decode attention kernel: the
least time the live K/V codes and scales need at HBM bandwidth over the
kernel's device time in the trace, percent."""


def read(run):
    return run.attention_roofline()
