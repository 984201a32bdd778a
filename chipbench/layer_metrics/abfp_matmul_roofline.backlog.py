"""Kernels: roofline share of the ABFP matmul kernels (packed and fused
QKV): the least time their work needs at the int8 peak and HBM bandwidth
(work from chipbench.work) over their device time in the trace, percent."""


def read(run):
    return run.matmul_roofline()
