"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS for 512 placeholder devices *before* first jax init, and smoke
tests/benches must keep seeing 1 device.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``-typed.

    The sharding rules here (``distributed.sharding``) place arrays with
    ``NamedSharding`` and leave the partitioning of everything in between
    to the compiler.  ``jax.make_mesh``'s own default types the axes
    Explicit, under which ops such as the embedding gather or a KV-cache
    scatter refuse operands sharded that way.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e); 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
HBM_PER_CHIP = 16 * 2**30         # 16 GiB
