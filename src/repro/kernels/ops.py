"""Unified dense-matmul dispatch: the single entry point models use.

``dense(x, w, cfg, key)`` routes to:
  * ``mode="float"``       — plain matmul in the operand dtype (FLOAT baseline)
  * ``mode="abfp_ref"``    — pure-jnp scan ABFP (core.abfp.abfp_matmul)
  * ``mode="abfp_kernel"`` — fused Pallas kernel (abfp_matmul_pallas)
  * ``mode="abfp_packed"`` — packed Pallas kernel: the weight is quantized
    once (``pack_abfp_weight``) and the kernel streams int8 codes + bf16
    scales from HBM.  ``dense`` packs a raw array on the fly (so QAT code
    can flip the mode switch); ``dense_packed`` takes an already-packed
    ``PackedWeight`` — the quantize-once serving path.
  * ``mode="abfp_fused"``  — the packed path plus the paper's per-tile
    ADC gains (packed with ``adaptive_gain=True``, applied inside the
    kernel) and, at serving decode ticks, the fused QKV + attention
    kernels of ``kernels.abfp_decode_fused`` (dispatched by
    ``models.layers.attention_block``; every non-decode matmul runs the
    packed kernel with gains).

All ABFP modes carry the straight-through estimator (paper Eq. 8): the
backward pass is that of the plain matmul, accumulated in FLOAT32 — this is
what makes the same call usable for inference simulation AND for QAT.  For
``dense_packed`` the original float weight no longer exists, so the STE
weight matmul uses the dequantized lattice (the values the forward actually
multiplied by) and the packed weight itself gets a zero cotangent — packed
weights are frozen by construction.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abfp import (
    PackedWeight,
    QuantConfig,
    abfp_matmul,
    dequantize_packed,
    pack_abfp_weight,
)
from repro.kernels.abfp_matmul import (
    abfp_matmul_packed_pallas,
    abfp_matmul_pallas,
)


def _key_to_seed(key: Optional[jax.Array]) -> Optional[jax.Array]:
    """Fold a jax PRNG key into the int32 seed the Pallas hash PRNG expects."""
    if key is None:
        return None
    data = jax.random.key_data(key).astype(jnp.uint32)
    return jnp.bitwise_xor(data[..., 0], data[..., -1]).astype(jnp.int32)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dense(x: jax.Array, w: jax.Array, cfg: QuantConfig,
          key: Optional[jax.Array] = None) -> jax.Array:
    """x (..., K) @ w (K, N) -> (..., N) under the QuantConfig's mode."""
    return _dense_fwd_impl(x, w, cfg, key)


def _dense_fwd_impl(x, w, cfg, key):
    if cfg.mode == "float":
        return jnp.matmul(x, w.astype(x.dtype))
    if cfg.mode == "abfp_ref":
        return abfp_matmul(x, w, cfg, key)
    if cfg.mode == "abfp_kernel":
        return abfp_matmul_pallas(x, w, cfg, _key_to_seed(key))
    if cfg.mode in ("abfp_packed", "abfp_fused"):
        pw = pack_abfp_weight(w, cfg,
                              adaptive_gain=(cfg.mode == "abfp_fused"))
        return abfp_matmul_packed_pallas(x, pw, cfg, _key_to_seed(key))
    raise ValueError(f"unknown quant mode: {cfg.mode!r}")


def _dense_fwd(x, w, cfg, key):
    return _dense_fwd_impl(x, w, cfg, key), (x, w)


def _dense_bwd(cfg, res, g):
    # STE (Eq. 8): gradients of the un-quantized matmul, FLOAT32 accumulation.
    x, w = res
    g32 = g.astype(jnp.float32)
    dx = jnp.matmul(g32, w.astype(jnp.float32).T).astype(x.dtype)
    g2 = g32.reshape(-1, g32.shape[-1])
    x2 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    dw = jnp.matmul(x2.T, g2).astype(w.dtype)
    return dx, dw, None


dense.defvjp(_dense_fwd, _dense_bwd)


# ---------------------------------------------------------------------------
# Pre-packed weights: the quantize-once serving entry point
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dense_packed(x: jax.Array, pw: PackedWeight, cfg: QuantConfig,
                 key: Optional[jax.Array] = None) -> jax.Array:
    """x (..., K) @ packed weight (K, N) -> (..., N) via the packed kernel.

    ``pw`` is produced once by ``pack_abfp_weight`` (or ``pack_model_params``
    over a whole model); every call skips the weight max/round/clip work the
    plain kernel redoes per grid step.
    """
    return abfp_matmul_packed_pallas(x, pw, cfg, _key_to_seed(key))


def _dense_packed_fwd(x, pw, cfg, key):
    return dense_packed(x, pw, cfg, key), (x, pw)


def _dense_packed_bwd(cfg, res, g):
    # STE (Eq. 8) against the dequantized lattice; packed leaves are frozen.
    x, pw = res
    g32 = g.astype(jnp.float32)
    w = dequantize_packed(pw)                                # (K, N) f32
    dx = jnp.matmul(g32, w.T).astype(x.dtype)
    zero_codes = np.zeros(pw.codes.shape, dtype=jax.dtypes.float0)
    dpw = PackedWeight(zero_codes, jnp.zeros_like(pw.scales),
                       pw.k, pw.n_cols, pw.tile_width, pw.bits_w,
                       gains=None if pw.gains is None
                       else jnp.zeros_like(pw.gains))
    return dx, dpw, None


dense_packed.defvjp(_dense_packed_fwd, _dense_packed_bwd)


# ---------------------------------------------------------------------------
# Tensor-parallel dispatch: shard_map over the 'model' mesh axis
# ---------------------------------------------------------------------------
#
# Serving shards every dense matmul COLUMN-parallel (output features over
# 'model'): each shard runs the kernel on its slice of the weight columns
# and the results are all-gathered.  Column splits never break ABFP K-tiles
# (tiles live along the contracting dim), every output element's f32
# contraction is computed exactly as on one device, and the Pallas noise
# salts are globalized via ``col_block_offset``/``num_col_blocks`` — so
# column-parallel execution is BIT-IDENTICAL to single-device at any shard
# count, which is what makes sharded serving testable against the
# single-device engine (tests/test_sharded_serving.py).
#
# ``dense_tp_row`` is the complementary ROW-parallel (contracting-dim)
# form: x columns and weight rows sharded, partial products combined with a
# psum over 'model'.  The psum changes f32 accumulation order, so it is
# reproducible but NOT bit-identical to single-device — serving therefore
# never routes through it (the ABFP spec rules demote K-sharding anyway:
# distributed.sharding.abfp_param_spec_tree); it exists for float-mode
# training shards.
#
# Both wrappers are forward-only (the serving engine never differentiates);
# QAT keeps using ``dense``/``dense_packed``.

_MODEL_AXIS = "model"       # mirrors distributed.sharding.MODEL_AXIS
_DATA_AXES = ("pod", "data")
_LANE = 128                 # packed-weight lane alignment (core.abfp)


def tp_size(mesh) -> int:
    """Size of the 'model' axis of ``mesh`` (1 when absent / no mesh)."""
    if mesh is None or _MODEL_AXIS not in mesh.axis_names:
        return 1
    return mesh.shape[_MODEL_AXIS]


def tp_col_quantum(cfg: QuantConfig, packed: bool, tp: int) -> Optional[int]:
    """Column-count divisor a weight needs for column-sharding over ``tp``
    shards, or None when the mode can never shard.

    THE single source of the shardability rule — placement
    (``distributed.sharding.serving_param_spec_tree``) and dispatch
    (``tp_shardable``) both consult it, so a weight is stored sharded
    exactly when the matmul will consume it sharded:

    * float weights: any even column split (``tp``);
    * kernel modes with noise: every local slice must be a whole number of
      128-lane column blocks (``tp * 128``), so local Pallas grids tile
      exactly like the global grid and the globalized salts line up;
    * kernel modes without noise: any even split — per-column values are
      block-layout independent;
    * the pure-jnp scan path (``abfp_ref``) draws noise with
      shape-dependent ``jax.random`` streams that cannot be
      column-globalized — never sharded.
    """
    if packed or cfg.mode in ("abfp_kernel", "abfp_packed", "abfp_fused"):
        return tp * _LANE if cfg.noise_lsb > 0.0 else tp
    if cfg.mode == "float":
        return tp
    return None     # abfp_ref


def tp_shardable(w, cfg: QuantConfig, mesh) -> bool:
    """Can ``w`` be column-sharded over 'model' with bit-identical results?
    Only 2-D weights qualify (leading batch axes are indexed/scanned
    first); the column rule lives in ``tp_col_quantum``."""
    tp = tp_size(mesh)
    if tp <= 1 or getattr(w, "ndim", 0) != 2:
        return False
    packed = isinstance(w, PackedWeight)
    quantum = tp_col_quantum(cfg, packed, tp)
    if quantum is None:
        return False
    cols = w.n_padded if packed else w.shape[-1]
    return cols % quantum == 0


def dense_tp(x: jax.Array, w, cfg: QuantConfig,
             key: Optional[jax.Array] = None, mesh=None) -> jax.Array:
    """Column-parallel ``dense``/``dense_packed`` over the 'model' axis.

    Bit-identical to the single-device call (see module comment).  Falls
    back to the single-device path when the weight is not shardable at this
    mesh (indivisible columns, abfp_ref mode, stacked weights) — the
    fallback runs replicated, still correct at any mesh shape.  A Pallas
    kernel there runs whole on every device inside a replicated shard_map:
    the compiler cannot partition a TPU kernel, not even a replicated one.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    if not tp_shardable(w, cfg, mesh):
        packed = isinstance(w, PackedWeight)

        def whole(x_, w_, key_):
            if packed:
                return dense_packed(x_, w_, cfg, key_)
            return dense(x_, w_, cfg, key_)

        kernel = packed or cfg.mode not in ("float", "abfp_ref")
        if mesh is None or mesh.devices.size == 1 or not kernel:
            return whole(x, w, key)
        return shard_map(whole, mesh=mesh, in_specs=(P(), P(), P()),
                         out_specs=P(), check_rep=False)(x, w, key)

    tp = tp_size(mesh)
    seed = _key_to_seed(key)
    packed = isinstance(w, PackedWeight)
    mode = "packed" if packed else cfg.mode

    # Activation batch axis: shard over the data axes when possible, so a
    # dp > 1 mesh parallelizes rows instead of redundantly recomputing the
    # full batch per data group.  Row splits are bit-identity-safe only
    # while noise is OFF: the noise lattice indexes rows block-locally, so
    # a batch split would re-seat rows and change their draws (columns are
    # globalized via the salt offset; rows are not).  With noise on, x
    # stays replicated — correctness over dp-throughput.
    daxes = tuple(a for a in _DATA_AXES
                  if mesh is not None and a in mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
    batch_sharded = (cfg.noise_lsb == 0.0 and dp > 1 and x.ndim >= 2
                     and x.shape[0] % dp == 0)
    rep_x = (P(daxes, *([None] * (x.ndim - 1))) if batch_sharded
             else P(*([None] * x.ndim)))

    if mode == "packed":
        cols, n_cols = w.n_padded, w.n_cols
        nj_global, local_blocks = cols // _LANE, cols // tp // _LANE
    elif mode != "float":
        cols = n_cols = w.shape[-1]
        nj_global, local_blocks = cols // _LANE, cols // tp // _LANE

    def gather(y):
        return jax.lax.all_gather(y, _MODEL_AXIS, axis=-1, tiled=True)

    def offset():
        return jax.lax.axis_index(_MODEL_AXIS) * local_blocks

    if mode == "float":
        def body(x_, w_):
            return gather(jnp.matmul(x_, w_.astype(x_.dtype)))
        args, specs = (x, w), (rep_x, P(None, _MODEL_AXIS))
    elif mode == "packed":
        has_g = w.gains is not None

        def body(x_, codes, scales, *rest):
            # Per-tile gains live on the (replicated) K axis, so every
            # column shard amplifies with the same gain vector.
            gains = rest[0] if has_g else None
            s = rest[1:] if has_g else rest
            pw_l = PackedWeight(codes, scales, w.k, codes.shape[-1],
                                w.tile_width, w.bits_w, gains=gains)
            return gather(abfp_matmul_packed_pallas(
                x_, pw_l, cfg, s[0] if s else None,
                col_block_offset=offset(), num_col_blocks=nj_global))
        args = (x, w.codes, w.scales) \
            + ((w.gains,) if has_g else ()) \
            + (() if seed is None else (seed,))
        specs = (rep_x, P(None, _MODEL_AXIS), P(None, _MODEL_AXIS)) \
            + ((P(None),) if has_g else ()) \
            + (() if seed is None else (P(),))
    else:   # abfp_kernel
        def body(x_, w_, *s):
            return gather(abfp_matmul_pallas(
                x_, w_, cfg, s[0] if s else None,
                col_block_offset=offset(), num_col_blocks=nj_global))
        args = (x, w) + (() if seed is None else (seed,))
        specs = (rep_x, P(None, _MODEL_AXIS)) \
            + (() if seed is None else (P(),))

    out = shard_map(body, mesh=mesh, in_specs=specs,
                    out_specs=rep_x, check_rep=False)(*args)
    return out[..., :n_cols] if mode == "packed" else out


def dense_tp_row(x: jax.Array, w: jax.Array, cfg: QuantConfig,
                 mesh=None) -> jax.Array:
    """Row-parallel float matmul: contracting dim sharded over 'model',
    partials combined with a psum.  Reproducible, but NOT bit-identical to
    single-device (psum reorders the f32 reduction) — float mode only."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    if cfg.mode != "float":
        raise ValueError(
            "dense_tp_row is float-only: sharding the contracting dim "
            "splits ABFP tile accumulation across devices, breaking the "
            "per-tile ADC semantics (use column-parallel dense_tp)")
    tp = tp_size(mesh)
    if tp <= 1 or w.shape[0] % tp != 0:
        return dense(x, w, cfg, None)

    x_spec = P(*([None] * (x.ndim - 1) + [_MODEL_AXIS]))

    def body(x_, w_):
        return jax.lax.psum(jnp.matmul(x_, w_.astype(x_.dtype)), _MODEL_AXIS)

    return shard_map(body, mesh=mesh,
                     in_specs=(x_spec, P(_MODEL_AXIS, None)),
                     out_specs=P(*([None] * x.ndim)),
                     check_rep=False)(x, w)
