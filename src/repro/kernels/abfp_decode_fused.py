"""Fused ABFP decode-step kernels: QKV projections + quantized-KV attention.

The serving decode hot path was a CHAIN of dispatches per attention block —
three separate ``abfp_matmul_packed_pallas`` launches for the Q/K/V
projections, a jnp attention over the int8 KV cache, and a fourth launch for
the output projection.  This module fuses the chain's front end into two
Pallas kernels:

``fused_qkv_packed_pallas``
    ONE weight-stationary launch over the three packed projection weights.
    Following Drumond et al.'s hybrid-BFP dot-product tiling (PAPERS.md:
    "Training DNNs with Hybrid Block Floating Point"), the weights stay
    resident over the tile grid while the (tiny, m = batch) decode
    activation block streams against them: the kernel concatenates the
    lane-aligned column blocks of wq|wk|wv into one logical weight and runs
    the SAME grid cells the three separate launches would run — same block
    sizes, same ``_abfp_contrib`` core, same noise salts (re-derived
    per-segment via the explicit ``idx`` coordinates) — so the fused output
    is bit-identical to the separate calls BY CONSTRUCTION, while paying one
    kernel launch instead of three.

``fused_quantized_decode_attention``
    A (B,)-grid Pallas kernel computing decode attention directly on the
    int8 KV codes, mirroring ``models.layers.quantized_decode_attention``
    op-for-op.  A decode tick has a single query row, so the online-softmax
    running max / denominator of ``flash_attention.py`` collapses to one
    masked softmax over the whole (cache-resident) key axis; the kernel
    keeps that degenerate form explicit so the scores/PV contractions and
    the masking constant match the jnp reference bit-for-bit.

Gain / amplification (the paper's headline knob) rides along: packed
weights carry per-tile ADC gains (``PackedWeight.gains``, derived by
``core.abfp.adaptive_tile_gains``) and the shared ``_abfp_contrib`` core
amplifies each tile's partial product before the output quantizer and
divides it back out of the Eq. 6 sum — see ``core/abfp.py`` and
``docs/NUMERICS.md`` for the exact equations.

Tensor-parallel dispatch (``fused_qkv_dense``) mirrors ``kernels.ops
.dense_tp``: the three weights column-shard over the 'model' axis, each
shard runs the fused kernel on its local column blocks with per-segment
globalized noise salts, and the outputs are all-gathered — bit-identical to
single-device at any (dp, tp) mesh shape (tests/test_sharded_serving.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.abfp import PackedWeight, QuantConfig, code_dtype
from repro.kernels.abfp_matmul import (
    DEFAULT_BN,
    _abfp_contrib,
    _ceil_to,
    _seed_smem,
    auto_bm,
    default_bk,
    k_blocked,
)

_MODEL_AXIS = "model"       # mirrors kernels.ops._MODEL_AXIS


# ---------------------------------------------------------------------------
# Fused QKV projection kernel
# ---------------------------------------------------------------------------


def _fused_qkv_kernel(
    seed_ref,  # SMEM (3, 2) int32: [seed, col-block offset] per segment
    x_ref,     # VMEM (bm, bk) f32
    wc_ref,    # VMEM (bk, bn) int8 codes (concatenated segments)
    sw_ref,    # VMEM (1, tk, bn) scales
    *refs,     # [g_ref (1, 1, tk, 1, 1) f32 gains]  o_ref (bm, bn)  acc
    cfg: QuantConfig,
    tk: int,
    n: int,
    seg_starts: Tuple[int, int, int],
    seg_nj: Tuple[int, int, int],
    has_gains: bool,
):
    """Fused-QKV kernel body.

    Identical to ``_abfp_matmul_packed_kernel`` except that the column-block
    axis spans three weight segments: the body resolves which segment this
    grid step belongs to (static boundaries ``seg_starts``) and hands
    ``_abfp_contrib`` the segment's OWN coordinates — its seed, its global
    column-block count ``seg_nj[s]`` and its local block index (plus the
    tensor-parallel offset) — so every noise draw matches the draw the
    stand-alone packed kernel makes for that (weight, block).
    """
    if has_gains:
        g_ref, o_ref, acc_ref = refs
        g = g_ref[0, 0].astype(jnp.float32)                 # (tk, 1, 1)
    else:
        o_ref, acc_ref = refs
        g = None

    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bk = x_ref.shape
    bn = wc_ref.shape[1]

    xt = x_ref[...].astype(jnp.float32).reshape(bm, tk, n)
    cdt = code_dtype(max(cfg.bits_x, cfg.bits_w))
    wq = wc_ref[...].astype(cdt).reshape(tk, n, bn)
    sw = sw_ref[0].astype(jnp.float32)

    # Segment bookkeeping: scalar selects on the (static) boundaries.  The
    # per-segment SMEM rows carry [seed, tensor-parallel col-block offset].
    i = pl.program_id(0)
    jj = pl.program_id(1)
    in1 = (jj >= seg_starts[1])
    in2 = (jj >= seg_starts[2])

    def _sel(a0, a1, a2):
        return jnp.where(in2, a2, jnp.where(in1, a1, a0))

    seed_val = _sel(seed_ref[0, 0], seed_ref[1, 0], seed_ref[2, 0])
    off = _sel(seed_ref[0, 1], seed_ref[1, 1], seed_ref[2, 1])
    start = _sel(jnp.int32(seg_starts[0]), jnp.int32(seg_starts[1]),
                 jnp.int32(seg_starts[2]))
    nj_g = _sel(jnp.int32(seg_nj[0]), jnp.int32(seg_nj[1]),
                jnp.int32(seg_nj[2]))
    j_local = jj - start + off

    acc_ref[...] += _abfp_contrib(
        xt, wq, sw, seed_ref, cfg, tk, n, g=g,
        idx=(i, j_local, k, nk, nj_g, seed_val))

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _validate_fused_pws(pws, cfg: QuantConfig, bn: int) -> None:
    """Shared-shape validation for the three fused projection weights."""
    if len(pws) != 3:
        raise ValueError(f"fused QKV takes exactly 3 PackedWeights, "
                         f"got {len(pws)}")
    k_dim = pws[0].k
    n_gains = sum(pw.gains is not None for pw in pws)
    if n_gains not in (0, 3):
        raise ValueError("fused QKV weights must all carry gains or none")
    for pw in pws:
        if pw.codes.ndim != 2:
            raise ValueError(f"fused kernel takes 2-D PackedWeights, got "
                             f"codes {pw.codes.shape}")
        if pw.k != k_dim:
            raise ValueError(f"fused QKV weights must share K: "
                             f"{pw.k} != {k_dim}")
        if pw.tile_width != cfg.tile_width or pw.bits_w != cfg.bits_w:
            raise ValueError(
                f"PackedWeight(n={pw.tile_width}, bits_w={pw.bits_w}) does "
                f"not match cfg(n={cfg.tile_width}, bits_w={cfg.bits_w})")
        if pw.scales.dtype != jnp.dtype(cfg.scale_dtype):
            raise ValueError(
                f"PackedWeight scales are {pw.scales.dtype} but "
                f"cfg.scale_dtype is {jnp.dtype(cfg.scale_dtype)}")
        if pw.n_padded % bn != 0:
            raise ValueError(
                f"fused kernel needs every weight's padded columns to be a "
                f"multiple of bn={bn} (got {pw.n_padded}) so the segment "
                f"boundaries fall on block edges")
    if cfg.noise_lsb > 0.0 and bn % 128 != 0:
        raise ValueError(
            f"noise_lsb > 0 requires bn to be a multiple of 128 (got "
            f"bn={bn}): other widths change the per-weight grids vs the "
            f"stand-alone packed kernel and break noise bit-identity")


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "bm", "bn", "bk", "interpret", "num_col_blocks"),
)
def fused_qkv_packed_pallas(
    x: jax.Array,
    pws: Sequence[PackedWeight],
    cfg: QuantConfig,
    seeds: Optional[Sequence[Optional[jax.Array]]] = None,
    *,
    bm: Optional[int] = None,
    bn: int = DEFAULT_BN,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
    col_block_offsets: Optional[Sequence[jax.Array]] = None,
    num_col_blocks: Optional[Tuple[int, int, int]] = None,
):
    """Three packed ABFP projections of one activation in ONE Pallas launch.

    ``x``: (..., K); ``pws``: (wq, wk, wv) 2-D PackedWeights sharing K and
    the cfg's tile geometry; ``seeds``: one int32 noise seed per projection
    (each the seed the stand-alone call for that weight would receive), or
    None when ``cfg.noise_lsb == 0``.  Returns the tuple
    ``(x @ wq, x @ wk, x @ wv)`` with each output sliced to its weight's
    logical columns.

    Bit-identical to three ``abfp_matmul_packed_pallas`` calls at the same
    (bm, bn, bk): the fused grid is the disjoint union of the three
    per-weight grids (same defaults — ``bm = auto_bm(m)``,
    ``bk = default_bk(n, K)`` depend only on shared quantities) and each
    grid cell runs the identical ``_abfp_contrib`` block with the segment's
    own noise coordinates.  What changes is dispatch: one weight-stationary
    launch streaming all three weights instead of three launches re-staging
    the same activation block.

    ``col_block_offsets`` / ``num_col_blocks`` (one per segment): the
    tensor-parallel salt globalization of ``abfp_matmul_packed_pallas``,
    applied per weight — see ``fused_qkv_dense``.
    """
    pws = tuple(pws)
    _validate_fused_pws(pws, cfg, bn)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = cfg.tile_width
    k_dim = pws[0].k
    if x.shape[-1] != k_dim:
        raise ValueError(f"x K dim {x.shape[-1]} != packed weight K {k_dim}")
    if bk is None:
        bk = default_bk(n, k_dim)
    assert bk % n == 0, (bk, n)

    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, k_dim).astype(jnp.float32)
    m_dim = x2.shape[0]
    if bm is None:
        bm = auto_bm(m_dim)

    kp0 = pws[0].kp
    mp, kp = _ceil_to(m_dim, bm), _ceil_to(kp0, bk)
    x2 = jnp.pad(x2, ((0, mp - m_dim), (0, kp - k_dim)))

    # Concatenate the three weights' column blocks into one logical weight.
    # Each segment is already lane-aligned from pack time; K rows pad to the
    # shared kp exactly as the stand-alone wrapper pads them (code 0 under
    # scale 0: exact no-ops).
    has_gains = pws[0].gains is not None
    njs = tuple(pw.n_padded // bn for pw in pws)
    seg_starts = (0, njs[0], njs[0] + njs[1])
    seg_nj = tuple(num_col_blocks) if num_col_blocks is not None else njs
    nj_tot = sum(njs)
    nk, tk = kp // bk, bk // n

    wcs, sws, gcols = [], [], []
    for pw, nj_s in zip(pws, njs):
        wc, sw = pw.codes, pw.scales
        if kp > kp0:
            wc = jnp.pad(wc, ((0, kp - kp0), (0, 0)))
            sw = jnp.pad(sw, ((0, (kp - kp0) // n), (0, 0)))
        wcs.append(wc)
        sws.append(sw)
        if has_gains:
            gp = jnp.pad(pw.gains.astype(jnp.float32),
                         (0, kp // n - pw.num_tiles), constant_values=1.0)
            gcols.append(jnp.repeat(gp[:, None], nj_s, axis=1))
    wc = jnp.concatenate(wcs, axis=1)                  # (kp, nj_tot * bn)
    sw = jnp.concatenate(sws, axis=1)                  # (kp/n, nj_tot * bn)

    if seeds is None:
        seeds = (None, None, None)
    offs = (col_block_offsets if col_block_offsets is not None
            else (None, None, None))
    seed = jnp.stack([_seed_smem(s, cfg.noise_lsb, o)
                      for s, o in zip(seeds, offs)])   # (3, 2) int32

    grid = (mp // bm, nj_tot, nk)
    kernel = functools.partial(
        _fused_qkv_kernel, cfg=cfg, tk=tk, n=n,
        seg_starts=seg_starts, seg_nj=seg_nj, has_gains=has_gains)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # seeds
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),        # x
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),        # codes
        pl.BlockSpec((1, tk, bn), lambda i, j, k: (k, 0, j)),  # scales
    ]
    inputs = [seed, x2, wc, k_blocked(sw, nk)]
    if has_gains:
        # Per-(tile, column-block) gains: column j of the (T, nj_tot) table
        # is the owning segment's per-tile gain vector, so each grid cell
        # reads its own segment's gains, blocked over K as the stand-alone
        # packed kernel blocks them.
        gcol = jnp.concatenate(gcols, axis=1)          # (kp/n, nj_tot)
        gcol = gcol.reshape(nk, tk, nj_tot).transpose(0, 2, 1)
        in_specs.append(pl.BlockSpec((1, 1, tk, 1, 1),
                                     lambda i, j, k: (k, j, 0, 0, 0)))
        inputs.append(gcol.reshape(nk, nj_tot, tk, 1, 1))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, nj_tot * bn), cfg.out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)

    outs = []
    col = 0
    for pw, nj_s in zip(pws, njs):
        seg = out[:m_dim, col:col + pw.n_cols]
        outs.append(seg.reshape(*batch_shape, pw.n_cols))
        col += nj_s * bn
    return tuple(outs)


# ---------------------------------------------------------------------------
# Fused quantized-KV decode attention
# ---------------------------------------------------------------------------


def _fused_attn_kernel(len_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                       o_ref):
    """Decode attention on int8 KV codes for one (batch element, KV head).

    Mirrors ``models.layers.quantized_decode_attention`` op-for-op for the
    ``rep`` query heads sharing this KV head: scores contract head_dim
    against the raw int8 codes, the per-position scales factor out of both
    contractions, masked positions get the same -1e30 the jnp path uses,
    and the single query row makes the flash-attention online softmax
    (``flash_attention.py``) degenerate to one ``jax.nn.softmax`` over the
    key axis.
    """
    b = pl.program_id(0)
    d = q_ref.shape[-1]
    s_max = kc_ref.shape[0]
    rep = q_ref.shape[0]

    qf = q_ref[...].astype(jnp.float32) * (d ** -0.5)           # (rep, d)
    kc = kc_ref[...].astype(jnp.float32)                        # (s, d)
    s = jax.lax.dot_general(
        qf, kc, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (rep, s)
    s = s * (ks_ref[...].astype(jnp.float32) / 127.0)           # (1, s)
    pos = jax.lax.broadcasted_iota(jnp.int32, (rep, s_max), 1)
    s = jnp.where(pos < len_ref[b], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)                              # (rep, s)
    pv = p * (vs_ref[...].astype(jnp.float32) / 127.0)
    out = jax.lax.dot_general(
        pv, vc_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (rep, d)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_quantized_decode_attention(
    q: jax.Array,
    k_codes: jax.Array, k_scale: jax.Array,
    v_codes: jax.Array, v_scale: jax.Array,
    *,
    lengths: jax.Array,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas decode attention over the int8 KV cache, one grid cell per
    (batch element, KV head).

    Same signature and bit-identical output as
    ``models.layers.quantized_decode_attention`` (enforced by
    tests/test_fused.py); the cache is read once as int8 blocks instead of
    traversing XLA's intermediate materializations of the batched einsum
    chain.  ``q``: (B, 1, H, D); codes: (B, S, KH, D) int8; scales:
    (B, S, KH); ``lengths``: (B,) int32 filled-slot counts.

    Each cell holds one head's (S, D) code planes in fast memory.  The TPU
    tiles the two minor axes of a block: a cache row as stored, (S, KH, D),
    pads KH up to a sublane tile and D up to 128 lanes, which at S = 2048 no
    longer fits; and one of KH rows cannot be a block of it.  So the cache
    is handed over head-major, (B, KH, S, D), a copy per call.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, _, h, d = q.shape
    s_max, kh = k_codes.shape[1], k_codes.shape[2]
    rep = h // kh
    kc = jnp.swapaxes(k_codes, 1, 2)
    vc = jnp.swapaxes(v_codes, 1, 2)
    # (S,) scale rows get a unit sublane axis, for the same reason.
    ks = k_scale.transpose(0, 2, 1).reshape(b, kh, 1, s_max)
    vs = v_scale.transpose(0, 2, 1).reshape(b, kh, 1, s_max)
    head = pl.BlockSpec((None, None, rep, d), lambda i, g: (i, g, 0, 0))
    codes = pl.BlockSpec((None, None, s_max, d), lambda i, g: (i, g, 0, 0))
    scale = pl.BlockSpec((None, None, 1, s_max), lambda i, g: (i, g, 0, 0))
    out = pl.pallas_call(
        _fused_attn_kernel,
        grid=(b, kh),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),         # lengths
                  head, codes, scale, codes, scale],
        out_specs=head,
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(b, kh, rep, d), kc, ks, vc, vs)
    return out.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# Dispatch: single-device / tensor-parallel fused QKV
# ---------------------------------------------------------------------------


def fused_qkv_dense(x, pws, cfg: QuantConfig, keys, mesh=None):
    """Numerics-level dispatch for the fused QKV projection.

    ``keys``: one jax PRNG key (or None) per projection — EXACTLY the keys
    the three consecutive ``Numerics.dense`` calls of the packed chain
    would fold (models/layers.py threads them); they become the kernel's
    per-segment noise seeds.  Routing mirrors ``kernels.ops.dense_tp``:

    * no mesh / tp == 1 — one fused launch;
    * tp > 1 and all three weights column-shardable — shard_map over
      'model': each shard fuses its LOCAL column blocks of all three
      weights with per-segment globalized salts, then all-gathers each
      output (bit-identical to single-device, as for ``dense_tp``);
    * otherwise — per-weight ``dense_tp`` (the packed chain's own dispatch,
      with its replicated fallback), keeping fused mode correct at every
      mesh shape.
    """
    from repro.kernels.ops import _key_to_seed, dense_tp, tp_shardable, tp_size

    tp = tp_size(mesh)
    if tp > 1:
        if all(tp_shardable(pw, cfg, mesh) for pw in pws):
            return _fused_qkv_tp(x, pws, cfg,
                                 [_key_to_seed(k) for k in keys], mesh)
        return tuple(dense_tp(x, pw, cfg, key, mesh)
                     for pw, key in zip(pws, keys))
    return fused_qkv_packed_pallas(
        x, pws, cfg, [_key_to_seed(k) for k in keys])


def _fused_qkv_tp(x, pws, cfg: QuantConfig, seeds, mesh):
    """Column-parallel fused QKV over the 'model' axis (see
    ``fused_qkv_dense``); weights arrive column-sharded, gains and seeds
    replicated, x replicated."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ops import tp_size

    tp = tp_size(mesh)
    pws = tuple(pws)
    njs_g = tuple(pw.n_padded // DEFAULT_BN for pw in pws)
    local_blocks = tuple(nj // tp for nj in njs_g)
    has_gains = pws[0].gains is not None
    has_seed = seeds[0] is not None
    rep_x = P(*([None] * x.ndim))

    def body(x_, cq, sq, ck, sk, cv, sv, *rest):
        gains = rest[:3] if has_gains else (None, None, None)
        sds = rest[3:] if has_gains else rest
        t = jax.lax.axis_index(_MODEL_AXIS)
        pws_l = tuple(
            PackedWeight(c, s_, pw.k, c.shape[-1], pw.tile_width, pw.bits_w,
                         gains=g)
            for c, s_, g, pw in zip((cq, ck, cv), (sq, sk, sv), gains, pws))
        outs = fused_qkv_packed_pallas(
            x_, pws_l, cfg, tuple(sds) if has_seed else None,
            col_block_offsets=tuple(t * lb for lb in local_blocks),
            num_col_blocks=njs_g)
        return tuple(jax.lax.all_gather(y, _MODEL_AXIS, axis=-1, tiled=True)
                     for y in outs)

    args = [x]
    specs = [rep_x]
    for pw in pws:
        args += [pw.codes, pw.scales]
        specs += [P(None, _MODEL_AXIS), P(None, _MODEL_AXIS)]
    if has_gains:
        args += [pw.gains for pw in pws]
        specs += [P(None)] * 3
    if has_seed:
        args += list(seeds)
        specs += [P()] * 3

    out = shard_map(body, mesh=mesh, in_specs=tuple(specs),
                    out_specs=(rep_x,) * 3, check_rep=False)(*args)
    return tuple(y[..., :pw.n_cols] for y, pw in zip(out, pws))
