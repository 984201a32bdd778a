"""Fused ABFP decode-step kernels: QKV projections + quantized-KV attention.

The serving decode hot path was a CHAIN of dispatches per attention block —
three separate ``abfp_matmul_packed_pallas`` launches for the Q/K/V
projections, a jnp attention over the int8 KV cache, and a fourth launch for
the output projection.  This module fuses the chain's front end into two
Pallas kernels, and holds the int8 KV cache's one other writer:

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
    A (B, KH)-grid Pallas kernel that appends the tick's token to the int8
    KV cache in place and computes decode attention directly on the codes,
    mirroring ``models.layers.quantized_decode_attention`` op-for-op.  A
    decode tick has a single query row, so the online-softmax running max /
    denominator of ``flash_attention.py`` collapses to one masked softmax
    over the whole (cache-resident) key axis; the kernel keeps that
    degenerate form explicit so the scores/PV contractions and the masking
    constant match the jnp reference bit-for-bit.

``append_kv_columns``
    The cache's other writer on one device (prefill chunks, the packed
    chain's ticks): writes each slot's new positions in place, one
    128-column tile at a time.

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


def _tile(pos, w: int, s_max: int):
    """The W-wide column tile that holds cache position ``pos``, clamped
    to the cache's last tile."""
    return jnp.minimum(pos // w, s_max // w - 1)


def _fused_attn_kernel(layer_ref, len_ref, q_ref, kc_ref, vc_ref, sc_ref,
                       nk_ref, nv_ref, ns_ref, o_ref, ko_ref, vo_ref, so_ref):
    """Append one token and attend, on int8 KV codes, for one (batch
    element, KV head).

    The new token's codes and scales (``nk/nv/ns``, one column each) go in
    at position ``len - 1``: into the cache blocks the attention reads, and
    out through the aliased cache outputs, whose blocks are the W-wide
    column tile that holds the position — the rest of the cache is not
    written.  The attention mirrors ``models.layers
    .quantized_decode_attention`` op-for-op for the ``rep`` query heads
    sharing this KV head: scores contract head_dim against the raw int8
    codes, the per-position scales factor out of both contractions, masked
    positions get the same -1e30 the jnp path uses, and the single query
    row makes the flash-attention online softmax (``flash_attention.py``)
    degenerate to one ``jax.nn.softmax`` over the key axis.
    """
    del layer_ref                            # used by the index maps only
    b = pl.program_id(0)
    d = q_ref.shape[-1]
    s_max = kc_ref.shape[-1]
    w = ko_ref.shape[-1]
    rep = q_ref.shape[0]
    pos = len_ref[b] - 1
    f32, i32 = jnp.float32, jnp.int32

    def append(col_ref, plane, out_ref, dtype):
        """The plane with the new column, in f32; its W-wide tile that
        holds ``pos`` to ``out_ref`` (a position past the end, which a free
        slot's length reaches, matches no lane: the last tile goes back
        unchanged)."""
        col = col_ref[...].astype(f32)                          # (X, 1)
        new = jax.lax.broadcasted_iota(i32, (1, s_max), 1) == pos
        base = pl.multiple_of(_tile(pos, w, s_max) * w, w)
        tile = plane[:, pl.ds(base, w)].astype(f32)
        at = jax.lax.broadcasted_iota(i32, (1, w), 1) + base == pos
        out_ref[...] = jnp.where(at, col, tile).astype(dtype)
        return jnp.where(new, col, plane[...].astype(f32))

    kc = append(nk_ref, kc_ref, ko_ref, jnp.int8)               # (d, s)
    vc = append(nv_ref, vc_ref, vo_ref, jnp.int8)
    sc = append(ns_ref, sc_ref, so_ref, sc_ref.dtype)           # (2, s)

    qf = q_ref[...].astype(f32) * (d ** -0.5)                   # (rep, d)
    s = jax.lax.dot_general(
        qf, kc, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32)                             # (rep, s)
    s = s * (sc[0:1] / 127.0)                                   # (1, s)
    pos_s = jax.lax.broadcasted_iota(i32, (rep, s_max), 1)
    s = jnp.where(pos_s < len_ref[b], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)                              # (rep, s)
    pv = p * (sc[1:2] / 127.0)
    out = jax.lax.dot_general(
        pv, vc, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=f32)                             # (rep, d)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_quantized_decode_attention(
    q: jax.Array,
    k_codes: jax.Array,
    v_codes: jax.Array,
    scales: jax.Array,
    new_k: jax.Array,
    new_v: jax.Array,
    new_scales: jax.Array,
    *,
    lengths: jax.Array,
    layer: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
):
    """Pallas decode tick over the int8 KV cache: append each slot's new
    token at position ``lengths - 1``, then attend — one grid cell per
    (batch element, KV head).

    ``q``: (B, 1, H, D); codes: (B, KH, D, S) int8; scales: (B, KH, 2, S),
    K scales in row 0 and V scales in row 1; ``new_k``/``new_v``: (B, KH,
    D) codes and ``new_scales``: (B, KH, 2) scales of the token to append;
    ``lengths``: (B,) int32 filled-slot counts, the new token included.
    Returns (out (B, 1, H, D), k_codes, v_codes, scales) with the token
    appended.  The output is bit-identical to ``models.layers
    .quantized_decode_attention`` on the appended cache (enforced by
    tests/test_fused.py).

    The cache is read where it lies: codes and scales may carry a leading
    layer axis, (L, B, KH, D, S) and (L, B, KH, 2, S), and ``layer`` picks
    the layer through the index maps (a scalar-prefetch argument), so the
    decode step's scan over layers hands over its stacked cache as is.
    Each cell DMAs one head's (D, S) code planes and (2, S) scale plane
    into fast memory: blocks whose two minor axes are whole axes of the
    array, which the TPU stores row-major, so no copy precedes the call.
    The cache arrays are aliased to the outputs, and each cell writes back
    only the 128-column tile that holds the new position (the whole
    position axis when it is not a multiple of 128): the append is in
    place, and the only writes to the cache in a decode tick, so XLA keeps
    the cache in its row-major layout throughout.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    stacked = k_codes.ndim == 5
    if not stacked:                          # one layer's cache
        k_codes, v_codes, scales = k_codes[None], v_codes[None], scales[None]
        layer = 0
    b, _, h, d = q.shape
    kh, s_max = k_codes.shape[2], k_codes.shape[4]
    rep = h // kh
    w = 128 if s_max % 128 == 0 else s_max

    def plane(x):
        return pl.BlockSpec((None, None, None, x, s_max),
                            lambda i, g, l, n: (l[0], i, g, 0, 0))

    def tile(x):
        return pl.BlockSpec((None, None, None, x, w),
                            lambda i, g, l, n: (l[0], i, g, 0,
                                                _tile(n[i] - 1, w, s_max)))

    def column(x):
        return pl.BlockSpec((None, None, x, 1), lambda i, g, l, n: (i, g, 0, 0))

    head = pl.BlockSpec((None, None, rep, d), lambda i, g, l, n: (i, g, 0, 0))
    out, k_codes, v_codes, scales = pl.pallas_call(
        _fused_attn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # layer, lengths
            grid=(b, kh),
            in_specs=[head, plane(d), plane(d), plane(2),
                      column(d), column(d), column(2)],
            out_specs=[head, tile(d), tile(d), tile(2)]),
        out_shape=[jax.ShapeDtypeStruct((b, kh, rep, d), q.dtype),
                   jax.ShapeDtypeStruct(k_codes.shape, k_codes.dtype),
                   jax.ShapeDtypeStruct(v_codes.shape, v_codes.dtype),
                   jax.ShapeDtypeStruct(scales.shape, scales.dtype)],
        input_output_aliases={3: 1, 4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(b, kh, rep, d), k_codes, v_codes, scales,
      new_k[..., None], new_v[..., None], new_scales[..., None])
    if not stacked:
        k_codes, v_codes, scales = k_codes[0], v_codes[0], scales[0]
    return out.reshape(b, 1, h, d), k_codes, v_codes, scales


def _append_kernel(layer_ref, tile_ref, k_ref, v_ref, s_ref, kw_ref, vw_ref,
                   sw_ref, m_ref, ko_ref, vo_ref, so_ref, *, last: int):
    """Write one slot's new columns into one W-wide column tile of each
    cache plane: lanes the mask marks take the new column, the rest keep
    what the tile holds."""
    del layer_ref
    b, t = pl.program_id(0), pl.program_id(1)
    # Tiles past the cache's end are clamped onto its last tile; a step
    # that lands on the tile the step before wrote leaves it as written.
    revisit = jnp.logical_and(t > 0, tile_ref[b] + t > last)

    @pl.when(jnp.logical_not(revisit))
    def _write():
        new = m_ref[...] != 0                                  # (1, W)
        for old, win, out in ((k_ref, kw_ref, ko_ref), (v_ref, vw_ref, vo_ref),
                              (s_ref, sw_ref, so_ref)):
            out[...] = jnp.where(new, win[...].astype(jnp.float32),
                                 old[...].astype(jnp.float32)
                                 ).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_kv_columns(
    k_codes: jax.Array,
    v_codes: jax.Array,
    scales: jax.Array,
    new_k: jax.Array,
    new_v: jax.Array,
    new_scales: jax.Array,
    *,
    start: jax.Array,
    count: jax.Array,
    layer: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
):
    """Write C new positions per slot into the int8 KV cache, in place.

    ``k_codes``/``v_codes``: ([L,] B, KH, D, S) int8 and ``scales``: ([L,]
    B, KH, 2, S), at ``layer`` when stacked (the layout of
    ``fused_quantized_decode_attention``); ``new_k``/``new_v``: (B, KH, D,
    C) and ``new_scales``: (B, KH, 2, C), the columns of slot b going to
    positions ``start[b] + [0, count[b])``; positions past S are dropped.
    Returns the three arrays, aliased to the inputs.

    One grid cell per (slot, W-wide column tile the slot's columns can
    reach): it reads and writes back that tile only.  Because the cache is
    written by Pallas calls alone, XLA keeps it in the row-major layout the
    attention kernel reads (an XLA scatter into it would lay the whole
    cache out again, and back, around every write).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    stacked = k_codes.ndim == 5
    if not stacked:
        k_codes, v_codes, scales = k_codes[None], v_codes[None], scales[None]
        layer = 0
    _, b, kh, d, s_max = k_codes.shape
    c = new_k.shape[-1]
    w = 128 if s_max % 128 == 0 else s_max
    n_tiles = 1 if w == s_max else -(-(c + w - 1) // w)
    # The new columns laid out over the tiles they fall in: lane j of the
    # window is position start // w * w + j.
    tile0 = start.astype(jnp.int32) // w
    lane = jnp.arange(n_tiles * w)[None, :]
    col = tile0[:, None] * w + lane - start[:, None]
    mask = (col >= 0) & (col < count[:, None]) & (
        tile0[:, None] * w + lane < s_max)
    idx = jnp.clip(col, 0, c - 1)[:, None, None, :]
    wins = [jnp.take_along_axis(x, idx, axis=-1)
            for x in (new_k, new_v, new_scales)]

    def tile(x):
        return pl.BlockSpec(
            (None, None, kh, x, w),
            lambda i, t, l, t0: (l[0], i, 0, 0,
                                 jnp.minimum(t0[i] + t, s_max // w - 1)))

    def window(x):
        return pl.BlockSpec((None, kh, x, w), lambda i, t, l, t0: (i, 0, 0, t))

    out = pl.pallas_call(
        functools.partial(_append_kernel, last=s_max // w - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # layer, first tile
            grid=(b, n_tiles),
            in_specs=[tile(d), tile(d), tile(2), window(d), window(d),
                      window(2),
                      pl.BlockSpec((None, 1, w),
                                   lambda i, t, l, t0: (i, 0, t))],
            out_specs=[tile(d), tile(d), tile(2)]),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (k_codes, v_codes, scales)],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tile0, k_codes, v_codes,
      scales, *wins, mask[:, None, :].astype(jnp.int32))
    return tuple(out) if stacked else tuple(a[0] for a in out)


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
