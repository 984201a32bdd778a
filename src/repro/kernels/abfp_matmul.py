"""Fused ABFP tiled-matmul Pallas TPU kernel.

The compute hot-spot of ABFP simulation.  A naive XLA implementation either
materializes the (T, M, N) per-tile partial-product tensor in HBM (T = K/n —
a 64x blow-up at K=8192, n=128; 512x at n=8) or re-reads the operands T
times.  This kernel keeps everything tile-local in VMEM:

  grid = (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics).
  Each step loads x_blk (bm, bk) and w_blk (bk, bn), splits the K block into
  tk = bk/n ABFP tiles, and per tile:

    s_x = max|x_tile|  (bf16-rounded)          s_w = max|w_tile|
    x_q = Q(x/s_x; d_X, 1)                     w_q = Q(w/s_w; d_W, 1)
    p   = x_q . w_q                 (MXU batched dot over the tk tiles)
    y_q = Q(G*p + E; n*d_Y, n)      (ADC with gain and uniform noise)
    acc += y_q * s_x * s_w / G      (FLOAT32 accumulator in VMEM scratch)

  The accumulator is written to HBM once, as BFLOAT16, on the last K step.

AMS noise uses a counter-based murmur3-style hash PRNG (seed, program ids,
tile index) -> uniform, identical under `interpret=True` on CPU and compiled
TPU execution, so the oracle comparison and noise statistics are testable in
this container.

TPU adaptation note (DESIGN.md §2): the paper's analog device processes one
n-wide tile per clock; here tk tiles are batched into one MXU dot_general so
small n (8/32) still feeds the 128x128 systolic array efficiently — the tile
*semantics* (per-tile ADC quantization) are preserved exactly.

Packed-weight variant (``abfp_matmul_packed_pallas``)
-----------------------------------------------------

The kernel above re-derives the weight scales and integer codes on every
grid step — M/bm times per call, and once per decode tick in serving — even
though weights are static.  The packed variant consumes a pre-quantized
``repro.core.abfp.PackedWeight`` instead:

  codes : int8     (Kp, Np)  integer weight codes in [-L_w, +L_w]; row
                             ``t*n + i`` is element i of K-tile t.  Kp is K
                             zero-padded to a multiple of the tile width n;
                             Np is N zero-padded to the 128-lane boundary
                             at PACK time (padding rows/columns are code 0
                             under scale 0, contributing exactly 0).
  scales: bf16 (T, Np)       per-(tile, out-column) scales, T = Kp/n,
                             ``cfg.scale_dtype``-rounded (bf16 by default)
                             exactly as the in-kernel ``max|w| -> bf16``
                             derivation would round them.

Padding contract: the wrapper zero-pads Kp -> multiple of bk and
Np -> multiple of bn at call time and slices the output back to the
caller's logical (M, N); with the default (or any 128-multiple) bn these
pads are no-ops, so the hot path streams codes/scales exactly as stored
— no per-call weight re-materialization.  Max-abs scales only
(``scale_percentile`` configs are rejected at pack time).

Per grid step the packed kernel loads the int8 code block + bf16 scale block
straight from HBM, casts, and goes directly to the MXU dot — deleting the
per-step weight max/round/clip work and halving weight-side HBM bytes
(int8 codes vs bf16, plus T/K-sized scales).  Output is bit-identical to
``abfp_matmul_pallas`` at matching block sizes — same integer lattice
(pack-time scales are bf16-rounded exactly as in-kernel), same f32 ADC
constant, same noise hash and salt layout, same accumulation order — and
matches the einsum oracle to the usual f32 accumulation-order ULP
tolerance (the oracle contracts all T tiles in one einsum).

Decode-shape specialization: when ``bm`` is not given, both wrappers pick
``bm = min(DEFAULT_BM, ceil8(M))`` so a 1–8 row decode matmul runs an
(8, bk) activation block instead of being zero-padded to 128 rows — a 16x
cut in per-step activation work at M=1.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.abfp import PackedWeight, QuantConfig

DEFAULT_BM = 128
DEFAULT_BN = 128


def auto_bm(m: int) -> int:
    """Decode-shape specialization: smallest f32-legal row block covering m.

    A decode step has m in 1..8; padding it to the 128-row default block
    wastes 16x the activation-side work (and VMEM).  f32 sublane tiling
    needs multiples of 8, so clamp to [8, DEFAULT_BM].
    """
    return min(DEFAULT_BM, max(8, ((m + 7) // 8) * 8))


def default_bk(n: int, k: int) -> int:
    """K-block: multiple of the ABFP tile width, capped to bound VMEM.

    tk = bk/n partial products of (bm, bn) f32 live in VMEM: at bm=bn=128,
    bk=512 -> tk*64KiB <= 4 MiB (n=8 uses bk=256 -> 2 MiB).
    """
    cap = 256 if n <= 8 else 512
    bk = min(cap, max(n, k))
    return max(n, (bk // n) * n)


# ---------------------------------------------------------------------------
# Counter-based uniform PRNG (murmur3 finalizer lattice hash)
# ---------------------------------------------------------------------------


def _hash_uniform(shape, seed, salt):
    """Deterministic uniform [0, 1) lattice: hash(row, col, seed, salt)."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = (
        r * jnp.uint32(0x9E3779B9)
        + c * jnp.uint32(0x85EBCA6B)
        + seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
        + salt.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)
    )
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # The TPU compiler has no uint32 -> f32 cast; the top 24 bits fit int32
    # exactly, so going through int32 draws the same value.
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) / jnp.float32(1 << 24)


# ---------------------------------------------------------------------------
# Kernel bodies (shared ABFP core; weight source is the only difference)
# ---------------------------------------------------------------------------


def _abfp_contrib(xt, wq, sw, seed_ref, cfg: QuantConfig, tk: int, n: int,
                  nj: Optional[int] = None, g=None, idx=None):
    """Shared per-grid-step ABFP math: everything except how (wq, sw) were
    obtained.  ALL the ABFP kernels (unpacked, packed, fused decode) route
    through this one function so the packed == unpacked == fused
    bit-identity contract lives in exactly one place.

    xt: (bm, tk, n) f32 activation tiles;  wq: (tk, n, bn) integer weight
    codes, already cast to the MXU code dtype;  sw: (tk, bn) f32 weight
    scales (``scale_dtype``-rounded).  Returns the (bm, bn) f32 contribution
    of this K block.

    ``seed_ref`` is SMEM (2,) int32: [noise seed, column-block offset].  The
    offset (plus ``nj``, the GLOBAL column-block count) globalizes the noise
    salt for tensor-parallel column shards: shard s computing column blocks
    [off, off + nj_local) draws exactly the noise the single-device grid
    draws for those blocks, so sharded execution is bit-identical to
    unsharded at any shard count (kernels/ops.dense_tp).  Defaults (offset
    0, nj = num_programs(1)) reproduce the historical single-device salts.

    ``g`` (optional (tk, 1, 1) f32): per-tile ADC gains (``PackedWeight.gains``,
    the paper's amplification knob).  Each tile's exact partial product is
    amplified by G_t before the b_Y-bit output quantizer
    (``v = p * adc_base_scale * G_t``) and divided back out of that tile's
    Eq. 6 term (``yq * s_x * (s_w / G_t)``) — raising effective output
    precision by log2(G_t) bits with no extra output bits.  ``None`` keeps
    the scalar ``cfg.gain`` path byte-for-byte unchanged; an all-ones ``g``
    is bit-identical to the scalar path at ``gain=1.0`` (amplifying and
    dividing by exactly 1.0 are exact f32 no-ops).

    ``idx`` (optional): explicit grid coordinates
    ``(i, j, k, nk, nj_g, seed_val)`` replacing the ``pl.program_id`` /
    ``seed_ref`` reads — the fused decode kernel spans several logical
    weights in one launch and must reproduce each segment's own
    single-weight salts, so it computes the per-segment coordinates itself
    and passes them here.  ``None`` (every single-weight kernel) reads the
    real grid position, preserving the historical salts exactly.
    """
    bm = xt.shape[0]
    bn = wq.shape[-1]

    # Adaptive per-tile activation scales (paper Sec. III) + DAC encode
    # (Eq. 2).  Activations are dynamic: their scales/codes must be derived
    # per call, unlike the static weight side.
    sx = jnp.max(jnp.abs(xt), axis=2)               # (bm, tk)
    sx = sx.astype(cfg.scale_dtype).astype(jnp.float32)
    sx_safe = jnp.where(sx == 0.0, 1.0, sx)
    lx = jnp.float32(2 ** (cfg.bits_x - 1) - 1)
    xq = jnp.clip(jnp.round(xt / sx_safe[:, :, None] * lx), -lx, lx)
    xq = xq.astype(wq.dtype)

    # Batched MXU dot over the tk tiles: (tk, bm, n) @ (tk, n, bn).
    # Integer-valued operands: the f32-accumulated dot is EXACT
    # (|p| <= n*L_x*L_w < 2^24 at 8 bits), matching the analog MAC array and
    # the jnp oracle bit-for-bit.
    p = jax.lax.dot_general(
        xq.transpose(1, 0, 2),
        wq,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                               # (tk, bm, bn)

    # Eq. 5/7: the ADC in code units — same fused f32 constant as the oracle
    # so round-half-even ties resolve identically.  Per-tile gains amplify
    # each tile's exact product before the output quantizer.
    if g is None:
        v = p * jnp.float32(cfg.adc_code_scale)
    else:
        v = p * jnp.float32(cfg.adc_base_scale) * g
    if cfg.noise_lsb > 0.0:
        # One independent uniform noise draw per partial output, in LSB
        # units, salted by the grid position.
        if idx is None:
            i = pl.program_id(0)
            j = pl.program_id(1) + seed_ref[1]      # global column block
            k = pl.program_id(2)
            nk = pl.num_programs(2)
            nj_g = nj if nj is not None else pl.num_programs(1)
            seed_val = seed_ref[0]
        else:
            i, j, k, nk, nj_g, seed_val = idx
        salt = (i * nj_g + j) * nk + k
        u = _hash_uniform(
            (tk * bm, bn),
            seed_val,
            jnp.uint32(salt),
        ).reshape(tk, bm, bn)
        v = v + (u - 0.5) * jnp.float32(2.0 * cfg.noise_lsb)
    ly = jnp.float32(2 ** (cfg.bits_y - 1) - 1)
    yq = jnp.clip(jnp.round(v), -ly, ly) * jnp.float32(cfg.bin_y)

    # Eq. 6: rescale partials and sum over the tk tiles in FLOAT32.  Per-tile
    # gains (powers of two) divide out of the weight scales exactly; the
    # scalar gain divides the sum.  The sum runs tile by tile in a fixed
    # order, each tile's rescale feeding its add: a reduction leaves the
    # order, and whether a multiply and add fuse, to the compiler, which
    # decides per program, so kernels sharing this core (and the gain and
    # gain-free paths at unit gains) would stop agreeing bit for bit.
    sxb = sx.T[:, :, None]                           # (tk, bm, 1)
    swb = sw[:, None, :] if g is None else sw[:, None, :] / g
    acc = yq[0] * sxb[0] * swb[0]
    for t in range(1, tk):
        acc = acc + yq[t] * sxb[t] * swb[t]
    if g is None:
        acc = acc / jnp.float32(cfg.gain)
    return acc                                       # (bm, bn)


def _abfp_matmul_kernel(
    seed_ref,  # SMEM (2,) int32: [seed, col-block offset]
    x_ref,     # VMEM (bm, bk)
    w_ref,     # VMEM (bk, bn)
    o_ref,     # VMEM (bm, bn)
    acc_ref,   # VMEM scratch (bm, bn) f32
    *,
    cfg: QuantConfig,
    tk: int,
    n: int,
    nj: Optional[int] = None,
):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bk = x_ref.shape
    bn = w_ref.shape[1]

    xt = x_ref[...].astype(jnp.float32).reshape(bm, tk, n)
    wt = w_ref[...].astype(jnp.float32).reshape(tk, n, bn)

    # Weight side, re-derived every grid step (the packed kernel skips
    # this): scale_dtype-rounded max-abs scales + DAC encode (Eq. 2).
    sw = jnp.max(jnp.abs(wt), axis=1)               # (tk, bn)
    sw = sw.astype(cfg.scale_dtype).astype(jnp.float32)
    sw_safe = jnp.where(sw == 0.0, 1.0, sw)
    lw = jnp.float32(2 ** (cfg.bits_w - 1) - 1)
    wq = jnp.clip(jnp.round(wt / sw_safe[:, None, :] * lw), -lw, lw)
    # bf16 codes are exact for <= 9-bit operands and feed the MXU at its
    # bf16 rate (vs ~1/8 rate for f32) — see core.abfp.code_dtype.
    from repro.core.abfp import code_dtype
    wq = wq.astype(code_dtype(max(cfg.bits_x, cfg.bits_w)))

    acc_ref[...] += _abfp_contrib(xt, wq, sw, seed_ref, cfg, tk, n, nj=nj)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Host-side wrapper
# ---------------------------------------------------------------------------


def _ceil_to(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def k_blocked(scales: jax.Array, nk: int) -> jax.Array:
    """(T, N) per-tile rows -> (nk, T/nk, N): one leading entry per K block.

    A K block holds tk = bk/n tiles, and tk is rarely a multiple of the 8
    rows the TPU tiles a block by; a (tk, bn) block of the 2-D array is
    then illegal, while (1, tk, bn) of this view spans its full tile axis.
    """
    return scales.reshape(nk, scales.shape[0] // nk, scales.shape[1])


def _seed_smem(seed, noise_lsb: float, col_block_offset) -> jax.Array:
    """(2,) int32 SMEM payload: [noise seed, global column-block offset]."""
    if seed is None:
        if noise_lsb > 0.0:
            raise ValueError("noise_lsb > 0 requires a seed")
        seed = jnp.zeros((), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape(())
    off = (jnp.zeros((), jnp.int32) if col_block_offset is None
           else jnp.asarray(col_block_offset, jnp.int32).reshape(()))
    return jnp.stack([seed, off])


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "bm", "bn", "bk", "interpret", "num_col_blocks"),
)
def abfp_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    cfg: QuantConfig,
    seed: Optional[jax.Array] = None,
    *,
    bm: Optional[int] = None,
    bn: int = DEFAULT_BN,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
    col_block_offset: Optional[jax.Array] = None,
    num_col_blocks: Optional[int] = None,
) -> jax.Array:
    """y = ABFP(x @ w); x: (..., K), w: (K, N) -> (..., N) in cfg.out_dtype.

    ``seed``: int32 scalar seeding the in-kernel noise hash (required when
    cfg.noise_lsb > 0).  ``interpret`` defaults to True off-TPU so the same
    call validates on CPU and runs compiled on TPU.  ``bm`` defaults to the
    decode-aware ``auto_bm`` (8-row blocks for 1–8 row decode matmuls).

    ``col_block_offset`` (runtime int32) and ``num_col_blocks`` (static):
    tensor-parallel salt globalization — a column shard owning blocks
    [off, off + N_local/bn) of a global grid with ``num_col_blocks`` column
    blocks draws the same noise the single-device grid draws for those
    blocks (see ``_abfp_contrib``).  Leave unset for single-device calls.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = cfg.tile_width
    if bk is None:
        bk = default_bk(n, x.shape[-1])
    assert bk % n == 0, (bk, n)

    batch_shape = x.shape[:-1]
    k_dim, n_dim = w.shape
    x2 = x.reshape(-1, k_dim).astype(jnp.float32)
    m_dim = x2.shape[0]
    if bm is None:
        bm = auto_bm(m_dim)

    mp, kp, np_ = _ceil_to(m_dim, bm), _ceil_to(k_dim, bk), _ceil_to(n_dim, bn)
    x2 = jnp.pad(x2, ((0, mp - m_dim), (0, kp - k_dim)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, kp - k_dim), (0, np_ - n_dim)))

    seed = _seed_smem(seed, cfg.noise_lsb, col_block_offset)

    grid = (mp // bm, np_ // bn, kp // bk)
    tk = bk // n

    kernel = functools.partial(_abfp_matmul_kernel, cfg=cfg, tk=tk, n=n,
                               nj=num_col_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # seed
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),        # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),        # w
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), cfg.out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seed, x2, wp)

    return out[:m_dim, :n_dim].reshape(*batch_shape, n_dim)


# ---------------------------------------------------------------------------
# Packed-weight kernel: pre-quantized int8 codes + bf16 scales from HBM
# ---------------------------------------------------------------------------


def _abfp_matmul_packed_kernel(
    seed_ref,  # SMEM (2,) int32: [seed, col-block offset]
    x_ref,     # VMEM (bm, bk) f32
    wc_ref,    # VMEM (bk, bn) int8 weight codes
    sw_ref,    # VMEM (1, tk, bn) scale_dtype weight scales
    *refs,     # [g_ref (1, tk, 1, 1) f32 gains]  o_ref (bm, bn)  acc scratch
    cfg: QuantConfig,
    tk: int,
    n: int,
    nj: Optional[int] = None,
    has_gains: bool = False,
):
    """Packed-weight kernel body: codes/scales (and optional per-tile
    gains) stream straight from HBM into the shared ABFP core."""
    if has_gains:
        g_ref, o_ref, acc_ref = refs
        g = g_ref[0].astype(jnp.float32)                 # (tk, 1, 1)
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

    # Weight side: NO max/round/clip — codes and scales come straight from
    # HBM.  int8 -> bf16/f32 cast is exact for |code| <= 127.
    from repro.core.abfp import code_dtype
    cdt = code_dtype(max(cfg.bits_x, cfg.bits_w))
    wq = wc_ref[...].astype(cdt).reshape(tk, n, bn)  # (tk, n, bn)
    sw = sw_ref[0].astype(jnp.float32)               # (tk, bn)

    acc_ref[...] += _abfp_contrib(xt, wq, sw, seed_ref, cfg, tk, n, nj=nj,
                                  g=g)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "bm", "bn", "bk", "interpret", "num_col_blocks"),
)
def abfp_matmul_packed_pallas(
    x: jax.Array,
    pw: PackedWeight,
    cfg: QuantConfig,
    seed: Optional[jax.Array] = None,
    *,
    bm: Optional[int] = None,
    bn: int = DEFAULT_BN,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
    col_block_offset: Optional[jax.Array] = None,
    num_col_blocks: Optional[int] = None,
) -> jax.Array:
    """y = ABFP(x @ w) from a pre-packed weight; x: (..., K) -> (..., N).

    ``pw`` must be a 2-D ``PackedWeight`` (no leading batch axes) packed at
    this ``cfg``'s tile width / bits_w.  Bit-identical to
    ``abfp_matmul_pallas(x, w, cfg, seed)`` at matching block sizes,
    without re-deriving weight scales/codes on every grid step.

    When ``pw.gains`` is present (the ``mode="abfp_fused"`` adaptive-gain
    packing), each K tile's partial product is amplified by its own G_t
    before the ADC and divided out after (see ``_abfp_contrib``); with
    all-ones gains the output is bit-identical to a gain-free pack at
    ``cfg.gain == 1.0``.

    ``col_block_offset`` / ``num_col_blocks``: tensor-parallel noise-salt
    globalization, as in ``abfp_matmul_pallas``.
    """
    if pw.codes.ndim != 2:
        raise ValueError(
            f"packed kernel takes a 2-D PackedWeight, got codes "
            f"{pw.codes.shape}; index leading axes first")
    if pw.tile_width != cfg.tile_width or pw.bits_w != cfg.bits_w:
        raise ValueError(
            f"PackedWeight(n={pw.tile_width}, bits_w={pw.bits_w}) does not "
            f"match cfg(n={cfg.tile_width}, bits_w={cfg.bits_w})")
    if pw.scales.dtype != jnp.dtype(cfg.scale_dtype):
        raise ValueError(
            f"PackedWeight scales are {pw.scales.dtype} but cfg.scale_dtype "
            f"is {jnp.dtype(cfg.scale_dtype)}; re-pack at this config")
    if cfg.noise_lsb > 0.0 and bn % 128 != 0:
        # The noise salt depends on the column-block count; only bn multiples
        # of the 128-lane pre-padding guarantee the packed and unpacked grids
        # (and thus their noise streams) coincide.
        raise ValueError(
            f"noise_lsb > 0 requires bn to be a multiple of 128 for the "
            f"packed kernel (got bn={bn}): other block widths change the "
            f"grid vs the unpacked kernel and break noise bit-identity")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = cfg.tile_width
    k_dim, n_dim = pw.k, pw.n_out
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

    # Pad x's K to the packed Kp (zero activations against real tiles are
    # exact no-ops), then everything to block multiples.  The weight is
    # already lane-aligned from pack time, so for the default bn (and any
    # bn that is a multiple of 128) the pads below are no-ops and the hot
    # path streams pw.codes/pw.scales exactly as stored.
    kp0, npad0 = pw.kp, pw.n_padded
    mp, kp, np_ = _ceil_to(m_dim, bm), _ceil_to(kp0, bk), _ceil_to(npad0, bn)
    x2 = jnp.pad(x2, ((0, mp - m_dim), (0, kp - k_dim)))
    wc, sw = pw.codes, pw.scales
    if kp > kp0 or np_ > npad0:
        wc = jnp.pad(wc, ((0, kp - kp0), (0, np_ - npad0)))
        sw = jnp.pad(sw, ((0, (kp - kp0) // n), (0, np_ - npad0)))

    seed = _seed_smem(seed, cfg.noise_lsb, col_block_offset)

    grid = (mp // bm, np_ // bn, kp // bk)
    nk, tk = kp // bk, bk // n

    has_gains = pw.gains is not None
    kernel = functools.partial(
        _abfp_matmul_packed_kernel, cfg=cfg, tk=tk, n=n, nj=num_col_blocks,
        has_gains=has_gains)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # seed
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),        # x
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),        # codes
        pl.BlockSpec((1, tk, bn), lambda i, j, k: (k, 0, j)),  # scales
    ]
    inputs = [seed, x2, wc, k_blocked(sw, nk)]
    if has_gains:
        # Per-tile gains ride along blocked over K like the scales (pad
        # tiles amplify zero scales: exact no-ops).
        gp = jnp.pad(pw.gains.astype(jnp.float32),
                     (0, kp // n - pw.num_tiles), constant_values=1.0)
        in_specs.append(
            pl.BlockSpec((1, tk, 1, 1), lambda i, j, k: (k, 0, 0, 0)))
        inputs.append(gp.reshape(nk, tk, 1, 1))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), cfg.out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)

    return out[:m_dim, :n_dim].reshape(*batch_shape, n_dim)
