"""Fused flash-attention (forward) Pallas TPU kernel.

The roofline analysis (EXPERIMENTS.md §Roofline) shows attention-score
traffic — O(S²) HBM bytes — dominating every train/prefill memory term in
the kernel-less XLA lowering.  This kernel keeps the (bq, bk) score tile,
the online-softmax statistics and the output accumulator in VMEM: HBM
traffic falls to one read of Q/K/V + one write of O.

Supports causal and sliding-window masking and GQA (kv-head mapping via the
BlockSpec index map — no materialized head repetition).  Fully-masked KV
blocks are skipped with ``pl.when`` (the causal wedge does half the work).

Forward-only by design: training uses the q-chunked remat path
(``models.layers.train_attention``); serving prefill is where the S² memory
term bites (32k cells) and where this kernel applies.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 512
DEFAULT_BK = 512
_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale, causal, window, bq, bk, skv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    # Skip KV blocks that are entirely masked (future of the causal wedge /
    # beyond the sliding window).
    live = jnp.bool_(True)
    if causal:
        live = k_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < skv                                # kv padding
        if causal:
            valid = jnp.logical_and(valid, kpos <= qpos)
        if window > 0:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        s = jnp.where(valid, s, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        den = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / den[:, None]).astype(o_ref.dtype)


def _ceil_to(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, KH, D) with H % KH == 0 (GQA).

    Returns (B, Sq, H, D) in q.dtype.  Softmax statistics in f32.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    rep = h // kh

    bq = min(bq, _ceil_to(sq, 128))
    bk = min(bk, _ceil_to(skv, 128))
    sqp, skvp = _ceil_to(sq, bq), _ceil_to(skv, bk)

    # (B*H, S, D) layout; KV heads addressed through the index map (GQA).
    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * kh, skv, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * kh, skv, d)
    if sqp != sq:
        qt = jnp.pad(qt, ((0, 0), (0, sqp - sq), (0, 0)))
    if skvp != skv:
        kt = jnp.pad(kt, ((0, 0), (0, skvp - skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, skvp - skv), (0, 0)))

    grid = (b * h, sqp // bq, skvp // bk)

    def kv_map(bh, qi, ki):
        return (bh // h) * kh + (bh % h) // rep, ki, 0

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5, causal=causal, window=window,
        bq=bq, bk=bk, skv=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)

    out = out[:, :sq].reshape(b, h, sq, d)
    return jnp.moveaxis(out, 1, 2)
