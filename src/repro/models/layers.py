"""Composable model layers.

Every weight-activation matmul is routed through ``Numerics.dense`` so the
whole zoo runs in FLOAT, ABFP-simulated (QAT forward), or Pallas-kernel mode
with one switch — ABFP as a first-class framework feature.

Norms, softmax, nonlinearities and the recurrent cell internals run in
FLOAT32, per the paper (Sec. V: range-sensitive ops stay digital).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.abfp import PackedWeight, QuantConfig
from repro.kernels.ops import dense as quant_dense
from repro.kernels.ops import dense_packed, dense_tp, tp_size

Array = jax.Array


# ---------------------------------------------------------------------------
# Numerics context: quant mode + PRNG threading for AMS noise
# ---------------------------------------------------------------------------


class Numerics:
    """Per-forward numerics state.

    Each ``dense`` call site gets a deterministic PRNG stream derived from
    (base key, call counter); the caller folds the layer index into the base
    key inside scan-over-layers, so streams are unique per (layer, call).

    ``mesh``: when given (sharded serving), every 2-D dense weight is
    dispatched column-parallel over the mesh's 'model' axis via
    ``kernels.ops.dense_tp`` — bit-identical to the single-device path at
    any mesh shape (noise salts are globalized per column shard).  Weights
    the mesh cannot split evenly fall back to replicated execution inside
    the same dispatch.
    """

    def __init__(self, quant: QuantConfig, key: Optional[Array] = None,
                 mesh=None):
        self.quant = quant
        self._key = key
        self.mesh = mesh
        self._count = 0

    def fold(self, idx) -> "Numerics":
        key = None if self._key is None else jax.random.fold_in(self._key, idx)
        return Numerics(self.quant, key, self.mesh)

    def dense(self, x: Array, w) -> Array:
        key = None
        if self._key is not None and self.quant.noise_lsb > 0.0 \
                and self.quant.mode != "float":
            key = jax.random.fold_in(self._key, self._count)
        self._count += 1
        if self.mesh is not None and tp_size(self.mesh) > 1:
            # Sharded serving: column-parallel tensor parallelism (with
            # replicated fallback for unsplittable weights) in one dispatch.
            return dense_tp(x, w, self.quant, key, self.mesh)
        if isinstance(w, PackedWeight):
            # Quantize-once serving path: the weight was packed at engine
            # init (pack_model_params); skip re-quantization entirely.
            return dense_packed(x, w, self.quant, key)
        return quant_dense(x, w, self.quant, key)


FLOAT_NUMERICS = lambda: Numerics(QuantConfig(mode="float"))  # noqa: E731


# ---------------------------------------------------------------------------
# Norms (digital FLOAT32)
# ---------------------------------------------------------------------------


def rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return y.astype(dtype)


def layernorm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(dtype)


def norm(x: Array, params: dict, kind: str) -> Array:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params["bias"])


# ---------------------------------------------------------------------------
# Positions: RoPE (full / partial "2d") and absolute sinusoidal
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float, fraction: float) -> Array:
    """x: (B, S, H, D); positions: (B, S).  fraction < 1 rotates only the
    first fraction*D dims (chatglm's 2d/partial rotary)."""
    d = x.shape[-1]
    rot_d = int(d * fraction)
    rot_d -= rot_d % 2
    if rot_d == 0:
        return x
    x_rot, x_pass = x[..., :rot_d], x[..., rot_d:]
    half = rot_d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq    # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1 = x_rot[..., :half].astype(jnp.float32)
    x2 = x_rot[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def sinusoidal_positions(positions: Array, d: int) -> Array:
    """Sinusoidal PE evaluated at (possibly traced) positions (B, S) -> (B, S, d)."""
    pos = positions.astype(jnp.float32)[..., None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = pos / jnp.power(10_000.0, dim / d)                 # (B, S, d/2)
    pe = jnp.zeros(positions.shape + (d,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(ang))
    pe = pe.at[..., 1::2].set(jnp.cos(ang))
    return pe


# ---------------------------------------------------------------------------
# Attention (chunked online-softmax — bounded memory at 32k prefill)
# ---------------------------------------------------------------------------


def _repeat_kv(k: Array, num_heads: int) -> Array:
    """(B, S, KH, D) -> (B, S, H, D) for GQA/MQA."""
    kh = k.shape[2]
    if kh == num_heads:
        return k
    return jnp.repeat(k, num_heads // kh, axis=2)


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: Array | int = 0,
    chunk: int = 512,
) -> Array:
    """Flash-semantics attention in pure JAX: scan over KV chunks with an
    online softmax, so peak memory is O(B*H*Sq*chunk) instead of O(B*H*Sq*Skv).

    q: (B, Sq, H, D); k, v: (B, Skv, KH, D).  ``window`` > 0 restricts keys to
    the last ``window`` positions (sliding-window / local attention).
    ``q_offset``: global position of q[0] (decode / chunked prefill).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)

    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nchunks = (skv + pad) // chunk

    scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(sq)                       # (Sq,)

    kc = k.reshape(b, nchunks, chunk, h, d).astype(jnp.float32)
    vc = v.reshape(b, nchunks, chunk, h, d).astype(jnp.float32)
    kc = jnp.moveaxis(kc, 1, 0)                             # (C, B, c, H, D)
    vc = jnp.moveaxis(vc, 1, 0)

    neg = jnp.float32(-1e30)

    def step(carry, xs):
        m, den, acc = carry
        k_c, v_c, t = xs
        kpos = t * chunk + jnp.arange(chunk)                # (c,)
        s = jnp.einsum("bshd,bchd->bhsc", qf, k_c)          # (B, H, Sq, c)
        valid = kpos[None, :] < skv
        if causal:
            valid = valid & (kpos[None, :] <= q_pos[:, None])
        if window > 0:
            valid = valid & (kpos[None, :] > q_pos[:, None] - window)
        s = jnp.where(valid[None, None], s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        den_new = den * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhsc,bchd->bhsd", p, v_c)
        return (m_new, den_new, acc_new), None

    m0 = jnp.full((b, h, sq), neg, jnp.float32)
    den0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, den, acc), _ = jax.lax.scan(
        step, (m0, den0, a0), (kc, vc, jnp.arange(nchunks)))

    out = acc / jnp.maximum(den, 1e-30)[..., None]          # (B, H, Sq, D)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)          # (B, Sq, H, D)


def train_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 512,
) -> Array:
    """Training-path attention: scan over QUERY chunks with a rematerialized
    body.  Backward recomputes each chunk's (qc, Skv) scores instead of
    storing all of them — the flash-attention memory profile in pure JAX.
    (The KV-chunk online-softmax path in ``chunked_attention`` is ideal for
    inference but its scan carry makes backward storage O(S/c * B*H*S*D).)
    """
    b, s, h, d = q.shape
    skv = k.shape[1]
    k = _repeat_kv(k, h).astype(jnp.float32)
    v = _repeat_kv(v, h).astype(jnp.float32)
    q_chunk = min(q_chunk, s)
    if s % q_chunk:
        q_chunk = s
    nq = s // q_chunk
    scale = d ** -0.5
    qc_all = jnp.moveaxis(
        (q.astype(jnp.float32) * scale).reshape(b, nq, q_chunk, h, d), 1, 0)
    kpos = jnp.arange(skv)

    def chunk_body(carry, xs):
        qc, idx = xs                                      # (B, qc, H, D)
        s_ = jnp.einsum("bqhd,bkhd->bhqk", qc, k)         # (B, H, qc, Skv)
        qpos = idx * q_chunk + jnp.arange(q_chunk)
        valid = jnp.ones((q_chunk, skv), bool)
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            valid = valid & (kpos[None, :] > qpos[:, None] - window)
        s_ = jnp.where(valid[None, None], s_, -1e30)
        p = jax.nn.softmax(s_, axis=-1)
        out_c = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return carry, out_c

    _, outs = jax.lax.scan(jax.checkpoint(chunk_body), None,
                           (qc_all, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, d)    # (B, S, H, D)
    return out.astype(q.dtype)


def decode_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    *,
    lengths: Array,
) -> Array:
    """One-token attention against a KV cache.

    q: (B, 1, H, D); caches: (B, S_max, KH, D); ``lengths``: (B,) number of
    valid cache positions.

    Ring-buffer (sliding-window) caches need no extra masking here: the
    buffer is S_max == window wide and holds exactly the last
    ``min(length, window)`` tokens — every filled slot is in-window by
    construction, so validity is ``pos < lengths`` in both layouts
    (``lengths`` is the filled-slot count, clamped to S_max by the caller).
    """
    b, _, h, d = q.shape
    s_max = k_cache.shape[1]
    k = _repeat_kv(k_cache, h).astype(jnp.float32)
    v = _repeat_kv(v_cache, h).astype(jnp.float32)
    qf = q.astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bshd,bchd->bhsc", qf, k)[:, :, 0]       # (B, H, S_max)
    pos = jnp.arange(s_max)[None, :]
    valid = pos < lengths[:, None]
    s = jnp.where(valid[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhc,bchd->bhd", p, v)
    return out[:, None].astype(q.dtype)                     # (B, 1, H, D)


# ---------------------------------------------------------------------------
# ABFP-quantized KV cache (beyond-paper: the paper's per-vector adaptive
# scaling applied to the decode memory bottleneck)
# ---------------------------------------------------------------------------


def _kv_encode(v: Array):
    """(B, KH, D) -> int8 codes + per-(B, KH) bf16 scale (head_dim = tile)."""
    vf = v.astype(jnp.float32)
    s = jnp.max(jnp.abs(vf), axis=-1)                        # (B, KH)
    s = s.astype(jnp.bfloat16).astype(jnp.float32)
    s_safe = jnp.where(s == 0.0, 1.0, s)
    codes = jnp.clip(jnp.round(vf / s_safe[..., None] * 127.0), -127, 127)
    return codes.astype(jnp.int8), s.astype(jnp.bfloat16)


# The unpaged int8 cache is stored in the layout the decode attention kernel
# reads: per (slot, KV head) a (D, S) code plane, position minor, and a
# (2, S) scale plane holding the K (row 0) and V (row 1) scales —
#   {"k": (B, KH, D, S) int8, "v": ..., "kv_scale": (B, KH, 2, S) bf16,
#    "length": (B,)}.
# The TPU keeps these row-major, so the kernel's (D, S) and (2, S) blocks
# are DMA'd from where the cache lies.  (A (S, D) plane at D = 64 is stored
# position-minor, and a (KH, S) scale plane slot-minor unless KH is a
# multiple of 8: a kernel reading those gets a relayout copy per call.)
# Inside the scan over layers the arrays carry a leading layer axis and the
# dict a "layer" index: writes land in that layer's slice in place, reads
# take it where it lies.  On one device only Pallas kernels write the cache
# (``_q_write``, and the fused decode kernel): an XLA scatter into it makes
# XLA lay the whole cache out again, and back, around every write.


def _q_planes(kv_cache: dict):
    """The (k, v, kv_scale) planes of the cache's layer: (B, KH, D, S) x 2
    and (B, KH, 2, S)."""
    planes = (kv_cache["k"], kv_cache["v"], kv_cache["kv_scale"])
    if "layer" not in kv_cache:
        return planes
    return tuple(a[kv_cache["layer"]] for a in planes)


def _put(buf: Array, idx: tuple, vals: Array,
         valid: Optional[Array] = None) -> Array:
    """``buf.at[idx].set(vals)`` where ``idx[-1]`` holds the positions.

    Lanes with ``valid`` False (padding) and lanes past the buffer's end
    are dropped, so they leave what is there bit-for-bit."""
    if valid is not None:
        idx = idx[:-1] + (jnp.where(valid, idx[-1], buf.shape[len(idx) - 1]),)
    return buf.at[idx].set(vals.astype(buf.dtype), mode="drop")


def _q_write(kv_cache: dict, start: Array, count: Array, k: Array,
             v: Array, pallas: bool) -> dict:
    """Encode k, v (B, C, KH, D) and write their codes and scales at
    positions ``start + [0, count)`` of each slot — in place: only the new
    positions are written, positions past the cache's end are dropped.

    ``pallas`` (one device): by the Pallas append kernel, so that XLA keeps
    the cache in the layout the attention kernel reads; else (under a mesh,
    where GSPMD partitions it) by an XLA scatter."""
    kc, ks = _kv_encode(k)
    vc, vs = _kv_encode(v)
    sc = jnp.stack([ks, vs], -1)                        # (B, C, KH, 2)
    names = ("k", "v", "kv_scale")
    if pallas:
        from repro.kernels.abfp_decode_fused import append_kv_columns
        planes = append_kv_columns(
            *(kv_cache[n] for n in names),
            *(jnp.moveaxis(x, 1, -1) for x in (kc, vc, sc)),
            start=start, count=count, layer=kv_cache.get("layer"))
    else:
        b, c = k.shape[:2]
        offs = jnp.arange(c)[None, :]
        at = ((kv_cache["layer"],) if "layer" in kv_cache else ()) + (
            jnp.arange(b)[:, None], slice(None), slice(None),
            start[:, None] + offs)
        valid = offs < count[:, None]
        planes = [_put(kv_cache[n], at, x, valid)
                  for n, x in zip(names, (kc, vc, sc))]
    return {**kv_cache, **dict(zip(names, planes))}


def quantized_decode_attention(
    q: Array,
    k_codes: Array,
    v_codes: Array,
    scales: Array,
    *,
    lengths: Array,
) -> Array:
    """Decode attention directly on int8 KV codes (perf iteration 2 of the
    memory-bound decode cell): the per-position scale factors out of the
    dot product —

        q . k_t = (q . codes_t) * s_t / 127

    so the cache is read ONCE as int8 (+ tiny scale vectors) instead of
    int8-read + bf16-write + bf16-read of a dequantized copy.  Same math as
    dequantize-then-attend up to f32 rounding.

    q: (B, 1, H, D); codes: (B, KH, D, S) int8; scales: (B, KH, 2, S), the
    K scales in row 0 and the V scales in row 1.
    """
    b, _, h, d = q.shape
    kh = k_codes.shape[1]
    s_max = k_codes.shape[3]
    rep = h // kh
    qf = q.astype(jnp.float32) * (d ** -0.5)                 # (B, 1, H, D)
    qg = qf.reshape(b, kh, rep, d)                            # group by KV head
    s = _codes_dot(qg, k_codes, 2)                            # (B, KH, rep, S)
    s = s * (scales[:, :, 0:1].astype(jnp.float32) / 127.0)
    pos = jnp.arange(s_max)[None, None, None, :]
    s = jnp.where(pos < lengths[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)                            # (B, KH, rep, S)
    pv = p * (scales[:, :, 1:2].astype(jnp.float32) / 127.0)
    out = _codes_dot(pv, v_codes, 3)                          # (B, KH, rep, D)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def _codes_dot(x: Array, codes: Array, axis: int) -> Array:
    """Per (slot, KV head), the rows of x (B, KH, M, .) times the f32 codes
    (B, KH, D, S), contracting the codes' ``axis``: D for the scores, S
    for the values.  The decode and chunk readers, and the kernel, contract
    their rows in this one form, so a chunk's query rows round exactly as
    the decode ticks that would have produced them."""
    return jax.lax.dot_general(
        x, codes.astype(jnp.float32),
        dimension_numbers=(((3,), (axis,)), ((0, 1), (0, 1))))


# ---------------------------------------------------------------------------
# Chunked-prefill attention: append a whole prompt chunk to the cache and
# attend every chunk query at its own position in one pass.
# ---------------------------------------------------------------------------


def chunk_cache_attention(q: Array, k_cache: Array, v_cache: Array,
                          *, q_pos: Array) -> Array:
    """S-query attention against a (non-ring) cache buffer.

    q: (B, S, H, D); caches: (B, S_max, KH, D); ``q_pos``: (B, S) global
    position of each query — query (b, t) attends cache slots <= q_pos[b, t].
    Mirrors ``decode_attention``'s einsum layout (scores contract head_dim,
    PV contracts the full S_max buffer with masked p == 0) so each query row
    is bit-identical to the decode tick that would have produced it.
    """
    b, s, h, d = q.shape
    s_max = k_cache.shape[1]
    k = _repeat_kv(k_cache, h).astype(jnp.float32)
    v = _repeat_kv(v_cache, h).astype(jnp.float32)
    qf = q.astype(jnp.float32) * (d ** -0.5)
    sc = jnp.einsum("bshd,bchd->bhsc", qf, k)               # (B, H, S, S_max)
    mask = jnp.arange(s_max)[None, None, :] <= q_pos[:, :, None]
    sc = jnp.where(mask[:, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhsc,bchd->bhsd", p, v)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)          # (B, S, H, D)


def quantized_chunk_attention(
    q: Array,
    k_codes: Array,
    v_codes: Array,
    scales: Array,
    *,
    q_pos: Array,
) -> Array:
    """Chunked-prefill attention directly on int8 KV codes — the S-query
    generalization of ``quantized_decode_attention`` (same cache layout,
    same per-position scale factoring, same einsum layout per query row)."""
    b, s, h, d = q.shape
    kh = k_codes.shape[1]
    s_max = k_codes.shape[3]
    rep = h // kh
    qf = q.astype(jnp.float32) * (d ** -0.5)                 # (B, S, H, D)
    qg = jnp.moveaxis(qf.reshape(b, s, kh, rep, d), 1, 3)    # (B, KH, rep, S, D)
    sc = _codes_dot(qg.reshape(b, kh, rep * s, d), k_codes, 2)
    sc = sc.reshape(b, kh, rep, s, s_max)                    # (B, KH, rep, S, C)
    sc = sc * (scales[:, :, 0, None, None, :].astype(jnp.float32) / 127.0)
    mask = (jnp.arange(s_max)[None, None, :]
            <= q_pos[:, :, None])                            # (B, S, C)
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)                          # (B, KH, rep, S, C)
    pv = p * (scales[:, :, 1, None, None, :].astype(jnp.float32) / 127.0)
    out = _codes_dot(pv.reshape(b, kh, rep * s, s_max), v_codes, 3)
    out = jnp.moveaxis(out.reshape(b, kh, rep, s, d), 3, 1)  # (B, S, KH, rep, D)
    return out.reshape(b, s, h, d).astype(q.dtype)


def _append_attend_one(q: Array, k: Array, v: Array, kv_cache: dict,
                       window: int, valid: Optional[Array] = None, *,
                       pallas: bool = False, fused: bool = False):
    """Append ONE token's K/V and attend — the decode-tick attention core.

    q: (B, 1, H, D); k, v: (B, 1, KH, D).  Shared by the S=1 decode path and
    the ring-buffer chunk scan, so both run the same ops (bit-identical by
    construction).  ``valid`` (B,) False leaves a slot's cache and length
    as they were (the chunk scan's padding lanes).  ``pallas``: one device,
    so a quantized cache is written by the Pallas append kernel
    (``_q_write``); ``fused`` appends and attends in one Pallas kernel,
    which reads and writes the cache where it lies
    (``_fused_decode_attention_block``, window 0).  Returns (out (B, 1, H,
    D), new_cache).
    """
    b = q.shape[0]
    quantized = "kv_scale" in kv_cache
    s_max = kv_cache["k"].shape[-1 if quantized else 1]
    length = kv_cache["length"]                         # (B,)
    slot = (length % s_max) if window > 0 else length   # ring for window
    bidx = jnp.arange(b)
    filled = jnp.minimum(length + 1, s_max) if window > 0 else length + 1
    if quantized:
        # ABFP-quantized cache (beyond-paper, DESIGN.md): int8 codes +
        # per-(token, head) max-abs scale over the head_dim vector.
        # Attention runs directly on the codes (no dequantized copy).
        if fused:
            from repro.kernels.abfp_decode_fused import (
                fused_quantized_decode_attention,
            )
            (kc, ks), (vc, vs) = _kv_encode(k[:, 0]), _kv_encode(v[:, 0])
            out, kcache, vcache, scales = fused_quantized_decode_attention(
                q, kv_cache["k"], kv_cache["v"], kv_cache["kv_scale"],
                kc, vc, jnp.stack([ks, vs], -1), lengths=filled,
                layer=kv_cache.get("layer"))
            new_cache = {**kv_cache, "k": kcache, "v": vcache,
                         "kv_scale": scales}
        else:
            count = (jnp.ones_like(length) if valid is None
                     else valid.astype(length.dtype))
            new_cache = _q_write(kv_cache, slot, count, k, v, pallas)
            out = quantized_decode_attention(q, *_q_planes(new_cache),
                                             lengths=filled)
    else:
        new_cache = {
            "k": _put(kv_cache["k"], (bidx, slot), k[:, 0], valid),
            "v": _put(kv_cache["v"], (bidx, slot), v[:, 0], valid)}
        out = decode_attention(q, new_cache["k"], new_cache["v"],
                               lengths=filled)
    new_cache["length"] = length + (1 if valid is None
                                    else valid.astype(length.dtype))
    return out, new_cache


def chunk_append_attend(q: Array, k: Array, v: Array, kv_cache: dict,
                        *, n_tokens: Array, window: int,
                        pallas: bool = False):
    """Append up to S new K/V per slot and attend all S chunk queries — the
    chunked-prefill attention core.

    q: (B, S, H, D); k, v: (B, S, KH, D); ``n_tokens``: (B,) int32 — tokens
    0..n-1 of slot b's chunk are real, the rest padding.  A slot with
    n_tokens == 0 keeps its cache slice bit-for-bit unchanged (padding lanes
    write nothing).  ``pallas``: as for ``_append_attend_one``.

    Two regimes:
      * window == 0 (append-only cache): scatter the chunk, then one batched
        masked attention over the cache buffer, laid out exactly like
        ``decode_attention`` — bit-identical to S decode ticks, with the
        MXU-friendly (S queries at once) shape.
      * window > 0 (ring buffer): scan token-by-token through the exact
        decode core.  A mid-chunk query may need keys that LATER chunk
        tokens evict from the ring, so post-scatter attention is wrong; the
        scan also preserves decode's buffer layout, keeping bit-identity.
        Only the attention core is sequential — the projections around it
        stay batched.

    Returns (out (B, S, H, D), new_cache).
    """
    b, s = q.shape[:2]
    if window > 0:
        qs = jnp.moveaxis(q, 1, 0)[:, :, None]              # (S, B, 1, H, D)
        ks = jnp.moveaxis(k, 1, 0)[:, :, None]
        vs = jnp.moveaxis(v, 1, 0)[:, :, None]
        valid = jnp.arange(s)[:, None] < n_tokens[None, :]  # (S, B)

        def step(cache, xs):
            q_t, k_t, v_t, ok = xs
            out_t, new_cache = _append_attend_one(q_t, k_t, v_t, cache,
                                                  window, ok, pallas=pallas)
            return new_cache, out_t[:, 0]

        new_cache, outs = jax.lax.scan(step, kv_cache, (qs, ks, vs, valid))
        return jnp.moveaxis(outs, 0, 1), new_cache

    length = kv_cache["length"]                             # (B,)
    offs = jnp.arange(s)[None, :]
    valid = offs < n_tokens[:, None]                        # (B, S)
    # Padding lanes are DROPPED (sent past the cache's end, scatter
    # mode="drop"): untouched slots stay bit-identical, and no padding lane
    # can land on a real token's position and win a duplicate-index race.
    bidx = jnp.arange(b)[:, None]
    q_pos = length[:, None] + offs                          # (B, S) global
    if "kv_scale" in kv_cache:
        new_cache = _q_write(kv_cache, length, n_tokens, k, v, pallas)
        out = quantized_chunk_attention(q, *_q_planes(new_cache),
                                        q_pos=q_pos)
    else:
        new_cache = {"k": _put(kv_cache["k"], (bidx, q_pos), k, valid),
                     "v": _put(kv_cache["v"], (bidx, q_pos), v, valid)}
        out = chunk_cache_attention(q, new_cache["k"], new_cache["v"],
                                    q_pos=q_pos)
    new_cache["length"] = length + n_tokens
    return out, new_cache


# ---------------------------------------------------------------------------
# Paged KV cache: pool + page-table indirection (serving.pages owns the
# host-side allocator; these are the device-side scatter/gather paths).
# ---------------------------------------------------------------------------


def _paged_view(pool: Array, table: Array) -> Array:
    """Gather a dense per-slot cache view out of the page pool.

    pool: (NP, PS, ...); table: (B, MP) int32 physical page per logical
    page (sentinel NP for unallocated entries — the gather clamps, and the
    garbage it reads sits at positions >= the slot's length, masked to
    -1e30 by the attention cores exactly like unpaged out-of-range slots).
    Returns (B, MP*PS, ...)."""
    np_ = pool.shape[0]
    g = pool[jnp.clip(table, 0, np_ - 1)]           # (B, MP, PS, ...)
    b, mp, ps = g.shape[:3]
    return g.reshape((b, mp * ps) + g.shape[3:])


def _paged_scatter(pool: Array, table: Array, pos: Array, vals: Array,
                   valid: Optional[Array] = None) -> Array:
    """Scatter per-lane values into the pool at global cache positions.

    pool: (NP, PS, ...); table: (B, MP); pos: (B, S) global positions;
    vals: (B, S, ...).  Lanes routed to a sentinel table entry (or past the
    table) are DROPPED — dead slots, whose rows are all sentinel, can never
    write into pages a live slot owns.  ``valid`` False lanes write back
    the value already there (a bit-identical no-op), mirroring
    ``chunk_append_attend``'s padding contract."""
    np_, ps = pool.shape[:2]
    mp = table.shape[1]
    page_idx = pos // ps
    off = pos % ps
    page = jnp.take_along_axis(table, jnp.clip(page_idx, 0, mp - 1), axis=1)
    page = jnp.where(page_idx >= mp, np_, page)     # past-table -> drop lane
    vals = vals.astype(pool.dtype)
    if valid is not None:
        old = pool[jnp.clip(page, 0, np_ - 1), off]
        sel = valid.reshape(valid.shape + (1,) * (vals.ndim - 2))
        vals = jnp.where(sel, vals, old)
    return pool.at[page, off].set(vals, mode="drop")


def paged_append_attend(q: Array, k: Array, v: Array, kv_cache: dict,
                        table: Array, *, n_tokens: Optional[Array] = None):
    """Decode / chunked-prefill attention against a PAGED cache.

    kv_cache: {"k_pages": (NP, PS, KH, D), "v_pages": ..., "length": (B,)}
    plus ``k_scale_pages``/``v_scale_pages`` for the quantized cache;
    ``table``: (B, MP) slot→page map.  New K/V are scattered at each slot's
    next positions, then the pool is gathered through the table into a
    dense (B, MP*PS, ...) view feeding the SAME attention cores as the
    unpaged cache.  When MP*PS equals the unpaged ``max_len`` the compute
    graph is identical on identical values, so float-mode decode is
    bit-identical to the unpaged path: garbage in unallocated pages scores
    -1e30 after masking and contributes exact zeros to the softmax, the
    same as unpaged out-of-range slots (tests/test_pages.py).

    q: (B, S, H, D); S == 1 with ``n_tokens`` None is the decode tick, else
    the chunked-prefill append (same padding semantics as
    ``chunk_append_attend``).  Window/ring caches are never paged — the
    engine gates paging to append-only full-attention models.
    """
    b, s = q.shape[:2]
    length = kv_cache["length"]
    decode = s == 1 and n_tokens is None
    if decode:
        pos = length[:, None]
        valid = None
        n_add = jnp.ones((b,), jnp.int32)
    else:
        n = n_tokens if n_tokens is not None else jnp.full((b,), s, jnp.int32)
        offs = jnp.arange(s)[None, :]
        valid = offs < n[:, None]
        pos = length[:, None] + jnp.minimum(offs, n[:, None])
        n_add = n
    q_pos = length[:, None] + jnp.arange(s)[None, :]
    quantized = "k_scale_pages" in kv_cache
    if quantized:
        kc, ks = _kv_encode(k)
        vc, vs = _kv_encode(v)
        kp = _paged_scatter(kv_cache["k_pages"], table, pos, kc, valid)
        vp = _paged_scatter(kv_cache["v_pages"], table, pos, vc, valid)
        ksp = _paged_scatter(kv_cache["k_scale_pages"], table, pos, ks, valid)
        vsp = _paged_scatter(kv_cache["v_scale_pages"], table, pos, vs, valid)
        new_cache = {"k_pages": kp, "v_pages": vp, "k_scale_pages": ksp,
                     "v_scale_pages": vsp, "length": length + n_add}
        # The dense view, in the unpaged cache's layout for the readers:
        # (B, KH, D, S) codes, (B, KH, 2, S) scales.
        codes = [jnp.transpose(_paged_view(a, table), (0, 2, 3, 1))
                 for a in (kp, vp)]
        scales = jnp.stack([jnp.swapaxes(_paged_view(a, table), 1, 2)
                            for a in (ksp, vsp)], axis=2)
        if decode:
            out = quantized_decode_attention(q, *codes, scales,
                                             lengths=length + 1)
        else:
            out = quantized_chunk_attention(q, *codes, scales, q_pos=q_pos)
    else:
        kp = _paged_scatter(kv_cache["k_pages"], table, pos, k, valid)
        vp = _paged_scatter(kv_cache["v_pages"], table, pos, v, valid)
        new_cache = {"k_pages": kp, "v_pages": vp, "length": length + n_add}
        if decode:
            out = decode_attention(q, _paged_view(kp, table),
                                   _paged_view(vp, table), lengths=length + 1)
        else:
            out = chunk_cache_attention(q, _paged_view(kp, table),
                                        _paged_view(vp, table), q_pos=q_pos)
    return out, new_cache


# ---------------------------------------------------------------------------
# Attention block (projections through Numerics)
# ---------------------------------------------------------------------------


def init_attention(key, mcfg, layer_shape=()) -> dict:
    d, h, kh = mcfg.d_model, mcfg.num_heads, mcfg.num_kv_heads
    hd = mcfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    shape = lambda *s: layer_shape + s  # noqa: E731
    init = lambda k, *s: (  # noqa: E731
        jax.random.normal(k, shape(*s)) * std).astype(mcfg.param_dtype)
    return {
        "wq": init(k1, d, h * hd),
        "wk": init(k2, d, kh * hd),
        "wv": init(k3, d, kh * hd),
        "wo": init(k4, h * hd, d),
    }


def _fused_decode_attention_block(params, x, mcfg, nx, *, positions,
                                  kv_cache):
    """One fused-kernel decode tick of ``attention_block``.

    Replaces the packed chain's three ``nx.dense`` projection dispatches
    with ONE ``fused_qkv_packed_pallas`` launch and the jnp quantized-KV
    attention with the ``fused_quantized_decode_attention`` Pallas kernel
    (``kernels.abfp_decode_fused``) — bit-identical to the chain by
    construction (tests/test_fused.py, tests/test_sharded_serving.py).

    PRNG contract: the packed chain folds ``(base key, call counter)`` per
    ``Numerics.dense`` call; the fused launch consumes the SAME three
    (key, counter) pairs for wq/wk/wv — one per weight segment — and bumps
    the counter identically, so the wo projection (and every later layer)
    sees an unchanged stream.
    """
    from repro.kernels.abfp_decode_fused import fused_qkv_dense

    b, s, _ = x.shape
    h, kh, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.resolved_head_dim

    keys = []
    for _ in range(3):                       # wq, wk, wv — in chain order
        key = None
        if nx._key is not None and nx.quant.noise_lsb > 0.0:
            key = jax.random.fold_in(nx._key, nx._count)
        nx._count += 1
        keys.append(key)
    yq, yk, yv = fused_qkv_dense(
        x, (params["wq"], params["wk"], params["wv"]), nx.quant, keys,
        nx.mesh)
    q = yq.reshape(b, s, h, hd)
    k = yk.reshape(b, s, kh, hd)
    v = yv.reshape(b, s, kh, hd)
    if mcfg.pos_type == "rope":
        q = rope(q, positions, mcfg.rope_theta, mcfg.rope_fraction)
        k = rope(k, positions, mcfg.rope_theta, mcfg.rope_fraction)

    # ``_append_attend_one``'s quantized branch, with the attention einsum
    # chain swapped for the Pallas kernel.  Under a mesh the jnp form runs
    # instead: it is bit-identical to the kernel (enforced by test) and
    # partitions under GSPMD, which a pallas_call does not.
    out, new_cache = _append_attend_one(q, k, v, kv_cache, 0,
                                        fused=nx.mesh is None)
    return nx.dense(out.reshape(b, s, h * hd), params["wo"]), new_cache


def _use_fused_decode(params, nx, s, kv_cache, cross_kv, window, n_tokens):
    """Does this ``attention_block`` call hit the fused decode fast path?

    Fused mode + a single-token decode tick on an (unpaged, un-windowed)
    quantized KV cache with all three projection weights packed.  Anything
    else — prefill chunks, float/paged/windowed caches, unpacked weights —
    falls back to the packed chain, which computes the same numbers
    dispatch-by-dispatch (gains included, via ``dense_packed``).
    """
    return (nx.quant.mode == "abfp_fused"
            and s == 1 and n_tokens is None and window == 0
            and kv_cache is not None and cross_kv is None
            and "kv_scale" in kv_cache
            and all(isinstance(params[w], PackedWeight)
                    for w in ("wq", "wk", "wv")))


def attention_block(
    params: dict,
    x: Array,
    mcfg,
    nx: "Numerics",
    *,
    positions: Array,
    causal: bool = True,
    window: int = 0,
    kv_cache: Optional[dict] = None,
    cross_kv: Optional[tuple] = None,
    train_mode: bool = False,
    n_tokens: Optional[Array] = None,
    page_table: Optional[Array] = None,
):
    """Self- (or cross-) attention with optional KV cache for decode.

    Returns (output, new_kv_cache).  ``kv_cache``: {"k": (B,S,KH,D),
    "v": ..., "length": (B,)} — ring buffer when window > 0 — or the int8
    cache (``_q_write``'s layout), whose arrays may be stacked over layers
    with a "layer" index.
    ``train_mode`` selects the q-chunked remat attention (backward-memory
    bounded); inference uses the kv-chunked online-softmax path.

    With a cache and S > 1 (or ``n_tokens`` given) this is the chunked
    prefill path: x holds a prompt chunk, ``n_tokens`` (B,) marks how many
    of its S tokens are real per slot (None == all S), and the whole chunk
    is appended + attended in one pass (``chunk_append_attend``).

    A PAGED cache ({"k_pages": ..., ...}, see serving.pages) requires
    ``page_table`` (B, MP) and routes through ``paged_append_attend``.
    """
    b, s, _ = x.shape
    h, kh, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.resolved_head_dim

    if _use_fused_decode(params, nx, s, kv_cache, cross_kv, window,
                         n_tokens):
        # abfp_fused decode tick: one fused QKV launch + Pallas quantized
        # attention, bit-identical to the chain below at matching gains.
        return _fused_decode_attention_block(
            params, x, mcfg, nx, positions=positions, kv_cache=kv_cache)

    q = nx.dense(x, params["wq"]).reshape(b, s, h, hd)
    if cross_kv is None:
        k = nx.dense(x, params["wk"]).reshape(b, s, kh, hd)
        v = nx.dense(x, params["wv"]).reshape(b, s, kh, hd)
        if mcfg.pos_type == "rope":
            q = rope(q, positions, mcfg.rope_theta, mcfg.rope_fraction)
            k = rope(k, positions, mcfg.rope_theta, mcfg.rope_fraction)
    else:
        k, v = cross_kv

    new_cache = None
    if kv_cache is not None and cross_kv is None and "k_pages" in kv_cache:
        assert page_table is not None, "paged kv_cache needs a page_table"
        out, new_cache = paged_append_attend(q, k, v, kv_cache, page_table,
                                             n_tokens=n_tokens)
    elif kv_cache is not None and cross_kv is None:
        if s == 1 and n_tokens is None:
            # Decode: append this step's K/V, attend over the filled cache.
            out, new_cache = _append_attend_one(
                q, k, v, kv_cache, window, pallas=nx.mesh is None)
        else:
            # Chunked prefill: append + attend a whole prompt chunk.
            n = (n_tokens if n_tokens is not None
                 else jnp.full((b,), s, jnp.int32))
            out, new_cache = chunk_append_attend(
                q, k, v, kv_cache, n_tokens=n, window=window,
                pallas=nx.mesh is None)
    elif cross_kv is not None:
        if train_mode:
            out = train_attention(q, k, v, causal=False,
                                  q_chunk=mcfg.attn_chunk)
        elif mcfg.use_flash_attention:
            from repro.kernels.flash_attention import flash_attention
            out = flash_attention(q, k, v, causal=False)
        else:
            out = chunked_attention(q, k, v, causal=False,
                                    chunk=mcfg.attn_chunk)
    elif train_mode:
        out = train_attention(q, k, v, causal=causal, window=window,
                              q_chunk=mcfg.attn_chunk)
    elif mcfg.use_flash_attention:
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(q, k, v, causal=causal, window=window)
    else:
        out = chunked_attention(
            q, k, v, causal=causal, window=window,
            q_offset=0, chunk=mcfg.attn_chunk)

    out = out.reshape(b, s, h * hd)
    return nx.dense(out, params["wo"]), new_cache


# ---------------------------------------------------------------------------
# MLP (swiglu / geglu / gelu)
# ---------------------------------------------------------------------------


def init_mlp(key, mcfg, layer_shape=()) -> dict:
    d, f = mcfg.d_model, mcfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    shape = lambda *s: layer_shape + s  # noqa: E731
    p = {
        "wi": (jax.random.normal(k1, shape(d, f)) * d**-0.5).astype(mcfg.param_dtype),
        "wo": (jax.random.normal(k2, shape(f, d)) * f**-0.5).astype(mcfg.param_dtype),
    }
    if mcfg.mlp_type in ("swiglu", "geglu"):
        p["wg"] = (jax.random.normal(k3, shape(d, f))
                   * d**-0.5).astype(mcfg.param_dtype)
    return p


def mlp_block(params: dict, x: Array, mcfg, nx: "Numerics") -> Array:
    h = nx.dense(x, params["wi"])
    if mcfg.mlp_type == "swiglu":
        g = nx.dense(x, params["wg"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    elif mcfg.mlp_type == "geglu":
        g = nx.dense(x, params["wg"])
        h = jax.nn.gelu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return nx.dense(h, params["wo"])


# ---------------------------------------------------------------------------
# im2col (utility: how the paper maps convs onto tiled matmuls, Sec. V)
# ---------------------------------------------------------------------------


def im2col(x: Array, kh: int, kw: int, stride: int = 1) -> Array:
    """(B, H, W, C) -> (B, H', W', kh*kw*C) patches so a conv becomes a
    matmul that ABFP can tile — the paper's treatment of ResNet50 convs."""
    b, hh, ww, c = x.shape
    oh = (hh - kh) // stride + 1
    ow = (ww - kw) // stride + 1
    idx_h = (jnp.arange(oh) * stride)[:, None] + jnp.arange(kh)[None, :]
    idx_w = (jnp.arange(ow) * stride)[:, None] + jnp.arange(kw)[None, :]
    patches = x[:, idx_h[:, None, :, None], idx_w[None, :, None, :], :]
    return patches.reshape(b, oh, ow, kh * kw * c)
