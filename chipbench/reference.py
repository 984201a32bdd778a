"""Plain float32 reference: a GQA / RoPE / SwiGLU decoder, dense or top-k MoE.

Written from the configuration file alone, in jax.numpy at the highest
matmul precision, with no kernels, cache or batching, and nothing imported
from the program.  It reads the weights the benchmark made (``weights.py``)
and follows the model as configured:

  x   = embed[tokens] * embedding_multiplier
  per layer:
    h = rmsnorm(x) * (1 + norm1)
    q, k, v = h Wq, h Wk, h Wv;  RoPE (rotate-half) on q and k
    a = softmax(q k^T * attention_multiplier, causal) v   (query head i
        reads key/value head i // (heads / kv_heads))
    x = x + residual_multiplier * a Wo
    h = rmsnorm(x) * (1 + norm2)
    dense: y = (silu(h Wg) * h Wi) Wo
    MoE:   p = softmax(h R); the top k of p, renormalised to sum 1, weigh
           the same SwiGLU of each chosen expert
    x = x + residual_multiplier * y
  logits = (rmsnorm(x) * (1 + final_norm)) W_head / logits_scaling

``attention_multiplier`` defaults to head_dim ** -0.5, every other
multiplier to 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

f32 = jnp.float32


def _rmsnorm(x, offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset.astype(f32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = pos[:, None].astype(f32) * freq                    # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, wi, wg, wo):
    return (jax.nn.silu(h @ wg.astype(f32)) * (h @ wi.astype(f32))) \
        @ wo.astype(f32)


def _moe(h, lw, k):
    probs = jax.nn.softmax(h @ lw["router"].astype(f32), axis=-1)   # (S, E)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    n_exp = probs.shape[-1]
    # Weight of each expert for each token: its gate if chosen, else 0.
    weight = jnp.sum(jax.nn.one_hot(ids, n_exp, dtype=f32)
                     * gates[..., None], axis=1)                    # (S, E)

    def one(y, xs):
        wi, wg, wo, w_e = xs
        return y + _swiglu(h, wi, wg, wo) * w_e[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (lw["wi"], lw["wg"], lw["wo"], weight.T))
    return y


def _layer(cfg, x, lw):
    s = x.shape[0]
    h_, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    res = cfg.get("residual_multiplier", 1.0)
    scale = cfg.get("attention_multiplier", hd ** -0.5)
    pos = jnp.arange(s)
    a = lw["attn"]
    h = _rmsnorm(x, lw["norm1"]["scale"], eps)
    q = (h @ a["wq"].astype(f32)).reshape(s, h_, hd)
    k = (h @ a["wk"].astype(f32)).reshape(s, kh, hd)
    v = (h @ a["wv"].astype(f32)).reshape(s, kh, hd)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, h_ // kh, axis=1)
    v = jnp.repeat(v, h_ // kh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * scale
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
    x = x + res * (o.reshape(s, h_ * hd) @ a["wo"].astype(f32))
    h = _rmsnorm(x, lw["norm2"]["scale"], eps)
    if "moe" in lw:
        y = _moe(h, lw["moe"], cfg["num_experts_per_tok"])
    else:
        m = lw["mlp"]
        y = _swiglu(h, m["wi"], m["wg"], m["wo"])
    return x + res * y


def _config_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=(0,))
def _logits(cfg_key, weights, tokens):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32) \
            * cfg.get("embedding_multiplier", 1.0)
        x, _ = jax.lax.scan(lambda x, lw: (_layer(cfg, x, lw), None), x,
                            weights["groups"][0])
        x = _rmsnorm(x, weights["final_norm"]["scale"], cfg["rms_norm_eps"])
        logits = x @ weights["lm_head"].astype(f32)
    return logits / cfg.get("logits_scaling", 1.0)


def logits(cfg: dict, weights: dict, tokens) -> jax.Array:
    """Reference logits (S, V) float32 for one token sequence (S,)."""
    return _logits(_config_key(cfg), weights, jnp.asarray(tokens, jnp.int32))


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(cfg_key, weights, tokens, start, n):
    z = _logits(cfg_key, weights, tokens)                    # (S, V)
    s = tokens.shape[0]
    j = jnp.arange(s)
    # Row j predicts token j + 1; served tokens sit at start .. start+n-1.
    tgt = jnp.roll(tokens, -1)
    gap = jnp.max(z, axis=-1) - jnp.take_along_axis(z, tgt[:, None], 1)[:, 0]
    mine = (j >= start - 1) & (j < start - 1 + n)
    return jnp.max(jnp.where(mine, gap, -jnp.inf)), \
        jnp.sum(jnp.where(mine, gap, 0.0))


def served_gaps(cfg: dict, weights: dict, prompt, served, pad_to: int):
    """Widest and summed gap, over the served tokens of one request, by
    which the served token's reference logit lies below the reference's
    best at that position.  The sequence is zero-padded to ``pad_to``
    (causal attention keeps padding out of every row that is read)."""
    seq = list(prompt) + list(served)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens > pad_to {pad_to}")
    toks = jnp.zeros((pad_to,), jnp.int32).at[:len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    widest, total = _gaps(_config_key(cfg), weights, toks,
                          jnp.int32(len(prompt)), jnp.int32(len(served)))
    return float(widest), float(total)
