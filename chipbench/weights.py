"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the serving engine takes (per-layer leaves stacked
over a leading layer axis under ``groups[0]``), so the benchmark hands it
to the program as is, and the reference reads the same tree.  Matrices are
N(0, 1/fan_in) in bfloat16, the type they are served in; norm weights are
float32 offsets from 1, drawn N(0, 0.1); the MoE router is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: the low 32 bits seed it, the
    high bits are folded in (a plain ``PRNGKey`` drops them)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def shapes(cfg: dict) -> dict:
    """{leaf path: (shape, dtype, fan_in or None for a norm weight)}."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        ("embed",): ((v, d), bf, d),
        ("final_norm", "scale"): ((d,), f32, None),
        ("lm_head",): ((d, v), bf, d),
        ("norm1", "scale"): ((L, d), f32, None),
        ("norm2", "scale"): ((L, d), f32, None),
        ("attn", "wq"): ((L, d, h * hd), bf, d),
        ("attn", "wk"): ((L, d, kh * hd), bf, d),
        ("attn", "wv"): ((L, d, kh * hd), bf, d),
        ("attn", "wo"): ((L, h * hd, d), bf, h * hd),
    }
    if cfg.get("num_local_experts"):
        e = cfg["num_local_experts"]
        out.update({
            ("moe", "router"): ((L, d, e), f32, d),
            ("moe", "wi"): ((L, e, d, f), bf, d),
            ("moe", "wg"): ((L, e, d, f), bf, d),
            ("moe", "wo"): ((L, e, f, d), bf, f),
        })
    else:
        out.update({
            ("mlp", "wi"): ((L, d, f), bf, d),
            ("mlp", "wg"): ((L, d, f), bf, d),
            ("mlp", "wo"): ((L, f, d), bf, f),
        })
    return out


def _tree(flat: dict) -> dict:
    """Nest the flat leaves into the engine's layout."""
    top = {"embed": flat[("embed",)], "lm_head": flat[("lm_head",)],
           "final_norm": {"scale": flat[("final_norm", "scale")]},
           "extra": ()}
    layer: dict = {}
    for path, leaf in flat.items():
        if path[0] in ("embed", "lm_head", "final_norm"):
            continue
        layer.setdefault(path[0], {})[path[1]] = leaf
    top["groups"] = (layer,)
    return top


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    flat = {}
    for i, (path, (shape, dtype, fan_in)) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if fan_in is None:
            flat[path] = (z * NORM_STD).astype(dtype)
        else:
            flat[path] = (z * fan_in ** -0.5).astype(dtype)
    return _tree(flat)


def make_weights(cfg: dict, seed: int) -> dict:
    """The weights of configuration ``cfg`` for ``seed``, on the device."""
    spec = tuple(sorted(shapes(cfg).items()))
    return _make(seed_key(seed), spec)
