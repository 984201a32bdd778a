"""Model assembly: decoder-only / hybrid / ssm / encoder-decoder LMs.

Layers are grouped by the repeating ``block_pattern`` and scanned with
stacked weights (`jax.lax.scan` over groups), so HLO size — and 512-device
SPMD compile time — is independent of depth (61-layer Kimi compiles one
scanned block).  Remainder layers (pattern not dividing num_layers) are
applied unrolled.

Forward modes:
  * ``forward``       — teacher-forced logits for train / full-sequence eval.
  * ``decode_step``   — one token with carried per-layer state (KV cache,
    ring-buffer window cache, or recurrent state), O(1) per token.
  * ``prefill``       — a whole prompt CHUNK with carried state in one pass
    (serving admission): same state semantics as ``decode_step`` but S =
    chunk, with a per-slot valid-token count so prefilling and decoding
    slots coexist in a batch.  Bit-identical to S decode steps in float
    mode.
  * ``forward_capture`` — unrolled paired FLOAT/ABFP pass returning per-layer
    differential-noise samples for DNF (paper Fig. 3).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.abfp import QuantConfig
from repro.core.dnf import NoiseHistogram
from repro.models import moe as moe_lib
from repro.models import recurrent as rec_lib
from repro.models.layers import (
    Numerics,
    _use_fused_decode,
    attention_block,
    init_attention,
    init_mlp,
    mlp_block,
    norm,
    sinusoidal_positions,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _norm_params(mcfg, shape=()):
    p = {"scale": jnp.zeros(shape + (mcfg.d_model,), jnp.float32)}
    if mcfg.norm_type == "layernorm":
        p["scale"] = jnp.ones(shape + (mcfg.d_model,), jnp.float32)
        p["bias"] = jnp.zeros(shape + (mcfg.d_model,), jnp.float32)
    return p


def _init_layer(key, mcfg: ModelConfig, kind: str, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": _norm_params(mcfg)}
    if kind == "attention":
        p["attn"] = init_attention(ks[0], mcfg)
        p["norm2"] = _norm_params(mcfg)
        if mcfg.num_experts:
            p["moe"] = moe_lib.init_moe(ks[1], mcfg)
        elif mcfg.d_ff:
            p["mlp"] = init_mlp(ks[1], mcfg)
        if cross:
            p["cross"] = init_attention(ks[2], mcfg)
            p["norm3"] = _norm_params(mcfg)
    elif kind == "recurrent":
        p["rglru"] = rec_lib.init_rglru_block(ks[0], mcfg)
        p["norm2"] = _norm_params(mcfg)
        p["mlp"] = init_mlp(ks[1], mcfg)
    elif kind == "mlstm":
        p["mlstm"] = rec_lib.init_mlstm_block(ks[0], mcfg)
    elif kind == "slstm":
        p["slstm"] = rec_lib.init_slstm_block(ks[0], mcfg)
    else:
        raise ValueError(kind)
    return p


def _pattern(mcfg: ModelConfig):
    pattern = mcfg.block_pattern or ("attention",)
    n_groups = mcfg.num_layers // len(pattern)
    remainder = mcfg.num_layers % len(pattern)
    return pattern, n_groups, remainder


def init_params(key: Array, mcfg: ModelConfig) -> dict:
    pattern, n_groups, remainder = _pattern(mcfg)
    keys = jax.random.split(key, 8)

    params: dict = {
        "embed": (jax.random.normal(keys[0], (mcfg.vocab_size, mcfg.d_model))
                  * mcfg.d_model**-0.5).astype(mcfg.param_dtype),
        "final_norm": _norm_params(mcfg),
    }
    cross = mcfg.is_encoder_decoder

    # Stacked pattern groups: one sub-init per pattern position, vmapped over
    # groups so every leaf gets a leading (n_groups,) axis.
    group_params = []
    for j, kind in enumerate(pattern):
        gkeys = jax.random.split(jax.random.fold_in(keys[1], j), n_groups)
        group_params.append(
            jax.vmap(lambda k, kind=kind: _init_layer(k, mcfg, kind, cross))(gkeys))
    params["groups"] = tuple(group_params)

    extra = []
    for r in range(remainder):
        kind = pattern[r]
        extra.append(_init_layer(jax.random.fold_in(keys[2], r), mcfg, kind, cross))
    params["extra"] = tuple(extra)

    if not mcfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[3], (mcfg.d_model, mcfg.vocab_size))
            * mcfg.d_model**-0.5).astype(mcfg.param_dtype)

    if mcfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[4], mcfg.num_encoder_layers)
        params["encoder"] = {
            "layers": jax.vmap(
                lambda k: _init_layer(k, mcfg, "attention", cross=False))(ekeys),
            "final_norm": _norm_params(mcfg),
        }
    return params


def param_count(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _apply_layer(
    lp: dict,
    x: Array,
    mcfg: ModelConfig,
    kind: str,
    nx: Numerics,
    *,
    positions: Array,
    state: Optional[dict] = None,
    enc_kv: Optional[tuple] = None,
    mesh=None,
    n_tokens: Optional[Array] = None,
    page_table: Optional[Array] = None,
):
    """One layer (pre-norm residual).  Returns (x, new_state, aux_loss).

    ``n_tokens`` (B,) marks the chunked-prefill path: x holds a prompt
    chunk of which only the first n_tokens[b] positions are real per slot;
    state updates for the padding (and for slots with n == 0) are no-ops.
    ``page_table`` (B, MP) routes paged KV caches (see serving.pages).
    """
    aux = jnp.float32(0.0)
    new_state: Any = None
    if kind == "attention":
        window = mcfg.window_size if mcfg.attention_type == "hybrid" else 0
        h = norm(x, lp["norm1"], mcfg.norm_type)
        attn_out, kv = attention_block(
            lp["attn"], h, mcfg, nx, positions=positions,
            window=window, kv_cache=(state or {}).get("kv"),
            train_mode=mcfg.remat, n_tokens=n_tokens,
            page_table=page_table)
        x = x + attn_out
        new_state = {"kv": kv} if kv is not None else None
        if enc_kv is not None:
            h = norm(x, lp["norm3"], mcfg.norm_type)
            cross_out, _ = attention_block(
                lp["cross"], h, mcfg, nx, positions=positions, cross_kv=enc_kv,
                train_mode=mcfg.remat)
            x = x + cross_out
        h = norm(x, lp["norm2"], mcfg.norm_type)
        if mcfg.num_experts:
            if mesh is not None:
                y, aux = moe_lib.moe_block_sharded(lp["moe"], h, mcfg, nx, mesh)
            else:
                y, aux = moe_lib.moe_block(lp["moe"], h, mcfg, nx)
        elif mcfg.d_ff:
            y = mlp_block(lp["mlp"], h, mcfg, nx)
        else:
            y = jnp.zeros_like(x)
        x = x + y
    elif kind == "recurrent":
        h = norm(x, lp["norm1"], mcfg.norm_type)
        y, st = rec_lib.rglru_block(lp["rglru"], h, mcfg, nx,
                                    state=(state or {}).get("rec"),
                                    n_tokens=n_tokens)
        x = x + y
        new_state = {"rec": st}
        h = norm(x, lp["norm2"], mcfg.norm_type)
        x = x + mlp_block(lp["mlp"], h, mcfg, nx)
    elif kind == "mlstm":
        h = norm(x, lp["norm1"], mcfg.norm_type)
        y, st = rec_lib.mlstm_block(lp["mlstm"], h, mcfg, nx,
                                    state=(state or {}).get("rec"),
                                    n_tokens=n_tokens)
        x = x + y
        new_state = {"rec": st}
    elif kind == "slstm":
        h = norm(x, lp["norm1"], mcfg.norm_type)
        y, st = rec_lib.slstm_block(lp["slstm"], h, mcfg, nx,
                                    state=(state or {}).get("rec"),
                                    n_tokens=n_tokens)
        x = x + y
        new_state = {"rec": st}
    else:
        raise ValueError(kind)
    return x, new_state, aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(params, tokens_or_embeds, mcfg, positions):
    if jnp.issubdtype(tokens_or_embeds.dtype, jnp.integer):
        x = jnp.take(params["embed"], tokens_or_embeds, axis=0)
    else:
        x = tokens_or_embeds.astype(mcfg.param_dtype)  # stub frontends
    x = x.astype(mcfg.activation_dtype)
    if mcfg.embed_scale:
        x = x * jnp.asarray(mcfg.d_model**0.5, x.dtype)
    if mcfg.pos_type == "absolute":
        x = x + sinusoidal_positions(positions, mcfg.d_model).astype(x.dtype)
    return x


def _lm_head(params, x, mcfg, nx: Numerics):
    # An explicit "lm_head" entry wins even for tied embeddings: the packed
    # serving path (models.packing) inserts a pre-quantized embed.T there.
    if "lm_head" in params:
        w = params["lm_head"]
    else:
        assert mcfg.tie_embeddings
        w = params["embed"].T
    return nx.dense(x, w).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Encoder (enc-dec models)
# ---------------------------------------------------------------------------


def encode(params, features: Array, mcfg, nx: Numerics) -> Array:
    """Whisper-style encoder over stub frame embeddings (B, S_enc, d)."""
    b, s, _ = features.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = features.astype(mcfg.activation_dtype)
    x = x + sinusoidal_positions(positions, mcfg.d_model).astype(x.dtype)

    def body(x, xs):
        lp, g = xs
        nxg = nx.fold(1000 + g)
        h = norm(x, lp["norm1"], mcfg.norm_type)
        attn_out, _ = attention_block(lp["attn"], h, mcfg, nxg,
                                      positions=positions, causal=False,
                                      train_mode=mcfg.remat)
        x = x + attn_out
        h = norm(x, lp["norm2"], mcfg.norm_type)
        x = x + mlp_block(lp["mlp"], h, mcfg, nxg)
        return x, None

    n_enc = mcfg.num_encoder_layers
    x, _ = jax.lax.scan(body, x, (params["encoder"]["layers"], jnp.arange(n_enc)))
    return norm(x, params["encoder"]["final_norm"], mcfg.norm_type)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(
    params: dict,
    tokens: Array,
    mcfg: ModelConfig,
    nx: Optional[Numerics] = None,
    *,
    encoder_features: Optional[Array] = None,
    dnf: Optional[NoiseHistogram] = None,
    dnf_key: Optional[Array] = None,
    mesh=None,
    return_hidden: bool = False,
):
    """Teacher-forced forward.  ``tokens``: (B, S) int ids or (B, S, d)
    stub-frontend embeddings.  Returns (logits (B, S, V) f32, aux_loss), or
    (hidden (B, S, d), aux_loss) with ``return_hidden`` (the chunked-loss
    path avoids materializing full-vocab logits)."""
    nx = nx or Numerics(QuantConfig(mode="float"))
    b, s = tokens.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(params, tokens, mcfg, positions)

    enc_kv = None
    if mcfg.is_encoder_decoder:
        assert encoder_features is not None
        enc_out = encode(params, encoder_features, mcfg, nx)
        enc_kv = _cross_kv(params, enc_out, mcfg, nx)   # per-pattern-pos, (NG,...)

    pattern, n_groups, remainder = _pattern(mcfg)
    glen = len(pattern)

    def body(carry, xs):
        x, aux = carry
        gparams, g_enc_kv, g = xs
        new_aux = aux
        for j, kind in enumerate(pattern):
            nxj = nx.fold(g * glen + j)
            lidx = g * glen + j
            ek = g_enc_kv[j] if g_enc_kv is not None else None
            x, _, a = _apply_layer(
                gparams[j], x, mcfg, kind, nxj,
                positions=positions, enc_kv=ek, mesh=mesh)
            new_aux = new_aux + a
            if dnf is not None:
                h = dnf.layer(lidx)
                key_l = jax.random.fold_in(dnf_key, lidx)
                x = x + h.sample(key_l, x.shape).astype(x.dtype)
        return (x, new_aux), None

    scan_body = jax.checkpoint(body) if mcfg.remat else body
    (x, aux), _ = jax.lax.scan(
        scan_body, (x, jnp.float32(0.0)),
        (params["groups"], enc_kv, jnp.arange(n_groups)))

    for r in range(remainder):
        kind = pattern[r]
        lidx = n_groups * glen + r
        # Remainder layers only occur for non-enc-dec patterns (no cross-attn).
        x, _, a = _apply_layer(
            params["extra"][r], x, mcfg, kind, nx.fold(lidx),
            positions=positions, enc_kv=None, mesh=mesh)
        aux = aux + a
        if dnf is not None:
            h = dnf.layer(lidx)
            x = x + h.sample(jax.random.fold_in(dnf_key, lidx), x.shape).astype(x.dtype)

    x = norm(x, params["final_norm"], mcfg.norm_type)
    if return_hidden:
        return x, aux
    logits = _lm_head(params, x, mcfg, nx.fold(999_983))
    return logits, aux


def lm_head_logits(params, hidden: Array, mcfg: ModelConfig,
                   nx: Optional[Numerics] = None) -> Array:
    """Project (B, S, d) hidden states to f32 logits (chunked-loss helper)."""
    nx = nx or Numerics(QuantConfig(mode="float"))
    return _lm_head(params, hidden, mcfg, nx.fold(999_983))


def encode_cross_kv(params, enc_out, mcfg, nx):
    """Public wrapper over ``_cross_kv`` for the serving path: precompute
    the cross-attention K/V a decoder consumes from an encoder output —
    the per-slot encoder cache ``serving.runners.EncDecRunner`` scatters
    into the decode state at admission."""
    return _cross_kv(params, enc_out, mcfg, nx)


def _cross_kv(params, enc_out, mcfg, nx):
    """Precompute encoder K/V per decoder layer (whisper cross-attention)."""
    b, s, _ = enc_out.shape
    kh, hd = mcfg.num_kv_heads, mcfg.resolved_head_dim

    def per_group(gparams):
        k = nx.dense(enc_out, gparams["cross"]["wk"]).reshape(b, s, kh, hd)
        v = nx.dense(enc_out, gparams["cross"]["wv"]).reshape(b, s, kh, hd)
        return k, v

    # Stacked over groups: vmap over the group axis of the params.  Returns a
    # list over pattern positions, each (k, v) with leading (n_groups,) axis.
    return [jax.vmap(per_group, in_axes=0, out_axes=0)(gp)
            for gp in params["groups"]]


# ---------------------------------------------------------------------------
# Decode (one token, carried state)
# ---------------------------------------------------------------------------


def init_decode_state(mcfg: ModelConfig, batch: int, max_len: int, *,
                      page_size: Optional[int] = None,
                      pool_pages: Optional[int] = None) -> dict:
    """Allocate per-layer decode state, stacked over scan groups.

    With ``page_size``/``pool_pages`` set, full-attention KV caches become
    PAGED: each layer holds a global ``(pool_pages, page_size, ...)`` pool
    shared by all slots, and the state gains a ``page_table`` (batch,
    max_pages) int32 leaf (initialized to the sentinel ``pool_pages``)
    mapping each slot's logical pages to physical pool pages.  Window/ring
    caches and recurrent state are never paged — the serving engine gates
    paging to append-only full-attention models.
    """
    pattern, n_groups, remainder = _pattern(mcfg)
    kh, hd = mcfg.num_kv_heads, mcfg.resolved_head_dim
    dtype = mcfg.activation_dtype
    paged = page_size is not None
    if paged:
        assert pool_pages is not None and pool_pages >= 1
        max_pages = -(-max_len // page_size)

    def one(kind):
        if kind == "attention":
            window = mcfg.window_size if mcfg.attention_type == "hybrid" else 0
            cache_len = window if window > 0 else max_len
            if paged and window == 0:
                if mcfg.kv_quant:
                    return {"kv": {
                        "k_pages": jnp.zeros(
                            (pool_pages, page_size, kh, hd), jnp.int8),
                        "v_pages": jnp.zeros(
                            (pool_pages, page_size, kh, hd), jnp.int8),
                        "k_scale_pages": jnp.zeros(
                            (pool_pages, page_size, kh), jnp.bfloat16),
                        "v_scale_pages": jnp.zeros(
                            (pool_pages, page_size, kh), jnp.bfloat16),
                        "length": jnp.zeros((batch,), jnp.int32),
                    }}
                return {"kv": {
                    "k_pages": jnp.zeros(
                        (pool_pages, page_size, kh, hd), dtype),
                    "v_pages": jnp.zeros(
                        (pool_pages, page_size, kh, hd), dtype),
                    "length": jnp.zeros((batch,), jnp.int32),
                }}
            if mcfg.kv_quant:
                # ABFP-quantized cache: int8 codes + per-(token, head)
                # scale, in the layout the decode attention kernel reads
                # (models.layers._q_write).
                return {"kv": {
                    "k": jnp.zeros((batch, kh, hd, cache_len), jnp.int8),
                    "v": jnp.zeros((batch, kh, hd, cache_len), jnp.int8),
                    "kv_scale": jnp.zeros((batch, kh, 2, cache_len),
                                          jnp.bfloat16),
                    "length": jnp.zeros((batch,), jnp.int32),
                }}
            return {"kv": {
                "k": jnp.zeros((batch, cache_len, kh, hd), dtype),
                "v": jnp.zeros((batch, cache_len, kh, hd), dtype),
                "length": jnp.zeros((batch,), jnp.int32),
            }}
        if kind == "recurrent":
            r = mcfg.lru_width or mcfg.d_model
            return {"rec": {
                "conv": jnp.zeros((batch, mcfg.conv_width - 1, r), dtype),
                "h": jnp.zeros((batch, r), jnp.float32),
            }}
        if kind == "mlstm":
            inner = 2 * mcfg.d_model
            nh = mcfg.num_heads
            dh = inner // nh
            return {"rec": {
                "C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
                "n": jnp.zeros((batch, nh, dh), jnp.float32),
                "m": jnp.zeros((batch, nh), jnp.float32),
            }}
        if kind == "slstm":
            nh = mcfg.num_heads
            dh = mcfg.d_model // nh
            z = jnp.zeros((batch, nh, dh), jnp.float32)
            return {"rec": {"h": z, "c": z, "n": z,
                            "m": jnp.full((batch, nh, dh), -1e30, jnp.float32)}}
        raise ValueError(kind)

    def stack(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)

    state = {
        "groups": tuple(stack(one(kind), n_groups) for kind in pattern),
        "extra": tuple(one(pattern[r]) for r in range(remainder)),
        "position": jnp.zeros((batch,), jnp.int32),
    }
    if paged:
        # Sentinel-initialized: every entry routes writes to the drop lane
        # until the engine allocates a page (serving.pages owns the host
        # mirror and refreshes this leaf before each jitted pass).
        state["page_table"] = jnp.full((batch, max_pages), pool_pages,
                                       jnp.int32)
    return state


def _carried_kv(layer_state) -> Optional[dict]:
    """The layer's int8 K/V cache, if it has one (``kv_scale``: unpaged)."""
    kv = (layer_state or {}).get("kv")
    return kv if kv is not None and "kv_scale" in kv else None


def _run_layers(params, state, x, mcfg: ModelConfig, nx: Numerics, *,
                positions, enc_kv=None, n_tokens=None):
    """Every layer of a decode tick or prefill chunk, with carried state.

    The scan over layer groups takes each layer's state as ``xs`` and gives
    it back as ``ys`` — except the int8 K/V caches, which ride in the scan's
    carry, stacked over groups: each layer writes its new positions into
    its own slice in place (``models.layers._q_write``) and its attention
    reads the slice where it lies, so no layer's cache is sliced out and
    stacked back.  Returns (x, group states, extra-layer states)."""
    pattern, n_groups, remainder = _pattern(mcfg)
    glen = len(pattern)
    pt = state.get("page_table")
    kvs = tuple(_carried_kv(st) for st in state["groups"])
    rest = tuple({} if kv is not None else st
                 for st, kv in zip(state["groups"], kvs))

    def body(carry, xs):
        x, kvs = carry
        gparams, gstate, g_enc_kv, g = xs
        new_kvs, new_states = [], []
        for j, kind in enumerate(pattern):
            st = gstate[j]
            if kvs[j] is not None:
                st = {"kv": {**kvs[j], "length": kvs[j]["length"][g],
                             "layer": g}}
            ek = g_enc_kv[j] if g_enc_kv is not None else None
            x, st, _ = _apply_layer(
                gparams[j], x, mcfg, kind, nx.fold(g * glen + j),
                positions=positions, state=st, enc_kv=ek,
                n_tokens=n_tokens, page_table=pt)
            if kvs[j] is not None:
                kv = st["kv"]
                new_kvs.append({"k": kv["k"], "v": kv["v"],
                                "kv_scale": kv["kv_scale"],
                                "length": kvs[j]["length"].at[g].set(
                                    kv["length"])})
                st = {}
            else:
                new_kvs.append(None)
            new_states.append(st)
        return (x, tuple(new_kvs)), tuple(new_states)

    (x, kvs), states = jax.lax.scan(
        body, (x, kvs),
        (params["groups"], rest, enc_kv, jnp.arange(n_groups)))
    groups = tuple(st if kv is None else {"kv": kv}
                   for st, kv in zip(states, kvs))

    extra = []
    for r in range(remainder):
        x, st, _ = _apply_layer(
            params["extra"][r], x, mcfg, pattern[r],
            nx.fold(n_groups * glen + r), positions=positions,
            state=state["extra"][r], enc_kv=None, n_tokens=n_tokens,
            page_table=pt)
        extra.append(st)
    return x, groups, tuple(extra)


def fused_decode_layers(params, state, mcfg: ModelConfig,
                        nx: Numerics) -> int:
    """How many layers' decode ticks run the fused attention kernel on the
    cache where it lies: ``models.layers._use_fused_decode`` per layer, on
    one device (under a mesh the jnp attention runs instead)."""
    if nx.mesh is not None:
        return 0
    pattern, n_groups, remainder = _pattern(mcfg)
    window = mcfg.window_size if mcfg.attention_type == "hybrid" else 0

    def fused(lp, st, kind):
        return kind == "attention" and _use_fused_decode(
            lp["attn"], nx, 1, (st or {}).get("kv"), None, window, None)

    return (n_groups * sum(fused(params["groups"][j], state["groups"][j], k)
                           for j, k in enumerate(pattern))
            + sum(fused(params["extra"][r], state["extra"][r], pattern[r])
                  for r in range(remainder)))


def decode_step(
    params: dict,
    state: dict,
    token: Array,
    mcfg: ModelConfig,
    nx: Optional[Numerics] = None,
    *,
    enc_kv=None,
):
    """One decode step.  token: (B,) int32 (or (B, d) embeds).
    Returns (logits (B, V) f32, new_state).

    With ``nx.quant.mode == "abfp_fused"`` (packed weights with per-tile
    ADC gains, quantized KV cache) every full-attention layer's tick runs
    the fused QKV + attention kernels instead of the dispatch chain —
    see ``models.layers._fused_decode_attention_block`` — with identical
    PRNG threading, so greedy decode matches the packed chain bit-for-bit
    at gain 1.0."""
    nx = nx or Numerics(QuantConfig(mode="float"))
    positions = state["position"][:, None]                   # (B, 1)
    tok = token[:, None] if token.ndim == 1 else token[:, None, :]
    x = _embed(params, tok, mcfg, positions)
    x, new_group_states, new_extra = _run_layers(
        params, state, x, mcfg, nx, positions=positions, enc_kv=enc_kv)

    x = norm(x, params["final_norm"], mcfg.norm_type)
    logits = _lm_head(params, x, mcfg, nx.fold(999_983))[:, 0]
    new_state = {
        "groups": new_group_states,
        "extra": new_extra,
        "position": state["position"] + 1,
    }
    if "page_table" in state:
        new_state["page_table"] = state["page_table"]
    return logits, new_state


# ---------------------------------------------------------------------------
# Chunked prefill (S = chunk generalization of decode_step)
# ---------------------------------------------------------------------------


def prefill(
    params: dict,
    state: dict,
    tokens: Array,
    n_tokens: Array,
    mcfg: ModelConfig,
    nx: Optional[Numerics] = None,
    *,
    enc_kv=None,
):
    """Advance slots by a whole prompt chunk in ONE jitted pass.

    tokens: (B, S) int32 prompt chunk per slot (padding values arbitrary);
    ``n_tokens``: (B,) int32 — tokens[b, :n_tokens[b]] are real.  A slot
    with n_tokens == 0 is left bit-for-bit untouched, so prefilling and
    decoding slots can share the batch.  Returns (logits (B, V) f32 taken
    at each slot's LAST valid token, new_state).

    Prompt admission cost drops from O(prompt_len) sequential decode ticks
    to O(prompt_len / chunk) passes whose matmuls run at M = B*S — the
    MXU-friendly shapes the packed ABFP kernel was built for.

    Numerics: in ``mode="float"`` the result is bit-identical to feeding
    the same tokens through ``decode_step`` one at a time (the projections
    batch over the chunk, while order-sensitive state updates — KV append,
    ring-buffer window attention, recurrent folds — run as scans of the
    exact decode-step ops; see tests/test_prefill.py).  ABFP modes are
    statistically equivalent only: the Pallas noise PRNG salts by grid
    position, and a chunked matmul grid differs from S decode-shaped grids.
    """
    nx = nx or Numerics(QuantConfig(mode="float"))
    s = tokens.shape[1]
    positions = state["position"][:, None] + jnp.arange(s)[None, :]
    x = _embed(params, tokens, mcfg, positions)
    x, new_group_states, new_extra = _run_layers(
        params, state, x, mcfg, nx, positions=positions, enc_kv=enc_kv,
        n_tokens=n_tokens)

    x = norm(x, params["final_norm"], mcfg.norm_type)
    last = jnp.clip(n_tokens - 1, 0, s - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (B, 1, d)
    logits = _lm_head(params, x_last, mcfg, nx.fold(999_983))[:, 0]
    new_state = {
        "groups": new_group_states,
        "extra": new_extra,
        "position": state["position"] + n_tokens,
    }
    if "page_table" in state:
        new_state["page_table"] = state["page_table"]
    return logits, new_state


# ---------------------------------------------------------------------------
# On-device sampling (overlapped serving keeps tokens as device arrays)
# ---------------------------------------------------------------------------


def sample_tokens(
    logits: Array,
    temperatures: Array,
    uids: Array,
    token_idxs: Array,
    seed: int,
) -> Array:
    """Sample one next token per batch row ON DEVICE.

    logits: (B, V) f32; temperatures: (B,) f32; uids / token_idxs: (B,)
    int32.  Rows with ``temperature == 0`` decode greedily — ``jnp.argmax``
    breaks ties at the first occurrence exactly like ``np.argmax``, so
    greedy device sampling is bit-identical to the host path.  Rows with
    ``temperature > 0`` draw from the temperature-scaled softmax using a
    per-row stream keyed by ``(seed, uid, token_idx)`` (a jax PRNG
    ``fold_in`` chain), so draws are reproducible for a given engine seed
    no matter how the scheduler interleaves requests across ticks — the
    same contract as the host sampler, though the two PRNGs draw different
    (equally valid) samples.  Returns (B,) int32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(row, t, uid, idx):
        k = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), uid), idx)
        safe_t = jnp.where(t > 0, t, 1.0)
        return jax.random.categorical(k, row / safe_t).astype(jnp.int32)

    sampled = jax.vmap(draw)(logits, temperatures, uids, token_idxs)
    return jnp.where(temperatures > 0, sampled, greedy)


# ---------------------------------------------------------------------------
# DNF paired capture (unrolled; smoke/finetune scale)
# ---------------------------------------------------------------------------


def forward_capture(
    params: dict,
    tokens: Array,
    mcfg: ModelConfig,
    nx_float: Numerics,
    nx_abfp_factory,
    *,
    encoder_features=None,
):
    """Paper Fig. 3: run each layer in FLOAT on the FLOAT stream, also run the
    ABFP version of the layer on the SAME input, and collect dy = ABFP - FLOAT
    per layer.  Unrolled (python loop) — used once, on one batch.

    ``nx_abfp_factory()`` must return a fresh ABFP Numerics per layer call.
    Returns (logits, [dy_1, ..., dy_L]) with dy in f32.
    """
    b, s = tokens.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(params, tokens, mcfg, positions)

    enc_kv = None
    if mcfg.is_encoder_decoder:
        enc_out = encode(params, encoder_features, mcfg, nx_float)
        enc_kv = _cross_kv(params, enc_out, mcfg, nx_float)

    pattern, n_groups, remainder = _pattern(mcfg)
    glen = len(pattern)
    deltas = []

    def layer_at(j, g):
        return jax.tree.map(lambda p: p[g], params["groups"][j])

    for g in range(n_groups):
        for j, kind in enumerate(pattern):
            lidx = g * glen + j
            lp = layer_at(j, g)
            ek = enc_kv[j] if enc_kv is not None else None
            ekg = jax.tree.map(lambda a: a[g], ek) if ek is not None else None
            x_f, _, _ = _apply_layer(lp, x, mcfg, kind, nx_float.fold(lidx),
                                     positions=positions, enc_kv=ekg)
            x_q, _, _ = _apply_layer(lp, x, mcfg, kind,
                                     nx_abfp_factory().fold(lidx),
                                     positions=positions, enc_kv=ekg)
            deltas.append((x_q.astype(jnp.float32) - x_f.astype(jnp.float32)))
            x = x_f                                           # FLOAT stream
    for r in range(remainder):
        kind = pattern[r]
        lidx = n_groups * glen + r
        lp = params["extra"][r]
        x_f, _, _ = _apply_layer(lp, x, mcfg, kind, nx_float.fold(lidx),
                                 positions=positions, enc_kv=None)
        x_q, _, _ = _apply_layer(lp, x, mcfg, kind, nx_abfp_factory().fold(lidx),
                                 positions=positions, enc_kv=None)
        deltas.append((x_q.astype(jnp.float32) - x_f.astype(jnp.float32)))
        x = x_f

    x = norm(x, params["final_norm"], mcfg.norm_type)
    logits = _lm_head(params, x, mcfg, nx_float.fold(999_983))
    return logits, deltas
