"""Recurrent temporal-mixing blocks: RG-LRU (Griffin / recurrentgemma),
mLSTM and sLSTM (xLSTM).

Projections (input/gate/output linears) run through ``Numerics.dense`` so
ABFP applies to them; the recurrence internals are elementwise / gated state
updates — range-sensitive, so they stay in digital FLOAT32, exactly the
paper's rule for norm-like ops (DESIGN.md §Arch-applicability).

Training/prefill uses parallel forms where the math allows:
  * RG-LRU — ``jax.lax.associative_scan`` over the linear recurrence.
  * mLSTM  — chunkwise linear attention with log-space gate stabilization.
  * sLSTM  — inherently sequential (recurrent weights inside the gates);
    ``jax.lax.scan`` over time.
Decode is a single recurrent step with a constant-size carried state — this
is what makes the long_500k shape servable for ssm/hybrid archs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import Numerics

Array = jax.Array


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (Griffin)
# ---------------------------------------------------------------------------

_RGLRU_C = 8.0


def init_rglru_block(key, mcfg, layer_shape=()) -> dict:
    d = mcfg.d_model
    r = mcfg.lru_width or d
    ks = jax.random.split(key, 7)
    shape = lambda *s: layer_shape + s  # noqa: E731
    init = lambda k, fan_in, *s: (  # noqa: E731
        jax.random.normal(k, shape(*s)) * fan_in**-0.5).astype(mcfg.param_dtype)
    # Lambda init so a = sigmoid(lam)^c is in ~[0.9, 0.999] (Griffin A.2).
    u = jax.random.uniform(ks[6], shape(r), minval=0.9**2, maxval=0.999**2)
    lam = jnp.log(u ** (1.0 / _RGLRU_C) / (1 - u ** (1.0 / _RGLRU_C)))
    return {
        "w_in": init(ks[0], d, d, r),
        "w_gate": init(ks[1], d, d, r),
        "conv_w": (jax.random.normal(ks[2], shape(mcfg.conv_width, r))
                   * mcfg.conv_width**-0.5).astype(mcfg.param_dtype),
        "w_rg": init(ks[3], r, r, r),       # recurrence gate
        "w_ig": init(ks[4], r, r, r),       # input gate
        "w_out": init(ks[5], r, r, d),
        "lam": lam.astype(jnp.float32),
    }


def _causal_depthwise_conv(u: Array, w: Array, state: Optional[Array],
                           n_tokens: Optional[Array] = None):
    """u: (B, S, R), w: (W, R) depthwise causal conv.  ``state``: last W-1
    inputs from the previous call (decode).  Returns (out, new_state).

    ``n_tokens`` (chunked prefill): only the first n_tokens[b] positions of
    u are real; the carried tail is then the last W-1 inputs of the VALID
    prefix (per-slot gather), so a slot with n == 0 keeps its state exactly.
    """
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((u.shape[0], width - 1, u.shape[2]), u.dtype)
    ext = jnp.concatenate([state, u], axis=1)               # (B, W-1+S, R)
    out = sum(
        ext[:, i : i + u.shape[1]] * w[i][None, None] for i in range(width)
    )
    if width == 1:
        new_state = state
    elif n_tokens is None:
        new_state = ext[:, -(width - 1):]
    else:
        idx = n_tokens[:, None] + jnp.arange(width - 1)[None, :]  # (B, W-1)
        new_state = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
    return out, new_state


def rglru_block(params, x: Array, mcfg, nx: Numerics,
                state: Optional[dict] = None,
                n_tokens: Optional[Array] = None):
    """Griffin recurrent block.  Returns (y, new_state); state carries the
    conv tail and the LRU hidden h — O(1) memory per token (long-context).

    ``n_tokens`` (B,) selects the chunked-prefill path: the projections run
    batched over the chunk while the h recurrence folds SEQUENTIALLY (same
    per-step op as decode, so the carried state is bit-identical to feeding
    the chunk token by token); positions >= n_tokens[b] leave slot b's
    state untouched.
    """
    gate = jax.nn.gelu(nx.dense(x, params["w_gate"]).astype(jnp.float32))
    u = nx.dense(x, params["w_in"])

    conv_state = state["conv"] if state else None
    u, new_conv = _causal_depthwise_conv(u, params["conv_w"], conv_state,
                                         n_tokens=n_tokens)

    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(nx.dense(u, params["w_rg"]).astype(jnp.float32))
    i = jax.nn.sigmoid(nx.dense(u, params["w_ig"]).astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(params["lam"]) * r    # (B, S, R)
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * uf)

    h0 = state["h"] if state else None
    if n_tokens is not None:
        assert h0 is not None, "chunked prefill needs a carried state"
        valid = jnp.arange(x.shape[1])[:, None] < n_tokens[None, :]  # (S, B)

        def stepf(h, xs):
            a_t, b_t, ok = xs
            h = jnp.where(ok[:, None], a_t * h + b_t, h)      # decode-step op
            return h, h

        h, hs = jax.lax.scan(
            stepf, h0,
            (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0), valid))
        hs = jnp.moveaxis(hs, 0, 1)
    elif x.shape[1] == 1 and h0 is not None:
        h = a[:, 0] * h0 + b[:, 0]                            # decode step
        hs = h[:, None]
    else:
        if h0 is not None:
            b = b.at[:, 0].add(a[:, 0] * h0)
        # h_t = a_t h_{t-1} + b_t  via associative scan over S.
        def op(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2
        _, hs = jax.lax.associative_scan(op, (a, b), axis=1)
        h = hs[:, -1]

    y = nx.dense((hs * gate).astype(x.dtype), params["w_out"])
    return y, {"conv": new_conv, "h": h}


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM) — chunkwise parallel linear attention with exp gates
# ---------------------------------------------------------------------------


def init_mlstm_block(key, mcfg, layer_shape=()) -> dict:
    d = mcfg.d_model
    inner = 2 * d                                   # xLSTM pf=2 up-projection
    nh = mcfg.num_heads
    ks = jax.random.split(key, 8)
    shape = lambda *s: layer_shape + s  # noqa: E731
    init = lambda k, fan, *s: (  # noqa: E731
        jax.random.normal(k, shape(*s)) * fan**-0.5).astype(mcfg.param_dtype)
    return {
        "w_up": init(ks[0], d, d, inner),
        "w_gate": init(ks[1], d, d, inner),
        "wq": init(ks[2], inner, inner, inner),
        "wk": init(ks[3], inner, inner, inner),
        "wv": init(ks[4], inner, inner, inner),
        "w_if": init(ks[5], inner, inner, 2 * nh),  # input+forget gate logits
        "w_down": init(ks[6], inner, inner, d),
        "skip_scale": jnp.zeros(shape(inner), jnp.float32),
    }


def _mlstm_chunk_scan(q, k, v, log_i, log_f, state, chunk):
    """Chunkwise stabilized mLSTM.  q,k,v: (B, NH, S, D); gates (B, NH, S).
    state: (C (B,NH,D,D), n (B,NH,D), m (B,NH)).  Returns (h, new_state).

    A position with log_i = -1e30 and log_f = 0 (no input, no decay)
    leaves the carried state exactly unchanged: the padding semantics.
    """
    b, nh, s, dh = q.shape
    pad = (-s) % chunk
    if pad:
        padf = lambda a, fill=0.0: jnp.pad(  # noqa: E731
            a, [(0, 0)] * (a.ndim - 1) + [(0, pad)] if a.ndim == 3 else
            [(0, 0), (0, 0), (0, pad), (0, 0)], constant_values=fill)
        q, k, v = padf(q), padf(k), padf(v)
        log_i = jnp.pad(log_i, ((0, 0), (0, 0), (0, pad)),
                        constant_values=-1e30)
        log_f = jnp.pad(log_f, ((0, 0), (0, 0), (0, pad)))
    sp = s + pad
    nc = sp // chunk
    # (NC, B, NH, c, D) chunked views.
    cq = jnp.moveaxis(q.reshape(b, nh, nc, chunk, dh), 2, 0)
    ck = jnp.moveaxis(k.reshape(b, nh, nc, chunk, dh), 2, 0)
    cv = jnp.moveaxis(v.reshape(b, nh, nc, chunk, dh), 2, 0)
    cli = jnp.moveaxis(log_i.reshape(b, nh, nc, chunk), 2, 0)
    clf = jnp.moveaxis(log_f.reshape(b, nh, nc, chunk), 2, 0)

    def step(carry, xs):
        cmat, n, m = carry                         # (B,NH,D,D),(B,NH,D),(B,NH)
        qc, kc, vc, li, lf = xs
        csum = jnp.cumsum(lf, axis=-1)             # (B, NH, c)
        total = csum[..., -1]
        # Decay from chunk start to position t (inclusive of f_t).
        # Inter-chunk stabilizer: m_inter[t] = csum[t] + m_prev.
        m_inter = csum + m[..., None]
        # Intra-chunk log weights: A[t, s] = csum[t] - csum[s] + li[s], s <= t.
        a_log = csum[..., :, None] - csum[..., None, :] + li[..., None, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        a_log = jnp.where(tri[None, None], a_log, -1e30)
        m_intra = jnp.max(a_log, axis=-1)          # (B, NH, c)
        m_new = jnp.maximum(m_inter, m_intra)      # running max per position
        # Stabilized weights.
        a = jnp.exp(a_log - m_new[..., None])      # (B, NH, c, c)
        inter_w = jnp.exp(m_inter - m_new)         # (B, NH, c)
        # Output: inter-chunk (state) + intra-chunk contributions.
        h_inter = jnp.einsum("bhcd,bhde->bhce", qc, cmat) * inter_w[..., None]
        n_inter = jnp.einsum("bhcd,bhd->bhc", qc, n) * inter_w
        scores = jnp.einsum("bhcd,bhsd->bhcs", qc, kc) * (dh ** -0.5)
        h_intra = jnp.einsum("bhcs,bhcs,bhse->bhce", scores, a, vc)
        n_intra = jnp.einsum("bhcs,bhcs->bhc", scores, a)
        denom = jnp.maximum(jnp.abs(n_inter + n_intra), jnp.exp(-m_new)) + 1e-6
        h = (h_inter + h_intra) / denom[..., None]
        # State update to end of chunk (stabilized by m_end).
        m_end = jnp.maximum(total + m, jnp.max(csum[..., -1:] - csum + li,
                                               axis=-1))
        decay_state = jnp.exp(total + m - m_end)   # (B, NH)
        k_w = jnp.exp(total[..., None] - csum + li - m_end[..., None])
        cmat_new = cmat * decay_state[..., None, None] + jnp.einsum(
            "bhs,bhsd,bhse->bhde", k_w, kc * (dh ** -0.5), vc)
        n_new = n * decay_state[..., None] + jnp.einsum(
            "bhs,bhsd->bhd", k_w, kc * (dh ** -0.5))
        return (cmat_new, n_new, m_end), h

    new_state, hs = jax.lax.scan(step, state, (cq, ck, cv, cli, clf))
    h = jnp.moveaxis(hs, 0, 2).reshape(b, nh, sp, dh)[:, :, :s]
    return h, new_state


def mlstm_block(params, x: Array, mcfg, nx: Numerics,
                state: Optional[dict] = None, chunk: int = 128,
                n_tokens: Optional[Array] = None):
    """xLSTM mLSTM block.  Returns (y, new_state).

    ``n_tokens`` (B,) selects the chunked-prefill path: projections batched
    over the chunk, state update run at chunk=1 (one token per scan step —
    the same arithmetic as a decode tick, so the carried state is
    bit-identical to token-by-token), padding positions masked out.
    """
    b, s, d = x.shape
    nh = mcfg.num_heads
    up = nx.dense(x, params["w_up"])
    gate = jax.nn.silu(nx.dense(x, params["w_gate"]).astype(jnp.float32))
    inner = up.shape[-1]
    dh = inner // nh

    q = nx.dense(up, params["wq"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    k = nx.dense(up, params["wk"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    v = nx.dense(up, params["wv"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    gl = nx.dense(up, params["w_if"]).astype(jnp.float32)     # (B, S, 2NH)
    log_i = gl[..., :nh].transpose(0, 2, 1)                   # (B, NH, S)
    log_f = jax.nn.log_sigmoid(gl[..., nh:]).transpose(0, 2, 1)

    if state is None:
        state = {
            "C": jnp.zeros((b, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((b, nh, dh), jnp.float32),
            "m": jnp.zeros((b, nh), jnp.float32),
        }
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    if n_tokens is not None:
        # Padding positions get the scan's own padding gates (no input,
        # no decay), which keep the state exactly: the step then runs the
        # same arithmetic as a decode tick, with no select around it.
        chunk_eff = 1
        valid = (jnp.arange(s)[None, :] < n_tokens[:, None])[:, None, :]
        log_i = jnp.where(valid, log_i, -1e30)
        log_f = jnp.where(valid, log_f, 0.0)
    else:
        chunk_eff = min(chunk, max(s, 1))
    h, (c_new, n_new, m_new) = _mlstm_chunk_scan(
        qf, kf, vf, log_i, log_f,
        (state["C"], state["n"], state["m"]), chunk_eff)
    h = h.transpose(0, 2, 1, 3).reshape(b, s, inner)
    h = h + (params["skip_scale"][None, None].astype(jnp.float32)
             * up.astype(jnp.float32))
    y = nx.dense((h * gate).astype(x.dtype), params["w_down"])
    return y, {"C": c_new, "n": n_new, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM) — sequential scalar-memory recurrence
# ---------------------------------------------------------------------------


def init_slstm_block(key, mcfg, layer_shape=()) -> dict:
    d = mcfg.d_model
    nh = mcfg.num_heads
    dh = d // nh
    ks = jax.random.split(key, 4)
    shape = lambda *s: layer_shape + s  # noqa: E731
    # 4 gates (i, f, z, o) from input and recurrent (block-diagonal) paths.
    return {
        "w_x": (jax.random.normal(ks[0], shape(d, 4 * d)) * d**-0.5
                ).astype(mcfg.param_dtype),
        "r_h": (jax.random.normal(ks[1], shape(nh, dh, 4 * dh)) * dh**-0.5
                ).astype(mcfg.param_dtype),
        "b": jnp.zeros(shape(4 * d), jnp.float32),
        # GeGLU projection pair: up to 2*(4d/3)-ish — we use 2d split into two
        # d-wide halves (gate, value), down from d.
        "w_up": (jax.random.normal(ks[2], shape(d, 2 * d)) * d**-0.5
                 ).astype(mcfg.param_dtype),
        "w_down": (jax.random.normal(ks[3], shape(d, d)) * d**-0.5
                   ).astype(mcfg.param_dtype),
    }


def slstm_block(params, x: Array, mcfg, nx: Numerics,
                state: Optional[dict] = None,
                n_tokens: Optional[Array] = None):
    """xLSTM sLSTM block with exp input gate and stabilizer state.
    Sequential over time (recurrent gate weights).  Returns (y, new_state).

    ``n_tokens`` (B,): chunked-prefill padding mask — steps at or past
    n_tokens[b] leave slot b's state unchanged (the scan is already the
    decode-step fold, so chunked state == token-by-token state bitwise).
    """
    b, s, d = x.shape
    nh = mcfg.num_heads
    dh = d // nh

    gx = nx.dense(x, params["w_x"]).astype(jnp.float32) \
        + params["b"][None, None]                            # (B, S, 4d)
    r_h = params["r_h"].astype(jnp.float32)                  # (NH, dh, 4dh)

    if state is None:
        zeros = jnp.zeros((b, nh, dh), jnp.float32)
        state = {"h": zeros, "c": zeros,
                 "n": jnp.zeros((b, nh, dh), jnp.float32),
                 "m": jnp.full((b, nh, dh), -1e30, jnp.float32)}

    def step(carry, xs):
        gx_t, ok = xs
        h, c, n, m = carry                                   # (B, NH, dh)
        rec = jnp.einsum("bhd,hde->bhe", h, r_h)             # (B, NH, 4dh)
        g = gx_t.reshape(b, nh, 4 * dh) + rec
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        if n_tokens is not None:
            # Padding steps: no input (i = 0), no decay (f = 1), so c, n
            # and m stay exactly as they are with the same arithmetic as
            # a decode tick; only h needs the select.
            gi = jnp.where(ok[:, None, None], gi, -jnp.inf)
            gf = jnp.where(ok[:, None, None], gf, jnp.inf)
        log_f = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(log_f + m, gi)                   # stabilizer
        i = jnp.exp(gi - m_new)
        f = jnp.exp(log_f + m - m_new)
        z = jnp.tanh(gz)
        o = jax.nn.sigmoid(go)
        c_new = f * c + i * z
        n_new = f * n + i
        h_new = o * c_new / jnp.maximum(n_new, 1.0)
        if n_tokens is not None:
            h_new = jnp.where(ok[:, None, None], h_new, h)
        return (h_new, c_new, n_new, m_new), h_new

    gx_t = jnp.moveaxis(gx, 1, 0)                            # (S, B, 4d)
    valid = (jnp.arange(s)[:, None] < n_tokens[None, :]
             if n_tokens is not None else jnp.ones((s, b), bool))
    (h, c, n, m), hs = jax.lax.scan(
        step, (state["h"], state["c"], state["n"], state["m"]),
        (gx_t, valid))
    hs = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(x.dtype)

    up = nx.dense(hs, params["w_up"])
    u1, u2 = jnp.split(up, 2, axis=-1)
    y = nx.dense((jax.nn.gelu(u1.astype(jnp.float32)).astype(x.dtype) * u2),
                 params["w_down"])
    return y, {"h": h, "c": c, "n": n, "m": m}
