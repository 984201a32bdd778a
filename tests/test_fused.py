"""Fused decode kernel + per-tile gain suite (kernels.abfp_decode_fused).

Three contracts:

* BIT-IDENTITY — the fused QKV launch reproduces three stand-alone packed
  kernel calls exactly (noise on/off, decode and small-batch shapes); the
  Pallas quantized-KV attention reproduces the jnp einsum chain exactly;
  and the whole abfp_fused decode tick reproduces the abfp_packed chain at
  gain 1.0 (all-ones per-tile gains are exact f32 no-ops).
* GAIN SEMANTICS — adaptive per-tile gains are powers of two in
  [1, cfg.gain], all ones at gain 1, monotone in the cap, and amplification
  never increases error against the FLOAT32 oracle on random tiles (the
  paper's effective-precision claim).
* ROUND-TRIP — gains survive ``pack_model_params``, the serving engine's
  pack-at-init, and the fault-injection PackedWeight reconstructions.
"""

import dataclasses

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.abfp import (
    PackedWeight,
    QuantConfig,
    adaptive_tile_gains,
    pack_abfp_weight,
)
from repro.kernels.abfp_decode_fused import (
    fused_qkv_packed_pallas,
    fused_quantized_decode_attention,
)
from repro.kernels.abfp_matmul import abfp_matmul_packed_pallas
from repro.models import decode_step, init_decode_state, init_params
from repro.models.layers import Numerics, quantized_decode_attention
from repro.models.packing import pack_model_params
from repro.serving import Request, ServingEngine


def _mk_qkv(rng, k=256, cols=(384, 128, 128)):
    x = jnp.asarray(rng.normal(size=(1, k)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(k, n)) * 0.1, jnp.float32)
          for n in cols]
    return x, ws


# ---------------------------------------------------------------------------
# Fused QKV == three stand-alone packed calls
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile", [32, 128])
@pytest.mark.parametrize("noise", [0.0, 0.5])
@pytest.mark.parametrize("m", [1, 8])
def test_fused_qkv_bit_identical_to_packed_calls(tile, noise, m):
    rng = np.random.default_rng(hash((tile, m)) % 2**31)
    cfg = QuantConfig(mode="abfp_packed", tile_width=tile, gain=1.0,
                      noise_lsb=noise)
    x, ws = _mk_qkv(rng)
    x = jnp.tile(x, (m, 1))
    pws = tuple(pack_abfp_weight(w, cfg) for w in ws)
    seeds = (None,) * 3 if noise == 0.0 else tuple(
        jnp.int32(s) for s in (11, 22, 33))
    ref = [abfp_matmul_packed_pallas(x, pw, cfg, s)
           for pw, s in zip(pws, seeds)]
    got = fused_qkv_packed_pallas(x, pws, cfg, seeds)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(g, np.float32))


def test_fused_qkv_all_ones_gains_bit_identical_to_gain_free():
    """gain=1.0 adaptive pack (all-ones per-tile gains) is bit-identical to
    a gain-free pack: multiplying and dividing by exactly 1.0f changes no
    bits, and f32(adc_base_scale) * 1.0 == f32(adc_code_scale at G=1)."""
    rng = np.random.default_rng(0)
    cfg = QuantConfig(mode="abfp_fused", tile_width=32, gain=1.0,
                      noise_lsb=0.5)
    x, ws = _mk_qkv(rng)
    pws_g = tuple(pack_abfp_weight(w, cfg, adaptive_gain=True) for w in ws)
    pws = tuple(pack_abfp_weight(w, cfg) for w in ws)
    for pw in pws_g:
        assert pw.gains is not None
        np.testing.assert_array_equal(np.asarray(pw.gains), 1.0)
    seeds = tuple(jnp.int32(s) for s in (1, 2, 3))
    for r, g in zip(fused_qkv_packed_pallas(x, pws, cfg, seeds),
                    fused_qkv_packed_pallas(x, pws_g, cfg, seeds)):
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(g, np.float32))


def test_fused_qkv_rejects_mismatched_weights():
    rng = np.random.default_rng(3)
    cfg = QuantConfig(mode="abfp_packed", tile_width=32, noise_lsb=0.0)
    x, ws = _mk_qkv(rng)
    pws = [pack_abfp_weight(w, cfg) for w in ws]
    other = pack_abfp_weight(
        jnp.asarray(rng.normal(size=(128, 128)), jnp.float32), cfg)
    with pytest.raises(ValueError, match="share K"):
        fused_qkv_packed_pallas(x, (pws[0], pws[1], other), cfg)
    mixed = dataclasses.replace(
        pws[2], gains=jnp.ones((pws[2].num_tiles,), jnp.float32))
    with pytest.raises(ValueError, match="gains"):
        fused_qkv_packed_pallas(x, (pws[0], pws[1], mixed), cfg)


# ---------------------------------------------------------------------------
# Fused attention == jnp quantized_decode_attention
# ---------------------------------------------------------------------------


def _kv_cache(rng, b, kh, d, s):
    """A random int8 cache (codes, scales) and one new token per slot."""
    codes = lambda *sh: jnp.asarray(  # noqa: E731
        rng.integers(-127, 128, size=sh), jnp.int8)
    scales = lambda *sh: jnp.asarray(  # noqa: E731
        rng.uniform(0.1, 2.0, size=sh), jnp.bfloat16)
    return (codes(b, kh, d, s), codes(b, kh, d, s), scales(b, kh, 2, s),
            (codes(b, kh, d), codes(b, kh, d), scales(b, kh, 2)))


def _appended_np(kc, vc, sc, new, lengths):
    """The cache with each slot's new token at position lengths - 1."""
    out = [np.array(a) for a in (kc, vc, sc)]
    for a, col in zip(out, new):
        for i, n in enumerate(np.asarray(lengths)):
            a[i, ..., n - 1] = np.asarray(col)[i]
    return tuple(jnp.asarray(a) for a in out)


@pytest.mark.parametrize("kh,h", [(2, 8), (4, 4)])
def test_fused_attention_bit_identical(kh, h):
    rng = np.random.default_rng(kh * 17 + h)
    B, S, D = 3, 16, 64
    q = jnp.asarray(rng.normal(size=(B, 1, h, D)), jnp.bfloat16)
    kc, vc, sc, new = _kv_cache(rng, B, kh, D, S)
    ln = jnp.asarray([1, 7, 16], jnp.int32)
    ref = quantized_decode_attention(
        q, *_appended_np(kc, vc, sc, new, ln), lengths=ln)
    got, *_ = fused_quantized_decode_attention(q, kc, vc, sc, *new,
                                               lengths=ln)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(got, np.float32))


# ---------------------------------------------------------------------------
# Adaptive gain semantics
# ---------------------------------------------------------------------------


def test_adaptive_gains_pow2_bounded_and_monotone():
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.laplace(0, 0.05, size=(512, 256)), jnp.float32)
    prev = None
    for cap in (1.0, 2.0, 4.0, 8.0, 16.0):
        cfg = QuantConfig(mode="abfp_fused", tile_width=32, gain=cap,
                          noise_lsb=0.0)
        g = np.asarray(adaptive_tile_gains(pack_abfp_weight(w, cfg), cfg))
        assert g.shape == (512 // 32,)
        assert np.all(g >= 1.0) and np.all(g <= cap)
        np.testing.assert_array_equal(np.log2(g), np.round(np.log2(g)))
        if cap == 1.0:
            np.testing.assert_array_equal(g, 1.0)
        if prev is not None:
            assert np.all(g >= prev)        # raising the cap never lowers G_t
        prev = g


@pytest.mark.parametrize("tile", [32, 128])
def test_gain_sweep_error_monotone_non_increasing(tile):
    """The paper's claim, on random tiles: amplification raises effective
    output precision, so error vs the FLOAT32 oracle never increases as the
    adaptive gain cap grows (the conservative per-tile choice never
    clips)."""
    rng = np.random.default_rng(tile)
    k, n, m = 768, 256, 16
    w = jnp.asarray(rng.laplace(0, 0.04, size=(k, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    ref = np.asarray(x @ w)
    errs = []
    for cap in (1.0, 2.0, 4.0, 8.0, 16.0):
        cfg = QuantConfig(mode="abfp_fused", tile_width=tile, gain=cap,
                          noise_lsb=0.0, out_dtype=jnp.float32)
        pw = pack_abfp_weight(w, cfg, adaptive_gain=True)
        y = np.asarray(abfp_matmul_packed_pallas(x, pw, cfg))
        errs.append(float(np.mean(np.abs(y - ref))))
    for lo_cap, hi_cap in zip(errs, errs[1:]):
        assert hi_cap <= lo_cap * (1 + 1e-6), errs
    assert errs[-1] < errs[0]               # and the knob actually helps


# ---------------------------------------------------------------------------
# Round-trip: pack_model_params, engine, decode parity
# ---------------------------------------------------------------------------

PACKED1 = QuantConfig(mode="abfp_packed", tile_width=32, gain=1.0,
                      noise_lsb=0.5)
FUSED1 = QuantConfig(mode="abfp_fused", tile_width=32, gain=1.0,
                     noise_lsb=0.5)


@pytest.fixture(scope="module")
def tinyllama_kvq():
    mcfg = dataclasses.replace(smoke_config("tinyllama-1.1b"), kv_quant=True)
    return mcfg, init_params(jax.random.PRNGKey(0), mcfg)


def _packed_leaves(tree):
    return [l for l in jax.tree_util.tree_leaves(
                tree, is_leaf=lambda x: isinstance(x, PackedWeight))
            if isinstance(l, PackedWeight)]


def test_gains_round_trip_pack_model_params(tinyllama_kvq):
    mcfg, params = tinyllama_kvq
    fused = _packed_leaves(pack_model_params(params, FUSED1, mcfg))
    plain = _packed_leaves(pack_model_params(params, PACKED1, mcfg))
    assert fused and len(fused) == len(plain)
    assert all(pw.gains is not None for pw in fused)
    assert all(pw.gains.shape == pw.codes.shape[:-2] + (pw.num_tiles,)
               for pw in fused)
    assert all(pw.gains is None for pw in plain)
    # pytree round-trip preserves the gains leaf (engine jit relies on it)
    leaves, treedef = jax.tree_util.tree_flatten(fused[0])
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.gains is not None
    np.testing.assert_array_equal(np.asarray(back.gains),
                                  np.asarray(fused[0].gains))


def test_fused_decode_step_bit_identical_to_packed_chain(tinyllama_kvq):
    """Three greedy ticks through decode_step: the fused kernels (QKV +
    attention) emit the exact logits of the packed dispatch chain at
    gain 1.0, PRNG streams included."""
    mcfg, params = tinyllama_kvq
    key = jax.random.PRNGKey(9)
    tok0 = jnp.asarray([3, 5], jnp.int32)
    outs = {}
    for name, quant in (("packed", PACKED1), ("fused", FUSED1)):
        pk = pack_model_params(params, quant, mcfg)
        st, toks, seq = init_decode_state(mcfg, 2, 16), tok0, []
        for t in range(3):
            logits, st = decode_step(pk, st, toks, mcfg,
                                     Numerics(quant,
                                              jax.random.fold_in(key, t)))
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
            seq.append(np.asarray(logits))
        outs[name] = seq
    for a, b in zip(outs["packed"], outs["fused"]):
        np.testing.assert_array_equal(a, b)


def _filled_state(mcfg, lengths, s_max, seed=0):
    """A decode state whose int8 cache holds random codes and scales at
    every position, with the slots' lengths (and positions) set."""
    rng = np.random.default_rng(seed)
    st = init_decode_state(mcfg, len(lengths), s_max)
    kv = st["groups"][0]["kv"]
    ln = jnp.asarray(lengths, jnp.int32)
    kv = {"k": jnp.asarray(rng.integers(-127, 128, kv["k"].shape), jnp.int8),
          "v": jnp.asarray(rng.integers(-127, 128, kv["v"].shape), jnp.int8),
          "kv_scale": jnp.asarray(rng.uniform(0.1, 2.0, kv["kv_scale"].shape),
                                  jnp.bfloat16),
          "length": jnp.broadcast_to(ln, kv["length"].shape)}
    return {**st, "groups": ({"kv": kv},), "position": ln}


def test_fused_tick_appends_in_place(tinyllama_kvq):
    """One fused tick over slots of lengths 0, 5 and S - 1 writes each
    slot's new codes and scales at its own length only — bit-identical to
    the packed chain's — and leaves every other position byte-equal.  A
    free slot's length grows with every tick the engine runs; past the
    cache's end its tick writes nothing."""
    mcfg, params = tinyllama_kvq
    s_max, lengths = 16, [0, 5, 15, 19]
    before = _filled_state(mcfg, lengths, s_max)
    tok = jnp.asarray([3, 5, 7, 9], jnp.int32)
    after = {}
    for name, quant in (("packed", PACKED1), ("fused", FUSED1)):
        pk = pack_model_params(params, quant, mcfg)
        _, st = decode_step(pk, jax.tree.map(jnp.copy, before), tok, mcfg,
                            Numerics(quant, jax.random.PRNGKey(4)))
        after[name] = st["groups"][0]["kv"]
    old = before["groups"][0]["kv"]
    new = jnp.arange(s_max)[None, :] == jnp.asarray(lengths)[:, None]
    for leaf in ("k", "v", "kv_scale"):
        a, f, c = (np.asarray(t[leaf], np.float32)
                   for t in (old, after["fused"], after["packed"]))
        np.testing.assert_array_equal(f, c)
        at = np.broadcast_to(np.asarray(new)[None, :, None, None, :], a.shape)
        np.testing.assert_array_equal(f[~at], a[~at])
        assert (f[at] != a[at]).any()            # the tick did write
    np.testing.assert_array_equal(np.asarray(after["fused"]["length"]),
                                  np.asarray(old["length"]) + 1)


def _cache_moves(jaxpr, s_max, kh, found):
    """Equations outside the Pallas kernels whose output has both the
    cache-length and the KV-head axis (the scan's and jit's nesting is
    followed into, a kernel's body is not)."""
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if isinstance(v, (jex_core.Jaxpr, jex_core.ClosedJaxpr))]
        if eqn.primitive.name == "pallas_call":
            continue
        if subs:
            for sub in subs:
                _cache_moves(getattr(sub, "jaxpr", sub), s_max, kh, found)
            continue
        if any({s_max, kh} <= set(getattr(v.aval, "shape", ()))
               for v in eqn.outvars):
            found.append(eqn.primitive.name)
    return found


def test_fused_decode_step_moves_no_cache():
    """The test-size counterpart of tests/test_tpu_compile.py's guard, on
    the program handed to the compiler: in the fused decode step nothing
    but the Pallas attention kernel, which appends in place, makes a value
    with both the cache-length and the KV-head axis.  (XLA:CPU runs the
    kernel in interpret mode, which copies an aliased operand per call, so
    its compiled HLO cannot show what the TPU's does.)"""
    mcfg = dataclasses.replace(smoke_config("tinyllama-1.1b"), kv_quant=True,
                               num_heads=8, num_kv_heads=4)
    s_max = 24
    pk = pack_model_params(init_params(jax.random.PRNGKey(0), mcfg), FUSED1,
                           mcfg)
    st = init_decode_state(mcfg, 3, s_max)
    tok = jnp.zeros((3,), jnp.int32)

    def step(p, s, t):
        return decode_step(p, s, t, mcfg,
                           Numerics(FUSED1, jax.random.PRNGKey(0)))

    jaxpr = jax.make_jaxpr(step)(pk, st, tok)
    assert _cache_moves(jaxpr.jaxpr, s_max, 4, []) == []
    # The guard sees what it guards: the packed chain's jnp attention and
    # its per-layer reads of the cache are caught.
    chain = jax.make_jaxpr(lambda p, s, t: decode_step(
        p, s, t, mcfg, Numerics(PACKED1, jax.random.PRNGKey(0))))(
        pack_model_params(init_params(jax.random.PRNGKey(0), mcfg), PACKED1,
                          mcfg), st, tok)
    assert _cache_moves(chain.jaxpr, s_max, 4, [])


@pytest.mark.parametrize("start,count", [
    ([0, 100, 250, 256], [128, 128, 128, 0]),   # first tile, across, past end
    ([5, 127, 128, 0], [1, 1, 1, 0]),           # decode-sized appends
])
def test_append_kv_columns_writes_only_new_positions(start, count):
    """The Pallas append writes slot b's columns at start[b] + [0,
    count[b]) and nothing else — across a 128-column tile edge, and
    dropping what falls past the cache's end — in a stacked cache at one
    layer."""
    from repro.kernels.abfp_decode_fused import append_kv_columns

    rng = np.random.default_rng(7)
    L, B, KH, D, S, C = 2, 4, 2, 8, 256, max(count)
    codes = lambda *sh: jnp.asarray(  # noqa: E731
        rng.integers(-127, 128, size=sh), jnp.int8)
    old = (codes(L, B, KH, D, S), codes(L, B, KH, D, S),
           jnp.asarray(rng.uniform(0.1, 2, (L, B, KH, 2, S)), jnp.bfloat16))
    cols = (codes(B, KH, D, C), codes(B, KH, D, C),
            jnp.asarray(rng.uniform(0.1, 2, (B, KH, 2, C)), jnp.bfloat16))
    got = append_kv_columns(
        *[jnp.copy(a) for a in old], *cols, start=jnp.asarray(start),
        count=jnp.asarray(count), layer=jnp.int32(1))
    for a, c, g in zip(old, cols, got):
        want = np.array(a)
        for b in range(B):
            for j in range(count[b]):
                if start[b] + j < S:
                    want[1, b, ..., start[b] + j] = np.asarray(c)[b, ..., j]
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      want.astype(np.float32))


def test_fused_engine_serving_path(tinyllama_kvq):
    """End-to-end runner path: the engine packs with gains at init in fused
    mode and serves bit-identical greedy tokens to the packed engine at
    gain 1.0; at gain 8.0 it still serves (different numerics, same
    schedule)."""
    mcfg, params = tinyllama_kvq
    prompts = [[3, 5, 7], [2], [8, 1, 2, 3, 4]]

    def serve(quant):
        eng = ServingEngine(params, mcfg, capacity=2, max_len=32,
                            quant=quant, seed=0, prefill_chunks=(4, 8))
        gains = [pw.gains for pw in _packed_leaves(eng.params)]
        done = eng.run([Request(uid=i, prompt=list(p), max_new_tokens=4)
                        for i, p in enumerate(prompts)])
        return {r.uid: tuple(r.generated) for r in done}, gains

    base, g_packed = serve(PACKED1)
    got, g_fused = serve(FUSED1)
    assert all(g is None for g in g_packed)
    assert g_fused and all(g is not None for g in g_fused)
    assert got == base

    fused8 = QuantConfig(mode="abfp_fused", tile_width=32, gain=8.0,
                         noise_lsb=0.5)
    got8, g8 = serve(fused8)
    assert sorted(got8) == sorted(base)               # same completions
    assert any(np.asarray(g).max() > 1.0 for g in g8)  # real amplification


def test_dense_dispatch_abfp_fused_packs_on_the_fly():
    """kernels.ops.dense accepts mode="abfp_fused" for raw float weights
    (QAT-style flips): it packs with adaptive gains per call and matches
    the explicit pack + packed-kernel route."""
    from repro.kernels.ops import dense, dense_packed

    rng = np.random.default_rng(11)
    cfg = QuantConfig(mode="abfp_fused", tile_width=32, gain=8.0,
                      noise_lsb=0.5, out_dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)) * 0.1, jnp.float32)
    key = jax.random.PRNGKey(1)
    pw = pack_abfp_weight(w, cfg, adaptive_gain=True)
    np.testing.assert_array_equal(
        np.asarray(dense(x, w, cfg, key)),
        np.asarray(dense_packed(x, pw, cfg, key)))


def test_faults_preserve_gains():
    """Every fault/repair PackedWeight reconstruction keeps the gains leaf
    (dropping it would silently change fused-mode numerics mid-serve)."""
    from repro.serving.faults import inject_scale_drift, inject_stuck_cols

    rng = np.random.default_rng(13)
    cfg = QuantConfig(mode="abfp_fused", tile_width=32, gain=8.0,
                      noise_lsb=0.0)
    w = jnp.asarray(rng.normal(size=(128, 128)) * 0.1, jnp.float32)
    params = {"wq": pack_abfp_weight(w, cfg, adaptive_gain=True)}
    g0 = np.asarray(params["wq"].gains)
    hurt = inject_stuck_cols(params, "wq", [0, 3])
    np.testing.assert_array_equal(np.asarray(hurt["wq"].gains), g0)
    hurt = inject_scale_drift(params, "wq", [(0, 1)], [1.5])
    np.testing.assert_array_equal(np.asarray(hurt["wq"].gains), g0)
