"""Chunked-prefill equivalence tests.

In ``mode="float"`` the chunked prefill path must be BIT-identical to
feeding the same tokens through ``decode_step`` one at a time: KV caches
(bf16/f32 and int8 ABFP-quantized), ring-buffer window caches (including
wraparound), recurrent states (rglru conv+h, mlstm, slstm), and the
next-token logits.  ABFP modes get statistical equivalence only — the
Pallas noise PRNG salts by grid position, so a chunked matmul grid draws
different noise than S decode-shaped grids.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.models import (
    Numerics,
    decode_step,
    init_decode_state,
    init_params,
    prefill,
)
from repro.serving import Request, ServingEngine

B = 2


def _mcfg(name):
    if name == "tinyllama-kvquant":
        return dataclasses.replace(smoke_config("tinyllama-1.1b"),
                                   kv_quant=True)
    if name.startswith("hybrid-window8"):
        # Window smaller than the prompt: exercises ring-buffer wraparound
        # inside and across chunks.
        return dataclasses.replace(smoke_config("recurrentgemma-2b"),
                                   window_size=8,
                                   kv_quant=name.endswith("kvquant"))
    return smoke_config(name)


def _decode_loop(params, mcfg, toks, max_len):
    state = init_decode_state(mcfg, toks.shape[0], max_len=max_len)
    logits = None
    for t in range(toks.shape[1]):
        logits, state = decode_step(params, state, toks[:, t], mcfg)
    return logits, state


def _chunked(params, mcfg, toks, chunks, max_len, pad=2):
    """Prefill ``toks`` in the given chunk split, each chunk padded by
    ``pad`` bogus positions to exercise the n_tokens masking."""
    state = init_decode_state(mcfg, toks.shape[0], max_len=max_len)
    logits, pos = None, 0
    for c in chunks:
        tk = jnp.zeros((toks.shape[0], c + pad), jnp.int32)
        tk = tk.at[:, :c].set(toks[:, pos:pos + c])
        logits, state = prefill(params, state, tk,
                                jnp.full((toks.shape[0],), c, jnp.int32),
                                mcfg)
        pos += c
    assert pos == toks.shape[1]
    return logits, state


def _assert_trees_bitwise(t1, t2):
    flat1, def1 = jax.tree.flatten(t1)
    flat2, def2 = jax.tree.flatten(t2)
    assert def1 == def2
    for a, b in zip(flat1, flat2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


ARCHS = ["tinyllama-1.1b", "recurrentgemma-2b", "xlstm-350m",
         "tinyllama-kvquant", "hybrid-window8", "hybrid-window8-kvquant"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_bit_identical(arch):
    """Chunked prefill == token-by-token decode, bit for bit (float mode):
    same KV caches / recurrent states / positions AND same last-token
    logits, through uneven chunk splits with padded buckets."""
    mcfg = _mcfg(arch)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    L = 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                              mcfg.vocab_size)
    logits_ref, state_ref = _decode_loop(params, mcfg, toks, max_len=24)
    logits, state = _chunked(params, mcfg, toks, chunks=(5, 7), max_len=24)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    _assert_trees_bitwise(state, state_ref)


def test_prefill_window_wraparound_bit_identical():
    """Prompt much longer than the sliding window: the ring buffer wraps
    several times within and across chunks."""
    mcfg = _mcfg("hybrid-window8")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    L = 20
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                              mcfg.vocab_size)
    logits_ref, state_ref = _decode_loop(params, mcfg, toks, max_len=40)
    logits, state = _chunked(params, mcfg, toks, chunks=(9, 11), max_len=40)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    _assert_trees_bitwise(state, state_ref)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "recurrentgemma-2b",
                                  "xlstm-350m"])
def test_prefill_idle_slot_untouched(arch):
    """A slot with n_tokens == 0 keeps its ENTIRE state slice bit-identical
    (prefilling and decoding slots share the batch), and the active slot is
    unaffected by its neighbor's n."""
    mcfg = _mcfg(arch)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 6), 0,
                              mcfg.vocab_size)
    state0 = init_decode_state(mcfg, B, max_len=16)

    _, state_both = prefill(params, state0, toks,
                            jnp.array([6, 6], jnp.int32), mcfg)
    _, state_one = prefill(params, state0, toks,
                           jnp.array([6, 0], jnp.int32), mcfg)

    def slot(tree, i):
        def pick(path, leaf):
            names = [str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path]
            b_axis = 1 if "groups" in names else 0
            return leaf if leaf.ndim <= b_axis else jnp.take(leaf, i, b_axis)
        return jax.tree_util.tree_map_with_path(pick, tree)

    # slot 0 advanced identically; slot 1 bitwise untouched
    _assert_trees_bitwise(slot(state_one, 0), slot(state_both, 0))
    _assert_trees_bitwise(slot(state_one, 1), slot(state0, 1))


def test_prefill_abfp_statistical():
    """ABFP chunked prefill draws different kernel-noise than token-by-token
    (grid-shape salted PRNG) but must stay statistically equivalent."""
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    L = 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                              mcfg.vocab_size)
    quant = QuantConfig(mode="abfp_ref", tile_width=32, gain=2.0,
                        noise_lsb=0.5)

    state = init_decode_state(mcfg, B, max_len=16)
    for t in range(L):
        nx = Numerics(quant, jax.random.PRNGKey(100 + t))
        logits_ref, state = decode_step(params, state, toks[:, t], mcfg, nx)

    state = init_decode_state(mcfg, B, max_len=16)
    nx = Numerics(quant, jax.random.PRNGKey(999))
    logits, state = prefill(params, state, toks,
                            jnp.full((B,), L, jnp.int32), mcfg, nx)

    a = np.asarray(logits, np.float32).ravel()
    b = np.asarray(logits_ref, np.float32).ravel()
    assert np.all(np.isfinite(a))
    c = np.corrcoef(a, b)[0, 1]
    assert c > 0.8, c


def _greedy_workload(mcfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, mcfg.vocab_size,
                                        17 + 9 * i).tolist(),
                    max_new_tokens=4)
            for i in range(n)]


def test_engine_chunked_matches_legacy():
    """End-to-end: the chunked engine generates exactly the same tokens as
    legacy prefill-in-decode, with far fewer ticks.  Covers slot reuse
    (more requests than capacity -> jitted reset), prefilling/decoding
    coexistence (uneven prompt lengths), and chunk bucketing."""
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)

    e1 = ServingEngine(params, mcfg, capacity=2, max_len=64, chunked=False)
    d1 = e1.run(_greedy_workload(mcfg, 3))
    e2 = ServingEngine(params, mcfg, capacity=2, max_len=64, chunked=True,
                       prefill_chunks=(4, 16))
    d2 = e2.run(_greedy_workload(mcfg, 3))

    assert {r.uid: r.generated for r in d1} == {r.uid: r.generated for r in d2}
    assert e2.ticks < e1.ticks


def test_engine_rejects_oversized_request():
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    eng = ServingEngine(params, mcfg, capacity=1, max_len=16)
    with pytest.raises(ValueError):
        eng.try_admit(Request(uid=0, prompt=list(range(1, 16)),
                              max_new_tokens=8))
    # max_new == 0 must still reserve one cache slot (chunk-scatter padding).
    with pytest.raises(ValueError):
        eng.try_admit(Request(uid=1, prompt=list(range(1, 17)),
                              max_new_tokens=0))
    # An empty prompt has no token to condition the first generation on —
    # rejecting it beats silently decoding from a stale _next_input.
    with pytest.raises(ValueError):
        eng.try_admit(Request(uid=4, prompt=[], max_new_tokens=2))
    # run() rejects oversized requests up front instead of crashing the
    # serve loop mid-flight; the rest of the workload is served.
    ok = Request(uid=2, prompt=[1, 2, 3], max_new_tokens=2)
    bad = Request(uid=3, prompt=list(range(1, 16)), max_new_tokens=8)
    done = eng.run([ok, bad])
    assert {r.uid for r in done} == {2, 3}
    assert next(r for r in done if r.uid == 3).generated == []
    assert len(next(r for r in done if r.uid == 2).generated) == 2


# ---------------------------------------------------------------------------
# Cache-full boundary: padding lanes must never race the last real write
# ---------------------------------------------------------------------------


def test_chunk_append_at_cache_boundary_keeps_real_write():
    """Regression (found failing, then fixed): with length + n_tokens ==
    S_max, the chunk scatter's padding lanes used to CLAMP onto index
    S_max - 1 — the very slot the last real token writes — and the
    duplicate-index race let the stale value win, silently corrupting the
    final K/V append.  Padding lanes past the cache end are dropped now;
    the boundary append must match a padding-free 1-token chunk exactly."""
    from repro.models.layers import chunk_append_attend

    b, s, h, d, s_max = 2, 4, 2, 8, 8
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))
    cache = {
        "k": jax.random.normal(jax.random.fold_in(key, 3), (b, s_max, h, d)),
        "v": jax.random.normal(jax.random.fold_in(key, 4), (b, s_max, h, d)),
        "length": jnp.array([s_max - 1, s_max - 3], jnp.int32),
    }
    n_tokens = jnp.array([1, 2], jnp.int32)     # slot 0 fills the cache

    out, new = chunk_append_attend(q, k, v, dict(cache),
                                   n_tokens=n_tokens, window=0)
    # Padding-free reference: per-slot 1-token appends (slot 0) / the same
    # chunk without excess lanes (slot 1 via a 2-token chunk).
    out1, ref = chunk_append_attend(q[:, :2], k[:, :2], v[:, :2],
                                    dict(cache), n_tokens=n_tokens, window=0)
    np.testing.assert_array_equal(np.asarray(new["k"][0, s_max - 1]),
                                  np.asarray(k[0, 0]))
    np.testing.assert_array_equal(np.asarray(new["k"]), np.asarray(ref["k"]))
    np.testing.assert_array_equal(np.asarray(new["v"]), np.asarray(ref["v"]))
    np.testing.assert_array_equal(np.asarray(out[:, :2]), np.asarray(out1))
    np.testing.assert_array_equal(np.asarray(new["length"]),
                                  np.asarray(cache["length"]) + [1, 2])
