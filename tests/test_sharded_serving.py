"""Sharded-serving parity suite (forced 8-device CPU mesh).

The contract under test: ``ServingEngine(mesh=...)`` emits the SAME greedy
tokens as the single-device engine at every mesh shape — exactly equal for
float mode and bit-identical (noise included) for abfp_packed with a fixed
seed.  Column-parallel tensor parallelism never splits an ABFP K-tile or
reorders an f32 contraction, and the Pallas noise salts are globalized per
column shard (kernels/ops.dense_tp), which is what makes this equality
testable at all.

Runs only when >= 8 jax devices exist — the ``dist`` CI leg forces them
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (see Makefile
``test-dist`` and .github/workflows/ci.yml); on a plain host the module
skips.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.serving import Request, ServingEngine

pytestmark = [
    pytest.mark.dist,
    pytest.mark.skipif(
        len(jax.devices()) < 8,
        reason="needs 8 devices (run under XLA_FLAGS="
               "--xla_force_host_platform_device_count=8 / make test-dist)"),
]

MESH_SHAPES = [(1, 1), (2, 1), (1, 2), (2, 4)]

# Prompts straddle the (4, 8) prefill buckets: lengths below, at, and above
# a bucket, plus a single-token prompt (routed through the decode tick).
PROMPTS = [[3, 5, 7, 9, 11], [2, 4, 6], [8, 1, 2, 3, 4, 5, 6, 7, 9], [13]]

FLOAT = QuantConfig(mode="float")
PACKED = QuantConfig(mode="abfp_packed", tile_width=32, gain=4.0,
                     noise_lsb=0.5)
# Gain 1.0 pair: the fused decode kernels must be bit-identical to the
# packed dispatch chain (all-ones per-tile gains are exact no-ops).
PACKED1 = QuantConfig(mode="abfp_packed", tile_width=32, gain=1.0,
                      noise_lsb=0.5)
FUSED1 = QuantConfig(mode="abfp_fused", tile_width=32, gain=1.0,
                     noise_lsb=0.5)
FUSED4 = QuantConfig(mode="abfp_fused", tile_width=32, gain=4.0,
                     noise_lsb=0.5)


def _serve(mcfg, params, quant, mesh, *, max_new=4, max_len=32, **ekw):
    eng = ServingEngine(params, mcfg, capacity=4, max_len=max_len,
                        quant=quant, seed=0, prefill_chunks=(4, 8),
                        mesh=mesh, **ekw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(PROMPTS)]
    done = eng.run(reqs)
    assert len(done) == len(PROMPTS)
    return {r.uid: tuple(r.generated) for r in done}


@pytest.fixture(scope="module")
def tinyllama():
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    return mcfg, params


@pytest.fixture(scope="module")
def tinyllama_kvq(tinyllama):
    """Same params, int8 KV cache — the fused decode kernel's habitat."""
    mcfg, params = tinyllama
    return dataclasses.replace(mcfg, kv_quant=True), params


@pytest.fixture(scope="module")
def tinyllama_base_float(tinyllama):
    return _serve(*tinyllama, FLOAT, None)


@pytest.fixture(scope="module")
def tinyllama_base_packed(tinyllama):
    return _serve(*tinyllama, PACKED, None)


@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_float_parity(tinyllama, tinyllama_base_float, shape):
    """Greedy float decode tokens identical to single-device at any mesh."""
    mesh = make_mesh(shape, ("data", "model"))
    got = _serve(*tinyllama, FLOAT, mesh)
    assert got == tinyllama_base_float, shape


@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_packed_parity_bit_identical(tinyllama, tinyllama_base_packed,
                                     shape):
    """abfp_packed greedy decode with ADC noise (fixed seed): bit-identical
    tokens to the single-device engine at any mesh shape — the acceptance
    gate for --mesh 2,4 --quant abfp-packed."""
    mesh = make_mesh(shape, ("data", "model"))
    got = _serve(*tinyllama, PACKED, mesh)
    assert got == tinyllama_base_packed, shape


@pytest.fixture(scope="module")
def tinyllama_base_packed1_kvq(tinyllama_kvq):
    return _serve(*tinyllama_kvq, PACKED1, None)


@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_fused_parity_bit_identical(tinyllama_kvq,
                                    tinyllama_base_packed1_kvq, shape):
    """The tentpole gate: abfp_fused (fused QKV + quantized-KV attention,
    per-tile ADC gains) at gain 1.0 emits bit-identical greedy tokens to
    the single-device abfp_packed engine at EVERY mesh shape — dp-only,
    tp-only, and the full (2, 4) mesh, seeded ADC noise included."""
    mesh = make_mesh(shape, ("data", "model"))
    got = _serve(*tinyllama_kvq, FUSED1, mesh)
    assert got == tinyllama_base_packed1_kvq, shape


@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_fused_gain_mesh_self_parity(tinyllama_kvq, shape):
    """With real amplification (gain cap 4.0, adaptive per-tile gains) the
    mesh engine matches the single-device FUSED engine bit-for-bit: the
    gains table shards/replicates without perturbing a single logit."""
    base = _serve(*tinyllama_kvq, FUSED4, None)
    mesh = make_mesh(shape, ("data", "model"))
    got = _serve(*tinyllama_kvq, FUSED4, mesh)
    assert got == base, shape


@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_paged_parity_bit_identical(tinyllama, tinyllama_base_float, shape):
    """Paged decode (replicated page pool, dp-sharded page table) is
    bit-identical to the UNPAGED single-device float baseline at every
    PR-4 mesh shape — the page-table gather must not change a single
    logit under either sharding axis."""
    mesh = make_mesh(shape, ("data", "model"))
    got = _serve(*tinyllama, FLOAT, mesh, paged=True, page_size=16)
    assert got == tinyllama_base_float, shape


def test_paged_packed_parity_on_mesh(tinyllama, tinyllama_base_packed):
    """abfp_packed + paged KV at the largest mesh shape: tokens identical
    to the single-device UNPAGED packed engine (seeded ADC noise and the
    quantized KV pool both survive the indirection)."""
    mesh = make_mesh((2, 4), ("data", "model"))
    got = _serve(*tinyllama, PACKED, mesh, paged=True, page_size=32)
    assert got == tinyllama_base_packed


@pytest.mark.parametrize("shape", [(1, 2), (2, 4)])
@pytest.mark.parametrize("quant", [FLOAT, PACKED],
                         ids=["float", "abfp_packed"])
def test_ring_cache_wraparound_parity(shape, quant):
    """Hybrid (recurrent + windowed-attention) model whose ring cache WRAPS
    during decode: chunked prefill plus ring wraparound stay bit-identical
    under the mesh.  window=8 with prompt+generated > 8 forces eviction."""
    mcfg = dataclasses.replace(smoke_config("recurrentgemma-2b"),
                               window_size=8)
    assert mcfg.attention_type == "hybrid"
    params = init_params(jax.random.PRNGKey(1), mcfg)
    base = _serve(mcfg, params, quant, None, max_new=6, max_len=48)
    assert any(len(p) + 6 > 8 for p in PROMPTS)     # wraps for long prompts
    mesh = make_mesh(shape, ("data", "model"))
    got = _serve(mcfg, params, quant, mesh, max_new=6, max_len=48)
    assert got == base, shape


def test_open_loop_api_unchanged_under_mesh(tinyllama):
    """submit/poll/drain (arrival-driven, priority policy) works unchanged
    on a mesh and matches the single-device engine token-for-token."""
    mcfg, params = tinyllama

    def run(mesh):
        eng = ServingEngine(params, mcfg, capacity=2, max_len=32,
                            quant=FLOAT, seed=0, prefill_chunks=(4, 8),
                            policy="priority", mesh=mesh)
        for i, p in enumerate(PROMPTS):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=3,
                               arrival_time=float(i), priority=i % 2,
                               tenant=f"t{i % 2}"))
        done = eng.drain()
        return {r.uid: tuple(r.generated) for r in done}, eng.ticks

    base_tokens, base_ticks = run(None)
    mesh = make_mesh((2, 4), ("data", "model"))
    got_tokens, got_ticks = run(mesh)
    assert got_tokens == base_tokens
    assert got_ticks == base_ticks


# ---------------------------------------------------------------------------
# ops-level dispatch: column-parallel bit-identity, row-parallel psum
# ---------------------------------------------------------------------------


def test_dense_tp_col_parallel_bit_identical():
    import jax.numpy as jnp

    from repro.core.abfp import pack_abfp_weight
    from repro.kernels.ops import dense, dense_packed, dense_tp

    mesh = make_mesh((2, 4), ("data", "model"))
    kx, kw, kk = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (8, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 512), jnp.float32) * 0.1

    cfg_f = QuantConfig(mode="float")
    np.testing.assert_array_equal(
        np.asarray(dense_tp(x, w, cfg_f, None, mesh)),
        np.asarray(dense(x, w, cfg_f)))

    # Packed with noise: tp=4 shards 512 padded columns as 128-lane blocks.
    cfg_p = QuantConfig(mode="abfp_packed", tile_width=32, gain=8.0,
                        noise_lsb=0.5, out_dtype=jnp.float32)
    pw = pack_abfp_weight(w, cfg_p)
    np.testing.assert_array_equal(
        np.asarray(dense_tp(x, pw, cfg_p, kk, mesh)),
        np.asarray(dense_packed(x, pw, cfg_p, kk)))

    cfg_k = cfg_p.replace(mode="abfp_kernel")
    np.testing.assert_array_equal(
        np.asarray(dense_tp(x, w, cfg_k, kk, mesh)),
        np.asarray(dense(x, w, cfg_k, kk)))


def test_dense_tp_fallback_on_indivisible_columns():
    """Columns the mesh cannot split in whole lane blocks run replicated —
    same values, no shard_map error."""
    import jax.numpy as jnp

    from repro.core.abfp import pack_abfp_weight
    from repro.kernels.ops import dense_packed, dense_tp, tp_shardable

    mesh = make_mesh((1, 8), ("data", "model"))
    cfg = QuantConfig(mode="abfp_packed", tile_width=32, gain=4.0,
                      noise_lsb=0.5, out_dtype=jnp.float32)
    kx, kw, kk = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (4, 96), jnp.float32)
    w = jax.random.normal(kw, (96, 130), jnp.float32) * 0.1   # Np=256, tp=8
    pw = pack_abfp_weight(w, cfg)
    assert not tp_shardable(pw, cfg, mesh)
    np.testing.assert_array_equal(
        np.asarray(dense_tp(x, pw, cfg, kk, mesh)),
        np.asarray(dense_packed(x, pw, cfg, kk)))


def test_dense_tp_row_psum_matches_to_tolerance():
    """Contracting-dim (row-parallel) psum: reproducible and allclose, but
    the f32 reduction order differs from single-device — float only, and
    ABFP modes are rejected outright."""
    import jax.numpy as jnp

    from repro.kernels.ops import dense_tp_row

    mesh = make_mesh((1, 8), ("data", "model"))
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (8, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 64), jnp.float32) * 0.1
    cfg = QuantConfig(mode="float")
    y = np.asarray(dense_tp_row(x, w, cfg, mesh))
    np.testing.assert_allclose(y, np.asarray(jnp.matmul(x, w)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        y, np.asarray(dense_tp_row(x, w, cfg, mesh)))    # reproducible
    with pytest.raises(ValueError, match="float-only"):
        dense_tp_row(x, w, QuantConfig(mode="abfp_kernel"), mesh)


def test_packed_params_shard_codes_and_scales_together(tinyllama):
    """Placement invariant: every column-sharded PackedWeight shards its
    int8 codes and bf16 scales along the SAME axis with the SAME layout, so
    per-(tile, col) scales live on the shard that owns their codes."""
    from repro.core.abfp import PackedWeight
    from repro.models.packing import pack_model_params

    mcfg, params = tinyllama
    mesh = make_mesh((1, 2), ("data", "model"))
    packed = pack_model_params(params, PACKED, mcfg, mesh=mesh)
    n_sharded = 0
    for leaf in jax.tree_util.tree_leaves(
            packed, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if not isinstance(leaf, PackedWeight):
            continue
        cspec = leaf.codes.sharding.spec
        sspec = leaf.scales.sharding.spec
        assert tuple(cspec) == tuple(sspec), leaf.shape
        if any(part == "model" for part in cspec):
            n_sharded += 1
            assert tuple(cspec)[-1] == "model"
            assert leaf.n_padded % (2 * 128) == 0
    assert n_sharded > 0        # mlp wi/wg + lm_head shard at tp=2
