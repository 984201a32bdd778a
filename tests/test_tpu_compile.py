"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other test here) cannot see what the TPU's kernel
compiler refuses: blocks that break its (8, 128) tiling, casts it has no
instruction for, or more fast memory than a kernel may use.  These tests
compile each kernel of the main serving path at SmolLM-360M widths
(d_model 960, d_ff 2560, vocab 49152, 15/5 heads of 64, tile 128, ADC noise
0.5 LSB, 2048-token cache) for a ``v5e:2x2`` topology that is described, not
attached.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, so it must not load while test files are
imported (every test worker imports every file).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.abfp import PackedWeight, QuantConfig
from repro.kernels.abfp_decode_fused import (
    fused_qkv_packed_pallas,
    fused_quantized_decode_attention,
)
from repro.kernels.abfp_matmul import (
    abfp_matmul_packed_pallas,
    abfp_matmul_pallas,
)

D_MODEL, D_FF, VOCAB = 960, 2560, 49152
HEADS, KV_HEADS, HEAD_DIM = 15, 5, 64
MAX_LEN, CAPACITY, TILE = 2048, 8, 128
CFG = QuantConfig(mode="abfp_fused", tile_width=TILE, gain=8.0,
                  noise_lsb=0.5)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(k, n, sharding, gains=True):
    kp = -(-k // TILE) * TILE
    np_ = -(-n // 128) * 128
    t = kp // TILE
    return PackedWeight(
        _sds((kp, np_), jnp.int8, sharding),
        _sds((t, np_), jnp.bfloat16, sharding),
        k, n, TILE, CFG.bits_w,
        gains=_sds((t,), jnp.float32, sharding) if gains else None)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [CAPACITY, CAPACITY * 64])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL),
                                 (D_MODEL, VOCAB)])
def test_packed_kernel_compiles(one_chip, m, k, n):
    """Packed kernel with per-tile gains and noise: decode and prefill rows."""
    pw = _packed(k, n, one_chip)
    x = _sds((m, k), jnp.bfloat16, one_chip)
    seed = _sds((), jnp.int32, one_chip)
    _compile(lambda x, pw, s: abfp_matmul_packed_pallas(
        x, pw, CFG, s, interpret=False), x, pw, seed)


def test_fused_qkv_kernel_compiles(one_chip):
    pws = (_packed(D_MODEL, HEADS * HEAD_DIM, one_chip),
           _packed(D_MODEL, KV_HEADS * HEAD_DIM, one_chip),
           _packed(D_MODEL, KV_HEADS * HEAD_DIM, one_chip))
    x = _sds((CAPACITY, 1, D_MODEL), jnp.bfloat16, one_chip)
    seeds = tuple(_sds((), jnp.int32, one_chip) for _ in range(3))
    _compile(lambda x, pws, s: fused_qkv_packed_pallas(
        x, pws, CFG, s, interpret=False), x, pws, seeds)


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_fused_kv_attention_compiles_at_full_context(one_chip, q_dtype):
    b, s = CAPACITY, MAX_LEN
    q = _sds((b, 1, HEADS, HEAD_DIM), q_dtype, one_chip)
    codes = _sds((b, s, KV_HEADS, HEAD_DIM), jnp.int8, one_chip)
    scales = _sds((b, s, KV_HEADS), jnp.bfloat16, one_chip)
    lengths = _sds((b,), jnp.int32, one_chip)
    _compile(lambda q, kc, ks, vc, vs, ln: fused_quantized_decode_attention(
        q, kc, ks, vc, vs, lengths=ln, interpret=False),
        q, codes, scales, codes, scales, lengths)


def test_unpacked_kernel_with_noise_compiles(one_chip):
    x = _sds((CAPACITY, D_MODEL), jnp.bfloat16, one_chip)
    w = _sds((D_MODEL, D_FF), jnp.bfloat16, one_chip)
    seed = _sds((), jnp.int32, one_chip)
    cfg = QuantConfig(mode="abfp_kernel", tile_width=TILE, gain=8.0,
                      noise_lsb=0.5)
    _compile(lambda x, w, s: abfp_matmul_pallas(
        x, w, cfg, s, interpret=False), x, w, seed)
