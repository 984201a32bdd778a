"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other test here) cannot see what the TPU's kernel
compiler refuses: blocks that break its (8, 128) tiling, casts it has no
instruction for, or more fast memory than a kernel may use.  These tests
compile each kernel of the main serving path at SmolLM-360M widths
(d_model 960, d_ff 2560, vocab 49152, 15/5 heads of 64, tile 128, ADC noise
0.5 LSB, 2048-token cache) for a ``v5e:2x2`` topology that is described, not
attached, and the engine's whole decode and prefill passes, whose optimized
HLO shows what moves the K/V cache.  Nothing runs, so they say nothing
about results or speed.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, so it must not load while test files are
imported (every test worker imports every file).
"""

import dataclasses
import os
import re

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
SLOTS = 32                  # the benchmark's engine capacity
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
    codes = _sds((b, KV_HEADS, HEAD_DIM, s), jnp.int8, one_chip)
    scales = _sds((b, KV_HEADS, 2, s), jnp.bfloat16, one_chip)
    new_codes = _sds((b, KV_HEADS, HEAD_DIM), jnp.int8, one_chip)
    new_scales = _sds((b, KV_HEADS, 2), jnp.bfloat16, one_chip)
    lengths = _sds((b,), jnp.int32, one_chip)
    _compile(lambda *a: fused_quantized_decode_attention(
        *a[:7], lengths=a[7], interpret=False),
        q, codes, codes, scales, new_codes, new_codes, new_scales, lengths)


# ---------------------------------------------------------------------------
# Whole serving passes: the int8 K/V cache stays where it lies
# ---------------------------------------------------------------------------

# Instructions that move no data: the scan's plumbing.
PLUMBING = {"parameter", "get-tuple-element", "tuple", "while"}


def _ops(hlo: str):
    """(opcode, name, output shapes) of every instruction the device runs:
    those of computations that are not the bodies of fusions."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", hlo))
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.+)$", line)
            op = m and re.search(r"\s([a-z][a-z0-9\-]*)\(", m.group(2))
            if op:
                shapes = [tuple(int(d) for d in g.split(",") if d)
                          for g in re.findall(r"\[([0-9,]*)\]",
                                              m.group(2)[:op.start()])]
                yield op.group(1), m.group(1), shapes


@pytest.fixture(scope="module")
def smollm_pass(one_chip):
    """Compile the engine's decode tick or 128-token prefill pass, as it
    jits them, for SmolLM-360M in the fused mode: 32 slots of 2048
    positions, int8 K/V cache.  Returns (optimized HLO, runner)."""
    from repro.configs import get_config
    from repro.models import init_decode_state, init_params
    from repro.models.packing import pack_model_params
    from repro.serving.runners import runner_for

    mcfg = dataclasses.replace(get_config("smollm-360m"), kv_quant=True,
                               param_dtype=jnp.bfloat16)
    params, state = jax.eval_shape(lambda k: (
        pack_model_params(init_params(k, mcfg), CFG, mcfg),
        init_decode_state(mcfg, SLOTS, MAX_LEN)), jax.random.PRNGKey(0))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params, state, key = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), (params, state, key))
    rows = _sds((SLOTS,), jnp.int32, one_chip)
    samp = (_sds((SLOTS,), jnp.float32, one_chip), rows, rows)

    def compile_pass(kind):
        runner = runner_for(mcfg)
        with pytest.MonkeyPatch.context() as mp:
            # Trace the kernels for the described chip, not in interpret
            # mode for the host's CPU backend.
            mp.setattr(jax, "default_backend", lambda: "tpu")
            if kind == "decode":
                fn = runner.make_step(CFG, None, seed=0)
                args = (params, state, rows, rows,
                        _sds((SLOTS,), jnp.bool_, one_chip), key) + samp
            else:
                fn = runner.make_prefill(CFG, None, seed=0)
                args = (params, state, _sds((SLOTS, 128), jnp.int32, one_chip),
                        rows, rows, _sds((SLOTS,), jnp.bool_, one_chip),
                        key) + samp
            hlo = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        return hlo.as_text(), runner

    return compile_pass


def test_fused_decode_step_moves_no_cache(smollm_pass):
    """What ``kv_copy_ms`` reads on the chip: in the fused decode tick no
    instruction but the attention kernel (which appends the new token in
    place) has an output with both the cache-length and the KV-head axis —
    no slice, copy or update-slice of the cache around it."""
    hlo, runner = smollm_pass("decode")
    assert runner.fused_layers == 32
    moves = [(op, name) for op, name, shapes in _ops(hlo)
             if op not in PLUMBING
             and any({MAX_LEN, KV_HEADS} <= set(s) for s in shapes)]
    assert moves and all(
        op == "custom-call" and name.startswith(
            "fused_quantized_decode_attention") for op, name in moves), moves


def test_prefill_pass_writes_the_cache_in_place(smollm_pass):
    """A prefill pass writes its chunk into the stacked int8 codes through
    the Pallas append kernel alone: no instruction lays the whole cache out
    again or copies it."""
    hlo, _ = smollm_pass("prefill")
    stacked = (32, SLOTS, KV_HEADS, HEAD_DIM, MAX_LEN)
    writes = [(op, name) for op, name, shapes in _ops(hlo)
              if op not in PLUMBING and stacked in shapes]
    assert writes and all(
        op == "custom-call" and name.startswith("append_kv_columns")
        for op, name in writes), writes


def test_unpacked_kernel_with_noise_compiles(one_chip):
    x = _sds((CAPACITY, D_MODEL), jnp.bfloat16, one_chip)
    w = _sds((D_MODEL, D_FF), jnp.bfloat16, one_chip)
    seed = _sds((), jnp.int32, one_chip)
    cfg = QuantConfig(mode="abfp_kernel", tile_width=TILE, gain=8.0,
                      noise_lsb=0.5)
    _compile(lambda x, w, s: abfp_matmul_pallas(
        x, w, cfg, s, interpret=False), x, w, seed)
