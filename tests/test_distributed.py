"""Distribution tests: sharding rules, ZeRO-1, HLO collective parsing, and a
multi-device MoE equivalence check (8 placeholder CPU devices, subprocess)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.distributed.sharding import (
    MODEL_AXIS,
    abfp_param_spec_tree,
    param_spec_tree,
    validate_spec,
    zero1_spec,
)
from repro.launch.hlo_analysis import collective_stats, roofline_terms
from repro.models import init_params


ROOT = Path(__file__).resolve().parents[1]
# JAX_PLATFORMS=cpu: the children run on placeholder CPU devices and must
# not load the TPU library, which one process at a time may hold.
CHILD_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"}


class _FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 2}


def test_param_spec_rules():
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    specs = param_spec_tree(params)
    g = specs["groups"][0]
    # Stacked leaves get a leading None (scan axis).
    assert g["attn"]["wq"] == P(None, None, MODEL_AXIS)
    assert g["attn"]["wo"] == P(None, MODEL_AXIS, None)
    assert g["mlp"]["wi"] == P(None, None, MODEL_AXIS)
    assert g["mlp"]["wo"] == P(None, MODEL_AXIS, None)
    assert g["norm1"]["scale"] == P(None, None)      # replicated
    assert specs["embed"] == P(MODEL_AXIS, None)
    assert specs["lm_head"] == P(None, MODEL_AXIS)


def test_abfp_spec_demotes_row_parallel():
    """ABFP tiles must not straddle shards: K-axis sharding demoted."""
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    specs = abfp_param_spec_tree(params)
    g = specs["groups"][0]
    assert g["attn"]["wq"] == P(None, None, MODEL_AXIS)   # col-parallel kept
    assert g["attn"]["wo"] == P(None, None, None)         # row demoted
    assert g["mlp"]["wo"] == P(None, None, None)


def test_moe_expert_parallel_specs():
    mcfg = smoke_config("granite-moe-1b-a400m")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    specs = param_spec_tree(params)
    g = specs["groups"][0]
    assert g["moe"]["wi"] == P(None, MODEL_AXIS, None, None)  # experts over TP
    assert g["moe"]["router"] == P(None, None, None)


def test_validate_spec_drops_indivisible():
    mesh = _FakeMesh()
    assert validate_spec(P("model", None), (51865, 512), mesh) == P(None, None)
    assert validate_spec(P("model", None), (512, 64), mesh) == P("model", None)
    assert validate_spec(P(("data",), None), (1, 8), mesh) == P(None, None)
    assert validate_spec(P(("data", "model"), None), (8, 8), mesh) == \
        P(("data", "model"), None)


def test_zero1_spec_picks_largest_divisible_axis():
    mesh = _FakeMesh()
    # (K=512, N=64) sharded (None, model): data goes on dim0 (512 % 4 == 0).
    assert zero1_spec(P(None, "model"), (512, 64), mesh) == P("data", "model")
    # nothing divisible: unchanged
    assert zero1_spec(P(None,), (7,), mesh) == P(None,)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------


_HLO = textwrap.dedent("""
ENTRY %main.1 (p: f32[256,1024]) -> f32[256,1024] {
  %param.1 = f32[256,1024]{1,0} parameter(0)
  %all-reduce.1 = f32[256,1024]{1,0} all-reduce(%param.1), replica_groups={{0,1,2,3}}, to_apply=%add
  %all-gather.2 = bf16[512,1024]{1,0} all-gather(%x), replica_groups=[4,2]<=[8], dimensions={0}
  %reduce-scatter.3 = f32[64,1024]{1,0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %collective-permute.4 = f32[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  ROOT %add.5 = f32[256,1024]{1,0} add(%param.1, %param.1)
}
""")


def test_collective_stats_parses_ops_and_bytes():
    stats = collective_stats(_HLO)
    assert stats["all-reduce"]["count"] == 1
    # all-reduce: 2 * size * (g-1)/g; size = 256*1024*4, g=4
    assert stats["all-reduce"]["bytes"] == int(2 * 256 * 1024 * 4 * 3 / 4)
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == int(512 * 1024 * 2 * 1 / 2)
    assert stats["reduce-scatter"]["count"] == 1
    assert stats["collective-permute"]["bytes"] == 8 * 8 * 4
    assert stats["total"]["count"] == 4


def test_roofline_terms_bottleneck():
    t = roofline_terms(1e12, 1e9, 1e6, chips=256,
                       peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)
    assert t["bottleneck"] == "compute_s"
    t2 = roofline_terms(1e9, 1e9, 1e9, chips=256,
                        peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)
    assert t2["bottleneck"] == "collective_s"


# ---------------------------------------------------------------------------
# Multi-device semantics (subprocess: 8 placeholder CPU devices)
# ---------------------------------------------------------------------------


_MOE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.launch.mesh import make_mesh
from repro.models.layers import Numerics
from repro.models import moe as moe_lib

# capacity_factor high enough that no (token, expert) pair is dropped: the
# expert-parallel path must then match the single-shard path exactly.
mcfg = dataclasses.replace(smoke_config("granite-moe-1b-a400m"),
                           capacity_factor=8.0)
key = jax.random.PRNGKey(0)
params = moe_lib.init_moe(key, mcfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, mcfg.d_model))
nx = Numerics(QuantConfig(mode="float"))

y_local, aux_local = moe_lib.moe_block(params, x, mcfg, nx)

mesh = make_mesh((2, 4), ("data", "model"))
with mesh:
    y_sh, aux_sh = jax.jit(
        lambda p, x: moe_lib.moe_block_sharded(p, x, mcfg, nx, mesh)
    )(params, x)

np.testing.assert_allclose(np.asarray(y_local, np.float32),
                           np.asarray(y_sh, np.float32), rtol=2e-2, atol=2e-2)
# aux is E*sum(density*p_mean): a nonlinear statistic, so the mean of
# per-data-shard values differs from the whole-batch value by O(1/T_loc) —
# ~1% at this smoke scale, vanishing at production token counts.
np.testing.assert_allclose(float(aux_local), float(aux_sh), rtol=5e-2)

# At the production capacity factor (1.25), GShard-style dropping may zero a
# small fraction of (token, expert) contributions under load imbalance.
mcfg2 = dataclasses.replace(mcfg, capacity_factor=1.25)
with mesh:
    y_dp, _ = jax.jit(
        lambda p, x: moe_lib.moe_block_sharded(p, x, mcfg2, nx, mesh)
    )(params, x)
frac = float(jnp.mean(jnp.any(
    jnp.abs(y_dp - y_sh) > 0.05 * (1 + jnp.abs(y_sh)), axis=-1)))
assert frac < 0.25, f"too many dropped tokens: {frac}"
print("MOE_SHARDED_OK")
"""


@pytest.mark.slow
def test_moe_sharded_matches_local():
    """Expert-parallel shard_map MoE == single-shard MoE (8 fake devices)."""
    r = subprocess.run([sys.executable, "-c", _MOE_SCRIPT],
                       capture_output=True, text=True, timeout=560,
                       env=CHILD_ENV, cwd=ROOT)
    assert "MOE_SHARDED_OK" in r.stdout, r.stdout + r.stderr


_SHARDED_FWD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import smoke_config
from repro.distributed.sharding import param_spec_tree, batch_spec
from repro.launch.mesh import make_mesh
from repro.models import forward, init_params

mcfg = smoke_config("tinyllama-1.1b")
params = init_params(jax.random.PRNGKey(0), mcfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, mcfg.vocab_size)

logits_1d, _ = jax.jit(lambda p, t: forward(p, t, mcfg))(params, toks)

mesh = make_mesh((2, 4), ("data", "model"))
ps = jax.tree.map(lambda s: NamedSharding(mesh, s),
                  param_spec_tree(params, mesh),
                  is_leaf=lambda x: isinstance(x, P))
sp = jax.device_put(params, ps)
st = jax.device_put(toks, NamedSharding(mesh, batch_spec(mesh, toks.shape)))
with mesh:
    logits_8d, _ = jax.jit(lambda p, t: forward(p, t, mcfg))(sp, st)

np.testing.assert_allclose(np.asarray(logits_1d), np.asarray(logits_8d),
                           rtol=2e-2, atol=2e-2)
print("SHARDED_FWD_OK")
"""


@pytest.mark.slow
def test_sharded_forward_matches_single_device():
    """GSPMD-sharded forward == single-device forward (8 fake devices)."""
    r = subprocess.run([sys.executable, "-c", _SHARDED_FWD_SCRIPT],
                       capture_output=True, text=True, timeout=560,
                       env=CHILD_ENV, cwd=ROOT)
    assert "SHARDED_FWD_OK" in r.stdout, r.stdout + r.stderr
