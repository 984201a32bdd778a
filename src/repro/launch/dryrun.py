import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ MUST precede any jax-importing module: jax locks the device count at first
#   init.  setdefault lets the mini-test override with a smaller count.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) cell:
  jax.jit(step, in_shardings, out_shardings).lower(**input_specs).compile()
then records memory_analysis(), cost_analysis(), and the collective traffic
parsed from the compiled HLO into experiments/dryrun/<cell>.json — the
roofline analysis (benchmarks/roofline.py) reads these artifacts.

Cells:
  train_4k      -> train_step (AdamW + ZeRO-1 + remat + 4 microbatches)
  prefill_32k   -> prefill (teacher-forced forward)
  decode_32k    -> decode_step with a 32k KV cache
  long_500k     -> decode_step at 524288 context (ssm/hybrid only)

``--quant abfp`` lowers the paper-faithful ABFP-simulation step instead
(column-parallel weight sharding so ABFP tiles stay shard-local; QAT for
train cells, ABFP inference for serve cells).

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--quant abfp]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.core.abfp import QuantConfig
from repro.distributed.sharding import (
    abfp_param_spec_tree,
    batch_spec,
    decode_state_spec_tree,
    param_spec_tree,
    zero1_spec,
)
from repro.launch import mesh as mesh_lib
from repro.launch.hlo_analysis import loop_aware_costs
from repro.models import init_decode_state, init_params
from repro.models.lm import _pattern
from repro.optim.optimizers import AdamW, constant
from repro.training.train_lib import TrainConfig, TrainState, make_train_step

# Artifact output dir; REPRO_DRYRUN_ART_DIR overrides so ad-hoc runs (e.g.
# the mini integration tests) don't pollute the real roofline artifact set.
ART_DIR = os.environ.get("REPRO_DRYRUN_ART_DIR") or os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _quant_cfg(quant: str) -> QuantConfig:
    if quant == "float":
        return QuantConfig(mode="float")
    # Paper-faithful ABFP: tile 128, gain 8, 8/8/8 bits, 0.5 LSB ADC noise —
    # the configuration the paper's Sec. VI analysis selects.
    return QuantConfig(mode="abfp_ref", tile_width=128, gain=8.0,
                       bits_w=8, bits_x=8, bits_y=8, noise_lsb=0.5)


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell —
    weak-type-correct, shardable, no device allocation."""
    mcfg = get_config(arch)
    sc = SHAPES[shape_name]
    b, s = sc.global_batch, sc.seq_len
    out: dict = {}
    if sc.kind == "train":
        if mcfg.frontend == "vision_stub":
            out["embeds"] = jax.ShapeDtypeStruct((b, s, mcfg.d_model),
                                                 jnp.bfloat16)
            out["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        else:
            out["tokens"] = jax.ShapeDtypeStruct((b, s + 1), jnp.int32)
        if mcfg.is_encoder_decoder:
            out["encoder_features"] = jax.ShapeDtypeStruct(
                (b, s, mcfg.d_model), jnp.bfloat16)
    elif sc.kind == "prefill":
        if mcfg.frontend == "vision_stub":
            out["tokens"] = jax.ShapeDtypeStruct((b, s, mcfg.d_model),
                                                 jnp.bfloat16)
        else:
            out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if mcfg.is_encoder_decoder:
            out["encoder_features"] = jax.ShapeDtypeStruct(
                (b, s, mcfg.d_model), jnp.bfloat16)
    else:  # decode
        out["token"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    return out


def _abstract_params(mcfg):
    return jax.eval_shape(lambda k: init_params(k, mcfg),
                          jax.random.PRNGKey(0))


def _param_shardings(mcfg, mesh, quant: str):
    a = _abstract_params(mcfg)
    tree = (abfp_param_spec_tree(a, mesh) if quant == "abfp"
            else param_spec_tree(a, mesh))
    return a, _ns(mesh, tree)


def _run_cell(arch: str, shape_name: str, mesh, mesh_name: str, quant: str,
              save: bool = True, kv_quant: bool = False,
              compression: str = None, microbatches: int = 4,
              tag: str = "") -> dict:
    import dataclasses

    t0 = time.time()
    sc = SHAPES[shape_name]
    mcfg = dataclasses.replace(get_config(arch), remat=(sc.kind == "train"),
                               kv_quant=kv_quant)
    qc = _quant_cfg(quant)
    abstract_params, p_shard = _param_shardings(mcfg, mesh, quant)
    specs = input_specs(arch, shape_name)

    if sc.kind == "train":
        opt = AdamW(schedule=constant(1e-6))
        tcfg = TrainConfig(microbatches=microbatches, quant=qc,
                           compression=compression)
        # MoE archs use the expert-parallel shard_map path over 'model'.
        _, train_step = make_train_step(
            mcfg, opt, tcfg, mesh=mesh if mcfg.num_experts else None)

        a_state = jax.eval_shape(
            lambda p: TrainState(p, opt.init(p), None, jnp.zeros((), jnp.int32)),
            abstract_params)
        pspec_tree = (abfp_param_spec_tree(abstract_params, mesh)
                      if quant == "abfp"
                      else param_spec_tree(abstract_params, mesh))
        z1 = jax.tree.map(
            lambda s, p: zero1_spec(s, p.shape, mesh),
            pspec_tree, abstract_params, is_leaf=lambda x: isinstance(x, P))
        state_shard = TrainState(
            params=_ns(mesh, pspec_tree),
            opt_state=type(a_state.opt_state)(
                step=NamedSharding(mesh, P()),
                mu=_ns(mesh, z1), nu=_ns(mesh, z1), master=_ns(mesh, z1)),
            ef=None,
            step=NamedSharding(mesh, P()),
        )
        a_batch = dict(specs)
        batch_shard = {
            k: NamedSharding(mesh, batch_spec(mesh, v.shape))
            for k, v in specs.items()}

        jitted = jax.jit(
            train_step,
            in_shardings=(state_shard, batch_shard, NamedSharding(mesh, P())),
            out_shardings=(state_shard, None),
            donate_argnums=(0,))               # state buffers alias in-place
        with mesh:
            lowered = jitted.lower(
                a_state, a_batch,
                jax.ShapeDtypeStruct((2,), jnp.uint32))

    elif sc.kind == "prefill":
        # MoE archs route through the expert-parallel shard_map (perf
        # iteration: the GSPMD-partitioned single-shard MoE path was the
        # most collective-bound cell in the grid — EXPERIMENTS.md §Perf).
        moe_mesh = mesh if mcfg.num_experts else None

        def prefill(params, batch, key):
            # Serving prefill: hidden states -> LAST-position logits only
            # (full (B, 32k, 256k-vocab) logits would be TBs; decode starts
            # from the final position).
            from repro.models import forward
            from repro.models.layers import Numerics
            from repro.models.lm import lm_head_logits
            nx = Numerics(qc, key)
            hidden, _ = forward(params, batch["tokens"], mcfg, nx,
                                encoder_features=batch.get("encoder_features"),
                                return_hidden=True, mesh=moe_mesh)
            return lm_head_logits(params, hidden[:, -1:], mcfg, nx)[:, 0]

        a_batch = {"tokens": specs["tokens"]}
        batch_shard = {"tokens": NamedSharding(
            mesh, batch_spec(mesh, specs["tokens"].shape))}
        if "encoder_features" in specs:
            a_batch["encoder_features"] = specs["encoder_features"]
            batch_shard["encoder_features"] = NamedSharding(
                mesh, batch_spec(mesh, specs["encoder_features"].shape))
        out_spec = batch_spec(mesh, (sc.global_batch, mcfg.vocab_size))
        jitted = jax.jit(
            prefill,
            in_shardings=(p_shard, batch_shard, NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, out_spec))
        with mesh:
            lowered = jitted.lower(abstract_params, a_batch,
                                   jax.ShapeDtypeStruct((2,), jnp.uint32))

    else:  # decode
        a_state = jax.eval_shape(
            lambda: init_decode_state(mcfg, sc.global_batch, sc.seq_len))
        s_shard = _ns(mesh, decode_state_spec_tree(a_state, mesh))

        enc_kv_spec = None
        a_enc_kv = None
        if mcfg.is_encoder_decoder:
            _, n_groups, _ = _pattern(mcfg)
            kh, hd = mcfg.num_kv_heads, mcfg.resolved_head_dim
            kv_sd = jax.ShapeDtypeStruct(
                (n_groups, sc.global_batch, sc.seq_len, kh, hd), jnp.bfloat16)
            a_enc_kv = [(kv_sd, kv_sd)]
            axis = "model" if hd % mesh.shape["model"] == 0 else None
            bax = batch_spec(mesh, (sc.global_batch,))[0]
            spec = P(None, bax, None, None, axis)
            enc_kv_spec = [(NamedSharding(mesh, spec),) * 2]

        def decode(params, state, token, key):
            from repro.models import decode_step
            from repro.models.layers import Numerics
            nx = Numerics(qc, key)
            return decode_step(params, state, token, mcfg, nx, enc_kv=None)

        if mcfg.is_encoder_decoder:
            def decode(params, state, token, key, enc_kv):  # noqa: F811
                from repro.models import decode_step
                from repro.models.layers import Numerics
                nx = Numerics(qc, key)
                return decode_step(params, state, token, mcfg, nx,
                                   enc_kv=enc_kv)

        in_sh = [p_shard, s_shard,
                 NamedSharding(mesh, batch_spec(mesh, (sc.global_batch,))),
                 NamedSharding(mesh, P())]
        args = [abstract_params, a_state, specs["token"],
                jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if mcfg.is_encoder_decoder:
            in_sh.append(enc_kv_spec)
            args.append(a_enc_kv)
        jitted = jax.jit(
            decode, in_shardings=tuple(in_sh),
            out_shardings=(NamedSharding(
                mesh, batch_spec(mesh, (sc.global_batch, mcfg.vocab_size))),
                s_shard),
            donate_argnums=(1,))               # KV cache updates in place
        with mesh:
            lowered = jitted.lower(*args)

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # Loop-aware costs: cost_analysis() counts while bodies (= every
    # lax.scan: layers, microbatches, attention chunks) only ONCE; the HLO
    # re-analysis multiplies by known_trip_count.  See hlo_analysis.py.
    la = loop_aware_costs(hlo)
    colls = la["collectives"]
    compile_s = time.time() - t0

    chips = mesh.devices.size
    mem_fields = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_fields[f] = int(v)
    # Per-device steady-state bytes: weights+state (aliased args) + temps.
    live = (mem_fields.get("argument_size_in_bytes", 0)
            + mem_fields.get("temp_size_in_bytes", 0)
            + mem_fields.get("output_size_in_bytes", 0)
            - mem_fields.get("alias_size_in_bytes", 0))

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "quant": quant + tag, "kind": sc.kind, "chips": int(chips),
        "flops_per_device": la["flops"],
        "hbm_bytes_per_device": la["hbm_bytes"],
        "hbm_bytes_pessimistic": la.get("hbm_bytes_pessimistic", -1.0),
        "flops_naive": float(cost.get("flops", -1.0)) if cost else -1.0,
        "hbm_bytes_naive": float(cost.get("bytes accessed", -1.0)) if cost else -1.0,
        "collectives": colls,
        "memory": mem_fields,
        "live_bytes_per_device": int(live),
        "fits_16g": bool(live <= mesh_lib.HBM_PER_CHIP),
        "compile_seconds": round(compile_s, 1),
    }
    print(f"[dryrun] {arch} x {shape_name} x {mesh_name} ({quant}): "
          f"compiled in {compile_s:.0f}s; live/device = {live/2**30:.2f} GiB; "
          f"flops/device = {result['flops_per_device']:.3e}; "
          f"coll bytes/device = {colls['total']['bytes']:.3e}")
    print(f"  memory_analysis: {mem_fields}")

    if save:
        os.makedirs(ART_DIR, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_name}__{quant}{tag}.json"
        with open(os.path.join(ART_DIR, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def runnable_cells():
    """The 40-cell grid minus documented skips (DESIGN.md)."""
    cells = []
    for arch in list_archs():
        mcfg = get_config(arch)
        for shape_name in SHAPES:
            if shape_name == "long_500k" and not mcfg.supports_long_context_decode:
                continue  # full-attention archs skip long_500k (DESIGN.md)
            cells.append((arch, shape_name))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--quant", choices=("float", "abfp"), default="float")
    ap.add_argument("--mesh-shape", default=None,
                    help="override, e.g. '4,2' (mini test)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8-ABFP KV cache (beyond-paper; decode cells)")
    ap.add_argument("--compression", choices=("bf16", "int8"), default=None,
                    help="DP gradient compression (train cells)")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--tag", default="",
                    help="suffix for the artifact filename (perf iterations)")
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        mesh = mesh_lib.make_mesh(shape, axes)
        mesh_name = "x".join(map(str, shape))
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
        mesh_name = "2x16x16" if args.multi_pod else "16x16"

    cells = runnable_cells() if args.all else [(args.arch, args.shape)]
    failures = []
    for arch, shape_name in cells:
        try:
            _run_cell(arch, shape_name, mesh, mesh_name, args.quant,
                      kv_quant=args.kv_quant, compression=args.compression,
                      microbatches=args.microbatches, tag=args.tag)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape_name, repr(e)))
            print(f"[dryrun] FAILED {arch} x {shape_name}: {e}")
            traceback.print_exc()
            if not args.continue_on_error:
                return 1
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}")
        return 1
    print(f"[dryrun] all {len(cells)} cells compiled OK on {mesh_name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
