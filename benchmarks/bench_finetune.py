"""Table III analog — QAT vs DNF recovery, plus the speed claim.

Protocol (paper Sec. V-B, scaled to this container):
  1. train a small LM to convergence in FLOAT;
  2. pick an ABFP config that *degrades* it (harsh: tile 128, low bits);
  3. recover with (a) QAT — ABFP forward + STE backward, and (b) DNF —
     histogram capture once, then FLOAT forward + sampled noise;
  4. report recovered quality as % of FLOAT32 and wall-clock per step.

Checks: both methods improve degraded quality; DNF's per-step time is lower
than QAT's (the paper reports ~4x on A100; the gap here is CPU-sized but
must be > 1).
"""

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.data import DataConfig, batch_at_step
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.optim import AdamW, constant
from repro.training.finetune import capture_histograms, make_dnf_train_step
from repro.training.train_lib import TrainConfig, make_train_step
from benchmarks.bench_quality_grid import accuracy, train_small_lm

FT_STEPS = 40
# Harsh config: tile 8 at gain 4 (Table II's saturation regime) degrades the
# small model visibly AND gives the QAT simulation its real tiled cost
# (d_model/8 = 16 scan steps per dense — at tile 128 the d=128 smoke model
# has ONE tile and the sim is nearly free, making the paper's QAT-vs-DNF
# speed comparison degenerate at smoke scale).
HARSH = QuantConfig(mode="abfp_ref", tile_width=8, gain=4.0,
                    bits_w=4, bits_x=4, bits_y=6, noise_lsb=0.5)


def _timed_steps(step_jit, state, dcfg, n, key):
    # warmup/compile
    state, _ = step_jit(state, batch_at_step(dcfg, 20_000),
                        jax.random.fold_in(key, 0))
    t0 = time.time()
    for i in range(1, n):
        state, metrics = step_jit(state, batch_at_step(dcfg, 20_000 + i),
                                  jax.random.fold_in(key, i))
    jax.block_until_ready(metrics["loss"])
    return state, (time.time() - t0) / max(n - 1, 1)


def run(csv_rows: list) -> dict:
    params, mcfg, dcfg, _ = train_small_lm(seed=1)
    key = jax.random.PRNGKey(7)

    float_acc = accuracy(params, mcfg, dcfg, QuantConfig(mode="float"), key)
    degraded = accuracy(params, mcfg, dcfg, HARSH, key)
    csv_rows.append(f"finetune_baseline,0,float={float_acc:.4f}")
    csv_rows.append(f"finetune_degraded,0,abfp={degraded:.4f}")
    assert degraded < 0.99 * float_acc, (degraded, float_acc)

    # ---- QAT: ABFP forward (STE), paper's AdamW recipe ----
    opt = AdamW(schedule=constant(3e-4))
    init_state, qat_step = make_train_step(
        mcfg, opt, TrainConfig(quant=HARSH))
    state = init_state(params)
    state, qat_s = _timed_steps(jax.jit(qat_step), state, dcfg, FT_STEPS, key)
    qat_acc = accuracy(state.params, mcfg, dcfg, HARSH, key)
    csv_rows.append(f"finetune_qat,{qat_s*1e6:.0f},acc={qat_acc:.4f}")

    # ---- DNF: capture histograms once, FLOAT forward + noise ----
    t0 = time.time()
    cap_batch = batch_at_step(dcfg, 30_000)["tokens"][:, :-1]
    hists, stds = capture_histograms(params, cap_batch, mcfg, HARSH, key=key)
    capture_s = time.time() - t0
    init_state, dnf_step = make_dnf_train_step(mcfg, opt, hists)
    state = init_state(params)
    state, dnf_s = _timed_steps(jax.jit(dnf_step), state, dcfg, FT_STEPS, key)
    dnf_acc = accuracy(state.params, mcfg, dcfg, HARSH, key)
    csv_rows.append(f"finetune_dnf,{dnf_s*1e6:.0f},acc={dnf_acc:.4f}")
    csv_rows.append(f"finetune_dnf_capture,{capture_s*1e6:.0f},"
                    f"layers={len(stds)}")

    speedup = qat_s / dnf_s
    csv_rows.append(f"finetune_dnf_speedup,0,x={speedup:.2f}")

    checks = {
        "qat_recovers": qat_acc > degraded,
        "dnf_recovers": dnf_acc > degraded,
        "dnf_faster_than_qat": speedup > 1.0,
        "layer_stds_finite": all(s >= 0 for s in stds),
    }
    assert all(checks.values()), checks
    return {"float": float_acc, "degraded": degraded, "qat": qat_acc,
            "dnf": dnf_acc, "qat_s": qat_s, "dnf_s": dnf_s,
            "speedup": speedup, "layer_stds": stds, "checks": checks}


if __name__ == "__main__":
    enable_compile_cache()
    rows: list = []
    out = run(rows)
    print("\n".join(rows))
    print("checks:", out["checks"])
