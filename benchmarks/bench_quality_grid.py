"""Table II analog — DNN quality over tile width x gain x bitwidths.

The paper's MLPerf models/datasets are not available in this container, so
the grid is reproduced as a *trend benchmark* on a model we train ourselves:
a reduced llama-family LM trained on the synthetic Markov task (repro.data),
then evaluated in ABFP simulation over the same grid the paper sweeps:
tiles {8, 32, 128} x gains {1, 2, 4, 8, 16} x bitwidths {6/6/8, 8/8/8}.

Quality metric = next-token accuracy as % of the FLOAT32 accuracy (the
paper's "percent of FLOAT32 quality").  Checks the structure of Table II:
  * tile 8 / gain 1 retains >99% of FLOAT quality
  * tile 8 degrades as gain rises (saturation)
  * tile 128 / moderate-high gain beats tile 128 / gain 1
"""

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.data import DataConfig, batch_at_step
from repro.launch.compile_cache import enable_compile_cache
from repro.models import forward, init_params
from repro.models.layers import Numerics
from repro.optim import AdamW, constant
from repro.training.train_lib import TrainConfig, make_train_step

TILES = (8, 32, 128)
GAINS = (1.0, 2.0, 4.0, 8.0, 16.0)
# Full grid at 8/8/8 (the paper's main setting); 6/6/8 at tile 8 only — the
# paper's finding is that 6-bit operands barely differ, checked there.
BITS = ((6, 6, 8), (8, 8, 8))

TRAIN_STEPS = 200
EVAL_BATCHES = 2


def train_small_lm(seed: int = 0):
    mcfg = dataclasses.replace(
        smoke_config("smollm-360m"), num_layers=4, vocab_size=256)
    dcfg = DataConfig(vocab_size=mcfg.vocab_size, seq_len=64, global_batch=16,
                      seed=seed)
    params = init_params(jax.random.PRNGKey(seed), mcfg)
    opt = AdamW(schedule=constant(3e-3))
    init_state, train_step = make_train_step(mcfg, opt, TrainConfig())
    state = init_state(params)
    step_jit = jax.jit(train_step)
    for i in range(TRAIN_STEPS):
        batch = batch_at_step(dcfg, i)
        state, metrics = step_jit(state, batch,
                                  jax.random.fold_in(jax.random.PRNGKey(1), i))
    return state.params, mcfg, dcfg, float(metrics["loss"])


def accuracy(params, mcfg, dcfg, quant: QuantConfig, key) -> float:
    correct = total = 0
    for i in range(EVAL_BATCHES):
        batch = batch_at_step(dcfg, 10_000 + i)
        tokens = batch["tokens"]
        nx = Numerics(quant, jax.random.fold_in(key, i))
        logits, _ = forward(params, tokens[:, :-1], mcfg, nx)
        pred = jnp.argmax(logits, axis=-1)
        correct += int((pred == tokens[:, 1:]).sum())
        total += tokens[:, 1:].size
    return correct / total


def run(csv_rows: list) -> dict:
    t0 = time.time()
    params, mcfg, dcfg, final_loss = train_small_lm()
    float_acc = accuracy(params, mcfg, dcfg, QuantConfig(mode="float"),
                         jax.random.PRNGKey(2))
    csv_rows.append(f"quality_float32,{(time.time()-t0)*1e6:.0f},"
                    f"acc={float_acc:.4f}")
    assert float_acc > 0.30, f"model failed to learn (acc={float_acc})"

    grid = {}
    for bw, bx, by in BITS:
        for tile in TILES:
            if (bw, bx, by) == (6, 6, 8) and tile != 8:
                continue
            for gain in GAINS:
                qc = QuantConfig(mode="abfp_ref", tile_width=tile, gain=gain,
                                 bits_w=bw, bits_x=bx, bits_y=by,
                                 noise_lsb=0.5)
                t1 = time.time()
                acc = accuracy(params, mcfg, dcfg, qc, jax.random.PRNGKey(3))
                rel = 100.0 * acc / float_acc
                grid[(f"{bw}/{bx}/{by}", tile, gain)] = rel
                csv_rows.append(
                    f"quality_{bw}{bx}{by}_t{tile}_g{int(gain)},"
                    f"{(time.time()-t1)*1e6:.0f},pct_float={rel:.1f}")

    checks = {
        "tile8_g1_above_99pct": grid[("8/8/8", 8, 1.0)] > 99.0,
        "tile8_degrades_with_gain":
            grid[("8/8/8", 8, 16.0)] < grid[("8/8/8", 8, 1.0)],
        "tile128_gain_helps":
            max(grid[("8/8/8", 128, g)] for g in (4.0, 8.0, 16.0))
            > grid[("8/8/8", 128, 1.0)],
        "bitwidth_6_vs_8_small_effect":
            abs(grid[("6/6/8", 8, 1.0)] - grid[("8/8/8", 8, 1.0)]) < 5.0,
    }
    assert all(checks.values()), (checks, grid)
    return {"float_acc": float_acc, "final_loss": final_loss,
            "grid": {str(k): v for k, v in grid.items()}, "checks": checks}


if __name__ == "__main__":
    enable_compile_cache()
    rows: list = []
    out = run(rows)
    print("\n".join(rows))
    print("checks:", out["checks"])
