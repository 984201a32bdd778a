"""Fig. S1 reproduction — ABFP error distributions vs tile width / gain / noise.

Exact paper protocol (Appendix A): weight matrix (768, 768) ~ Laplace(0,1),
input (16, 25, 768) ~ Normal(0,1) — "a BERT Base projection layer with batch
16, sequence 25" — multiplied in FLOAT32 and ABFP, elementwise difference
dy, 10 repetitions, tiles {8,32,128} x gains {1,2,4,8,16} x ADC noise
{0, 0.5} LSB at 8/8/8.

Quantitative checks of the paper's claims:
  * error variance with noise > without           (Eq. 7)
  * tile 8: error grows with gain                 (saturation)
  * tile 128: error at gain 8 < error at gain 1   (gain recovers LSBs)
  * adaptive per-tile gains (abfp_fused) never do worse than the scalar
    gain at the same cap — the conservative pow2 choice never clips

Also writes ``BENCH_error_dist.json`` (schema_version 2, see
docs/BENCHMARKS.md; override with REPRO_BENCH_JSON=path).
"""

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core.abfp import QuantConfig, abfp_matmul, pack_abfp_weight
from repro.kernels.abfp_matmul import abfp_matmul_packed_pallas
from repro.launch.compile_cache import enable_compile_cache

TILES = (8, 32, 128)
GAINS = (1.0, 2.0, 4.0, 8.0, 16.0)
NOISES = (0.0, 0.5)
REPS = 10
SCHEMA_VERSION = 2

_JSON_PATH = os.environ.get(
    "REPRO_BENCH_JSON",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "BENCH_error_dist.json"))


def run(csv_rows: list) -> dict:
    results = {}
    t0 = time.time()
    for tile in TILES:
        for gain in GAINS:
            for noise in NOISES:
                cfg = QuantConfig(tile_width=tile, gain=gain, noise_lsb=noise,
                                  bits_w=8, bits_x=8, bits_y=8,
                                  out_dtype=jnp.float32)

                @jax.jit
                def one_rep(key, cfg=cfg):
                    kw, kx, kn = jax.random.split(key, 3)
                    w = jax.random.laplace(kw, (768, 768), jnp.float32)
                    x = jax.random.normal(kx, (16, 25, 768), jnp.float32)
                    y_ref = jnp.einsum("bsd,dk->bsk", x, w)
                    y_abfp = abfp_matmul(x, w, cfg, kn)
                    return y_abfp - y_ref

                errs = [one_rep(jax.random.fold_in(jax.random.PRNGKey(0), rep))
                        for rep in range(REPS)]
                e = jnp.stack(errs)
                stats = {
                    "mean": float(e.mean()), "std": float(e.std()),
                    "p01": float(jnp.percentile(e, 1)),
                    "p99": float(jnp.percentile(e, 99)),
                    "max_abs": float(jnp.abs(e).max()),
                }
                results[(tile, gain, noise)] = stats
                csv_rows.append(
                    f"error_dist_t{tile}_g{int(gain)}_n{noise},"
                    f"{(time.time() - t0) * 1e6 / REPS:.0f},"
                    f"std={stats['std']:.4f}")

    # ---- adaptive per-tile gains (abfp_fused packing) -------------------
    # Same protocol, packed weights with adaptive_gain=True: per-tile G_t
    # chosen from code statistics under each cap.  Unlike the scalar sweep
    # above, a large cap cannot hurt a small tile — saturating tiles keep
    # G_t = 1 while headroom-rich tiles amplify.
    kw, kx = jax.random.split(jax.random.PRNGKey(7))
    w = jax.random.laplace(kw, (768, 768), jnp.float32)
    x = jax.random.normal(kx, (16, 25, 768), jnp.float32)
    y_ref = jnp.einsum("bsd,dk->bsk", x, w)
    adaptive = {}
    for tile in TILES:
        errs = []
        for cap in GAINS:
            cfg = QuantConfig(mode="abfp_fused", tile_width=tile, gain=cap,
                              noise_lsb=0.0, bits_w=8, bits_x=8, bits_y=8,
                              out_dtype=jnp.float32)
            pw = pack_abfp_weight(w, cfg, adaptive_gain=True)
            e = abfp_matmul_packed_pallas(x, pw, cfg) - y_ref
            std = float(jnp.std(e))
            errs.append(std)
            adaptive[f"t{tile}_g{int(cap)}"] = {
                "std": std,
                "max_gain": float(jnp.max(pw.gains)),
            }
            csv_rows.append(f"error_dist_adaptive_t{tile}_g{int(cap)},"
                            f"{(time.time() - t0) * 1e6 / REPS:.0f},"
                            f"std={std:.4f}")
        # amplification under the adaptive policy never increases error
        assert all(b <= a * (1 + 1e-6) for a, b in zip(errs, errs[1:])), \
            (tile, errs)

    # ---- assertions on the paper's qualitative structure ----
    checks = {
        "noise_widens": results[(32, 2.0, 0.5)]["std"]
        > results[(32, 2.0, 0.0)]["std"],
        "tile8_gain_hurts": results[(8, 16.0, 0.0)]["std"]
        > results[(8, 1.0, 0.0)]["std"],
        "tile128_gain_helps": results[(128, 8.0, 0.0)]["std"]
        < results[(128, 1.0, 0.0)]["std"],
        "small_tile_less_error_at_g1": results[(8, 1.0, 0.0)]["std"]
        < results[(128, 1.0, 0.0)]["std"],
        # The adaptive policy is conservative (never clips), so it may
        # amplify LESS than a lucky scalar gain — but raising the cap can
        # never leave it worse than no amplification at all, at any tile
        # (same weight/input draw: the cap-1 row IS the no-gain baseline).
        "adaptive_never_worse_than_no_gain": all(
            adaptive[f"t{t}_g{int(g)}"]["std"]
            <= adaptive[f"t{t}_g1"]["std"] * (1 + 1e-6)
            for t in TILES for g in GAINS),
        # And where the scalar gain saturates (tile 8, gain 16 hurts), the
        # per-tile choice holds back and stays at the no-gain error.
        "adaptive_avoids_tile8_saturation": (
            adaptive["t8_g16"]["std"]
            < results[(8, 16.0, 0.0)]["std"]),
    }
    assert all(checks.values()), checks
    out = {"results": {str(k): v for k, v in results.items()},
           "adaptive": adaptive, "checks": checks}
    try:
        with open(_JSON_PATH, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION,
                       "benchmark": "error_dist",
                       "backend": jax.default_backend(),
                       "results": out["results"],
                       "adaptive": adaptive,
                       "checks": {k: bool(v) for k, v in checks.items()}},
                      f, indent=2, sort_keys=True)
        csv_rows.append(f"bench_error_dist_json,0,path={_JSON_PATH}")
    except OSError as e:
        csv_rows.append(f"bench_error_dist_json,0,write_failed={e!r}")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    rows: list = []
    out = run(rows)
    print("\n".join(rows))
    print("checks:", out["checks"])
