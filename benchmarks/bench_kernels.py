"""Kernel-path benchmark: fused Pallas ABFP matmul vs the einsum oracle and
the scan path, packed (quantize-once) vs unpacked weights, decode-shape
(m=1 / m=8) rows, the fused QKV decode kernel vs three separate packed
launches, and an adaptive per-tile gain accuracy sweep.

On this CPU container the Pallas kernels run in interpret mode, so absolute
times are NOT TPU-indicative; the benchmark's value here is (a) correctness
at realistic shapes, (b) the HBM-traffic accounting — the packed path's
reason to exist: int8 weight codes + bf16 per-tile scales stream ~half the
weight bytes of bf16 weights (and a quarter of f32), and none of the
per-step max/round/clip work — and (c) the relative packed-vs-unpacked
wall-clock at decode shapes, where weight-side work dominates.

Emits ``name,us_per_call,derived`` CSV rows (the benchmarks/run.py
contract) AND a machine-readable JSON file (``BENCH_kernels.json`` at the
repo root, schema_version 2 — see docs/BENCHMARKS.md; override with
REPRO_BENCH_JSON=path).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abfp import QuantConfig, abfp_matmul, pack_abfp_weight
from repro.kernels.abfp_decode_fused import fused_qkv_packed_pallas
from repro.kernels.abfp_matmul import abfp_matmul_packed_pallas, abfp_matmul_pallas
from repro.kernels.ref import abfp_matmul_ref
from repro.launch.compile_cache import enable_compile_cache

SCHEMA_VERSION = 2

# Prefill-ish shapes (oracle + scan cross-check) and decode shapes (m=1/8).
SHAPES = [(256, 2048, 256), (128, 4096, 512)]
DECODE_SHAPES = [(1, 2048, 2048), (8, 2048, 2048)]
# Fused QKV decode shapes: (m, K, (Nq, Nk, Nv)) — a GQA projection block.
FUSED_SHAPES = [(1, 2048, (2048, 256, 256)), (8, 2048, (2048, 256, 256))]
GAIN_SWEEP = (1.0, 2.0, 4.0, 8.0, 16.0)

_JSON_PATH = os.environ.get(
    "REPRO_BENCH_JSON",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "BENCH_kernels.json"))


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return out, (time.time() - t0) / reps


def _hbm_bytes(m, k, n, tile, out_itemsize=4):
    """Derived HBM traffic per call for each weight representation.

    Activations (f32 in, one read) and the output write are common; the
    weight side is the differentiator:
      float32  — k*n*4      (what the unpacked kernel streams today)
      bfloat16 — k*n*2      (models' param dtype; the fair baseline)
      packed   — k*n*1 int8 codes + (k/tile)*n*2 bf16 scales
    """
    t_tiles = -(-k // tile)
    common = m * k * 4 + m * n * out_itemsize
    w_f32 = k * n * 4
    w_bf16 = k * n * 2
    w_packed = k * n * 1 + t_tiles * n * 2
    return {
        "common_bytes": common,
        "w_f32_bytes": w_f32,
        "w_bf16_bytes": w_bf16,
        "w_packed_bytes": w_packed,
        "packed_vs_bf16_weight_ratio": w_bf16 / w_packed,
        "unpacked_bytes": common + w_bf16,
        "packed_bytes": common + w_packed,
    }


def run(csv_rows: list) -> dict:
    results = {}

    for (m, k, n) in SHAPES:
        for tile in (32, 128):
            cfg = QuantConfig(tile_width=tile, gain=8.0, noise_lsb=0.0,
                              out_dtype=jnp.float32)
            kx, kw = jax.random.split(jax.random.PRNGKey(0))
            x = (jax.random.normal(kx, (m, k)) * 0.5).astype(jnp.bfloat16)
            w = (jax.random.laplace(kw, (k, n)) * 0.05).astype(jnp.bfloat16)
            pw = pack_abfp_weight(w, cfg)

            scan_fn = jax.jit(lambda x, w: abfp_matmul(x, w, cfg))
            ref_fn = jax.jit(lambda x, w: abfp_matmul_ref(x, w, cfg))
            ker_fn = jax.jit(lambda x, w: abfp_matmul_pallas(x, w, cfg))
            pack_fn = jax.jit(lambda x, pw: abfp_matmul_packed_pallas(x, pw, cfg))

            y_s, t_s = _time(scan_fn, x, w)
            y_r, t_r = _time(ref_fn, x, w)
            y_k, t_k = _time(ker_fn, x, w)
            y_p, t_p = _time(pack_fn, x, pw)
            np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                       rtol=3e-5, atol=3e-5)
            np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_r),
                                       rtol=3e-5, atol=3e-5)
            # Packed must be bit-identical to the unpacked kernel.
            np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_k))

            t_tiles = -(-k // tile)
            hbm = _hbm_bytes(m, k, n, tile)
            # The einsum oracle also materializes (T, M, N) partials twice.
            oracle_bytes = hbm["unpacked_bytes"] + 2 * t_tiles * m * n * 4
            name = f"kernel_m{m}_k{k}_n{n}_t{tile}"
            csv_rows.append(f"{name}_pallas,{t_k*1e6:.0f},"
                            f"hbm_bytes={hbm['unpacked_bytes']}")
            csv_rows.append(f"{name}_packed,{t_p*1e6:.0f},"
                            f"hbm_bytes={hbm['packed_bytes']}")
            csv_rows.append(f"{name}_oracle,{t_r*1e6:.0f},"
                            f"hbm_bytes={oracle_bytes}")
            csv_rows.append(f"{name}_scan,{t_s*1e6:.0f},"
                            f"traffic_ratio={oracle_bytes/hbm['unpacked_bytes']:.1f}")
            results[name] = {
                "m": m, "k": k, "n": n, "tile": tile,
                "pallas_s": t_k, "packed_s": t_p, "oracle_s": t_r,
                "scan_s": t_s,
                "packed_speedup_vs_pallas": t_k / t_p,
                "traffic_ratio": oracle_bytes / hbm["unpacked_bytes"],
                **hbm,
            }

    # Decode shapes: the serving hot path.  auto_bm picks an 8-row block;
    # the packed kernel additionally skips all weight re-quantization.
    for (m, k, n) in DECODE_SHAPES:
        for tile in (32, 128):
            cfg = QuantConfig(tile_width=tile, gain=8.0, noise_lsb=0.0,
                              out_dtype=jnp.bfloat16)
            kx, kw = jax.random.split(jax.random.PRNGKey(1))
            x = (jax.random.normal(kx, (m, k)) * 0.5).astype(jnp.bfloat16)
            w = (jax.random.laplace(kw, (k, n)) * 0.05).astype(jnp.bfloat16)
            pw = pack_abfp_weight(w, cfg)

            ker_fn = jax.jit(lambda x, w: abfp_matmul_pallas(x, w, cfg))
            pack_fn = jax.jit(lambda x, pw: abfp_matmul_packed_pallas(x, pw, cfg))
            y_k, t_k = _time(ker_fn, x, w)
            y_p, t_p = _time(pack_fn, x, pw)
            np.testing.assert_array_equal(np.asarray(y_p, np.float32),
                                          np.asarray(y_k, np.float32))

            hbm = _hbm_bytes(m, k, n, tile, out_itemsize=2)
            name = f"decode_m{m}_k{k}_n{n}_t{tile}"
            csv_rows.append(f"{name}_pallas,{t_k*1e6:.0f},"
                            f"hbm_bytes={hbm['unpacked_bytes']}")
            csv_rows.append(
                f"{name}_packed,{t_p*1e6:.0f},"
                f"hbm_bytes={hbm['packed_bytes']}"
                f";w_ratio={hbm['packed_vs_bf16_weight_ratio']:.2f}"
                f";speedup={t_k/t_p:.2f}")
            results[name] = {
                "m": m, "k": k, "n": n, "tile": tile,
                "pallas_s": t_k, "packed_s": t_p,
                "packed_speedup_vs_pallas": t_k / t_p,
                **hbm,
            }

    # Fused QKV decode step: one launch over the concatenated Q/K/V column
    # space vs three stand-alone packed launches.  One grid amortizes the
    # activation stream (x is read once per K-block instead of three times)
    # and drops two kernel dispatches per decode tick.
    for (m, k, cols) in FUSED_SHAPES:
        tile = 32
        cfg = QuantConfig(mode="abfp_packed", tile_width=tile, gain=8.0,
                          noise_lsb=0.0, out_dtype=jnp.bfloat16)
        kx, kw = jax.random.split(jax.random.PRNGKey(2))
        x = (jax.random.normal(kx, (m, k)) * 0.5).astype(jnp.bfloat16)
        pws = tuple(
            pack_abfp_weight(
                (jax.random.laplace(jax.random.fold_in(kw, i), (k, n))
                 * 0.05).astype(jnp.bfloat16), cfg)
            for i, n in enumerate(cols))

        def three_fn(x, pws=pws):
            return tuple(abfp_matmul_packed_pallas(x, pw, cfg) for pw in pws)

        def fused_fn(x, pws=pws):
            return fused_qkv_packed_pallas(x, pws, cfg)

        y3, t3 = _time(jax.jit(three_fn), x)
        yf, tf = _time(jax.jit(fused_fn), x)
        for a, b in zip(y3, yf):    # the tentpole gate: bit-identical
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

        name = f"fused_qkv_m{m}_k{k}_n{'+'.join(map(str, cols))}_t{tile}"
        csv_rows.append(f"{name}_three_calls,{t3*1e6:.0f},launches=3")
        csv_rows.append(f"{name}_fused,{tf*1e6:.0f},"
                        f"launches=1;speedup={t3/tf:.2f}")
        results[name] = {
            "m": m, "k": k, "cols": list(cols), "tile": tile,
            "three_calls_s": t3, "fused_s": tf,
            "fused_speedup_vs_three_calls": t3 / tf,
        }

    # Adaptive per-tile gain sweep: error vs the FLOAT32 oracle as the gain
    # cap rises.  The conservative pow2 per-tile choice must never increase
    # error (the paper's amplification claim); the sweep lands in the JSON.
    gain_rows = []
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    gx = jax.random.normal(kx, (16, 768), jnp.float32)
    gw = jax.random.laplace(kw, (768, 256), jnp.float32) * 0.04
    g_ref = np.asarray(gx @ gw)
    for tile in (32, 128):
        errs = []
        for cap in GAIN_SWEEP:
            cfg = QuantConfig(mode="abfp_fused", tile_width=tile, gain=cap,
                              noise_lsb=0.0, out_dtype=jnp.float32)
            pw = pack_abfp_weight(gw, cfg, adaptive_gain=True)
            y = np.asarray(abfp_matmul_packed_pallas(gx, pw, cfg))
            err = float(np.mean(np.abs(y - g_ref)))
            errs.append(err)
            gain_rows.append({"tile": tile, "gain_cap": cap,
                              "mean_abs_err": err})
            csv_rows.append(f"gain_sweep_t{tile}_g{int(cap)},0,"
                            f"mean_abs_err={err:.5f}")
        assert all(b <= a * (1 + 1e-6) for a, b in zip(errs, errs[1:])), errs
    results["gain_sweep"] = gain_rows

    try:
        with open(_JSON_PATH, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION,
                       "benchmark": "kernels",
                       "backend": jax.default_backend(),
                       "results": results}, f, indent=2, sort_keys=True)
        csv_rows.append(f"bench_kernels_json,0,path={_JSON_PATH}")
    except OSError as e:  # read-only checkout: CSV rows still carry the data
        csv_rows.append(f"bench_kernels_json,0,write_failed={e!r}")
    return results


if __name__ == "__main__":
    enable_compile_cache()
    rows: list = []
    out = run(rows)
    print("\n".join(rows))
    decode = {k: v for k, v in out.items() if k.startswith("decode")}
    for name, r in decode.items():
        print(f"{name}: packed {r['packed_speedup_vs_pallas']:.2f}x vs "
              f"unpacked, weight bytes {r['w_bf16_bytes']} -> "
              f"{r['w_packed_bytes']} "
              f"({r['packed_vs_bf16_weight_ratio']:.2f}x smaller)")
    for name, r in out.items():
        if name.startswith("fused_qkv"):
            print(f"{name}: fused "
                  f"{r['fused_speedup_vs_three_calls']:.2f}x vs three calls")
