"""Roofline analysis (deliverable g) — reads the dry-run artifacts.

Per (arch x shape x mesh) cell:
  compute term    = HLO_FLOPs_per_device / peak_FLOP/s       (197e12 bf16, v5e)
  memory term     = HLO_bytes_per_device / HBM_bw            (819e9 B/s)
  collective term = collective_wire_bytes_per_device / ICI   (~50e9 B/s/link)

plus MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) per step, the
usefulness ratio MODEL_FLOPS / HLO_FLOPs, the dominant term, and a
bottleneck note.  Emits a markdown table (EXPERIMENTS.md §Roofline consumes
it verbatim).
"""

import glob
import json
import os

from repro.configs import SHAPES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.models import param_count
import jax

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun")


def model_flops_per_step(arch: str, shape_name: str) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = batch tokens (1 step);
    inference (no backward): 2*N*D."""
    mcfg = get_config(arch)
    sc = SHAPES[shape_name]

    from repro.models import init_params
    a = jax.eval_shape(lambda k: init_params(k, mcfg), jax.random.PRNGKey(0))
    n_total = param_count(a)
    if mcfg.num_experts:
        # active = non-expert params + top-k/E of expert params
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        expert_params = sum(
            leaf.size for path, leaf in flat
            if any(getattr(k, "key", None) in ("wi", "wg", "wo") for k in path)
            and any(getattr(k, "key", None) == "moe" for k in path))
        n_active = (n_total - expert_params
                    + expert_params * mcfg.experts_per_token / mcfg.num_experts)
    else:
        n_active = n_total

    if sc.kind == "train":
        tokens = sc.global_batch * sc.seq_len
        return 6.0 * n_active * tokens
    if sc.kind == "prefill":
        tokens = sc.global_batch * sc.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * sc.global_batch


def analyze(path: str) -> dict:
    with open(path) as f:
        d = json.load(f)
    chips = d["chips"]
    flops_dev = max(d["flops_per_device"], 0.0)
    hbm_dev = max(d["hbm_bytes_per_device"], 0.0)
    coll_dev = d["collectives"]["total"]["bytes"]

    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = hbm_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops_per_step(d["arch"], d["shape"])
    useful = mf / (flops_dev * chips) if flops_dev > 0 else 0.0
    bound_s = max(terms.values())
    # roofline fraction: useful model flops per second at the bound, vs peak.
    mfu_at_bound = (mf / chips / PEAK_FLOPS_BF16) / bound_s if bound_s else 0.0

    return {
        **{k: d[k] for k in ("arch", "shape", "mesh", "quant", "kind",
                             "chips", "live_bytes_per_device", "fits_16g")},
        "flops_per_device": flops_dev,
        "hbm_bytes_per_device": hbm_dev,
        "hbm_bytes_pessimistic": d.get("hbm_bytes_pessimistic", -1.0),
        "collective_bytes_per_device": coll_dev,
        **terms,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": useful,
        "roofline_fraction": mfu_at_bound,
    }


_NOTES = {
    "compute_s": "compute-bound: raise MFU via larger per-step math "
                 "(microbatch/fusion) or cut redundant HLO flops (remat)",
    "memory_s": "HBM-bound: fuse/reuse activations, shrink dtype, "
                "re-block to raise arithmetic intensity",
    "collective_s": "ICI-bound: reshard to cut cross-shard traffic, overlap "
                    "collectives with compute, compress gradients",
}


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | mesh | quant | compute_s | memory_s | "
           "collective_s | dominant | MODEL_FLOPS | useful | roofline_frac |"
           " fits 16G | note |")
    sep = "|" + "---|" * 13
    lines = [hdr, sep]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"],
                                         r["quant"])):
        lines.append(
            "| {arch} | {shape} | {mesh} | {quant} | {compute_s:.2e} | "
            "{memory_s:.2e} | {collective_s:.2e} | {dom} | {mf:.2e} | "
            "{useful:.2f} | {rf:.3f} | {fits} | {note} |".format(
                arch=r["arch"], shape=r["shape"], mesh=r["mesh"],
                quant=r["quant"], compute_s=r["compute_s"],
                memory_s=r["memory_s"], collective_s=r["collective_s"],
                dom=r["dominant"].replace("_s", ""), mf=r["model_flops"],
                useful=r["useful_ratio"], rf=r["roofline_fraction"],
                fits="yes" if r["fits_16g"] else "NO",
                note=_NOTES[r["dominant"]].split(":")[0]))
    return "\n".join(lines)


def fused_decode_rows() -> list:
    """Analytic fused-decode cells: one fused QKV launch vs three packed
    launches at decode shapes (m=1 / m=8).

    At decode the QKV projections are memory-bound (useful ratio near the
    weight-byte floor), so the win is pure HBM traffic: the fused grid
    streams the activation row once per K-block instead of once per weight,
    and adds only the (T, nj) f32 gains table.  Representative GQA block:
    K=2048, N = 2048 + 256 + 256, tile 32 (kernels/abfp_decode_fused.py;
    measured wall-clock lives in BENCH_kernels.json ``fused_qkv_*`` rows).
    """
    k, cols, tile = 2048, (2048, 256, 256), 32
    t_tiles = -(-k // tile)
    rows = []
    for m in (1, 8):
        n_tot = sum(cols)
        w_bytes = k * n_tot * 1 + t_tiles * n_tot * 2     # int8 codes + bf16
        gains_bytes = t_tiles * (n_tot // 128) * 4        # f32 (T, nj) table
        out_bytes = m * n_tot * 2
        x_bytes = m * k * 4
        three = 3 * x_bytes + w_bytes + out_bytes
        fused = x_bytes + w_bytes + gains_bytes + out_bytes
        rows.append({
            "kind": "fused_decode", "m": m, "k": k, "cols": list(cols),
            "tile": tile,
            "three_call_bytes": three, "fused_bytes": fused,
            "three_call_memory_s": three / HBM_BW,
            "fused_memory_s": fused / HBM_BW,
            "traffic_speedup": three / fused,
        })
    return rows


def run(csv_rows: list) -> dict:
    paths = sorted(glob.glob(os.path.join(ART_DIR, "*.json")))
    rows = []
    for p in paths:
        try:
            r = analyze(p)
        except Exception as e:  # noqa: BLE001
            csv_rows.append(f"roofline_error_{os.path.basename(p)},0,{e!r}")
            continue
        rows.append(r)
        csv_rows.append(
            f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}_{r['quant']},0,"
            f"dom={r['dominant'].replace('_s','')}"
            f";frac={r['roofline_fraction']:.3f}")
    fused = fused_decode_rows()
    for r in fused:
        csv_rows.append(
            f"roofline_fused_decode_m{r['m']},0,"
            f"traffic_speedup={r['traffic_speedup']:.2f}"
            f";fused_memory_s={r['fused_memory_s']:.2e}")
    md = markdown_table(rows)
    md += ("\n\n### Fused decode step (abfp_fused)\n\n"
           "| m | three-call bytes | fused bytes | traffic speedup |\n"
           "|---|---|---|---|\n")
    for r in fused:
        md += (f"| {r['m']} | {r['three_call_bytes']} | {r['fused_bytes']} "
               f"| {r['traffic_speedup']:.2f}x |\n")
    out_path = os.path.join(ART_DIR, "..", "roofline.md")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        f.write(md)
    return {"rows": rows, "fused_decode": fused,
            "markdown_path": os.path.abspath(out_path)}


if __name__ == "__main__":
    enable_compile_cache()
    csv: list = []
    out = run(csv)
    print("\n".join(csv))
    print(f"\nwrote {out['markdown_path']} ({len(out['rows'])} cells)")
