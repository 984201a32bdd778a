"""Readings that set the limits of ``correct``, many seeds in one process.

    python3 -m chipbench.readings --workload smollm-reason-open \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 30

Each seed runs the whole cell (set-up, traffic, window, check) as a run
does; ``--control-seeds`` run the control: the same cell with every ABFP
bit width lowered from 8 to ``CONTROL_BITS`` (the program's own
lower-precision path).
One JSON line per run gives the numbers compared and ``correct``.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

from chipbench import loadgen
from chipbench.run import ROOT, config_of, find_cell, load_json, metrics_of, serve

CONTROL_BITS = 4


def main(argv=None) -> int:
    """Run the program's seeds, then the control's."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 3
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    cfg = config_of(bench, cell)
    mix = loadgen.load_mix(cell["traffic"])
    metrics = {k: metrics_of(bench, cell["name"], k)
               for k in ("end_to_end", "per_layer")}
    control = copy.deepcopy(cfg)
    b = CONTROL_BITS
    control["numerics"].update(bits_w=b, bits_x=b, bits_y=b)
    runs = [("program", cfg, s) for s in args.seeds.split(",") if s] + \
        [(f"control_int{b}", control, s)
         for s in args.control_seeds.split(",") if s]
    for what, c, seed in runs:
        res = serve(c, mix, int(seed), args.seconds, False, metrics)
        print(json.dumps({"what": what, "seed": int(seed),
                          "correct": res["correct"], "checks": res["checks"],
                          "metrics": res["metrics"], "info": res["info"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
