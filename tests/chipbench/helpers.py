"""Runs of the harness at miniature size on the CPU, past its look for a
chip: the same set-up, traffic, window and correctness check as a cell."""

import json
from pathlib import Path

DATA = Path(__file__).parent / "data"
E2E = {"end_to_end": [{"name": n, "unit": "s"} for n in
                      ("setup_s", "itl_p99_s", "itl_mean_s")]
       + [{"name": "output_tokens_per_s", "unit": "tokens/s"}],
       "per_layer": []}


def load(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def run_tiny(config: str, mix: str, seed: int, *, bits=None, breaker=None,
             seconds: float = 2.0) -> dict:
    """One run of the harness on a test configuration; ``bits`` lowers
    every ABFP bit width (the control), ``breaker`` breaks the served
    path."""
    from chipbench.run import serve

    cfg = load(config)
    if bits is not None:
        cfg["numerics"].update(bits_w=bits, bits_x=bits, bits_y=bits)
    return serve(cfg, load(mix), seed, seconds, False, E2E, breaker=breaker)
