"""The one load generator: a traffic mix file in, a list of requests out.

A mix (``chipbench/traffic/<name>.json``) is data only.  Its keys:

  loop         "open" (requests are due on a Poisson schedule, whatever
               the server does) or "closed" (``outstanding`` requests are
               kept in flight; the next is due when one finishes)
  rate_per_s   open loop: mean arrival rate
  outstanding  closed loop: requests kept in flight
  prompt_len, output_len
               lognormal lengths: {"dist": "lognormal", "median", "sigma",
               "min", "max"} (rounded, then clipped to [min, max])
  warm_live    requests already in flight when traffic starts, in the
               steady state's mix of progress (see ``warm``): for an open
               loop the measured rate x mean time in the engine, for a
               closed loop the engine's slots
  shape_seed   seed of the fixed set of lengths and gaps
  shuffle_block  sizes are shuffled within consecutive blocks of this many
  ramp_s       seconds of traffic before the measured window opens
  drain_cap_s  open loop: how long after the window closes requests due
               in it may take to get their first token

Every run draws the SAME arrival times and the SAME set of lengths (from
``shape_seed``), and assigns the lengths to arrivals in an order drawn
from ``--seed``; token ids come from ``--seed`` too.  The order is
shuffled within blocks: for an open loop, the arrivals in the ramp, in the
window and after it; for a closed loop, consecutive runs of
``outstanding`` requests; and in both, consecutive runs of
``shuffle_block``.  So every run's window holds the same requests' sizes
in nearly the same sequence, and runs differ in order and content, not in
the amount of work.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
CLOSED_POOL = 512       # closed loop: sizes planned, cycled when used up
STEADY_DRAWS = 4096     # lengths the steady state's mix is drawn from


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it: due time in seconds from the
    start of traffic (open loop; 0 for a closed loop), prompt token ids and
    the number of tokens to generate."""

    index: int
    due: float
    prompt: List[int]
    max_new: int


def load_mix(name: str) -> dict:
    """The traffic mix ``name`` from ``chipbench/traffic/<name>.json``."""
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from a lognormal length spec."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def count_needed(mix: dict, seconds: float) -> int:
    """Requests a run needs: every one due before the window closes (open
    loop, with margin), or the fixed pool (closed loop)."""
    if mix["loop"] == "closed":
        return CLOSED_POOL
    lam = float(mix["rate_per_s"]) * (float(mix.get("ramp_s", 0.0))
                                      + float(seconds))
    return int(math.ceil(lam + 6.0 * math.sqrt(lam) + 16))


def _blocks(bounds: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """A permutation of range(bounds[-1]) that shuffles only within each
    block [bounds[i], bounds[i + 1])."""
    return np.concatenate([a + rng.permutation(b - a)
                           for a, b in zip(bounds[:-1], bounds[1:])])


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """The run's requests in due order (open loop) or send order (closed)."""
    n = count_needed(mix, seconds)
    shape_rng = np.random.default_rng(int(mix["shape_seed"]))
    prompt_lens = draw_lengths(mix["prompt_len"], n, shape_rng)
    output_lens = draw_lengths(mix["output_len"], n, shape_rng)
    rng = np.random.default_rng(int(seed))
    if mix["loop"] == "open":
        dues = np.cumsum(shape_rng.exponential(1.0 / float(mix["rate_per_s"]),
                                               n))
        ramp = float(mix.get("ramp_s", 0.0))
        edges = np.searchsorted(dues, [ramp, ramp + float(seconds)])
        bounds = np.unique(np.concatenate([[0], edges, [n]]))
    elif mix["loop"] == "closed":
        k = max(1, int(mix["outstanding"]))
        bounds = np.unique(np.concatenate([np.arange(0, n, k), [n]]))
        dues = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    step = np.arange(0, n, int(mix.get("shuffle_block", n)))
    order = _blocks(np.unique(np.concatenate([bounds, step])), rng)
    prompt_lens, output_lens = prompt_lens[order], output_lens[order]
    return [Planned(index=i, due=float(dues[i]),
                    prompt=rng.integers(1, vocab, int(prompt_lens[i])).tolist(),
                    max_new=int(output_lens[i]))
            for i in range(n)]


def warm(mix: dict, seed: int, vocab: int, count: int) -> List[Planned]:
    """``count`` requests as the steady state holds them in flight.

    A request in flight at a random moment has an output length drawn in
    proportion to that length (long requests stay longer) and has
    generated a uniform share of it so far.  Each is planned as a prompt
    of its own prompt plus the tokens it has generated, and the rest of
    its output still to come, so set-up can put the engine into the
    steady state's occupancy and spread of lengths in a few prefill
    passes.  Sizes come from ``shape_seed``, token ids from ``seed``."""
    shape_rng = np.random.default_rng([int(mix["shape_seed"]), 1])
    outs = draw_lengths(mix["output_len"], STEADY_DRAWS, shape_rng)
    pick = shape_rng.choice(STEADY_DRAWS, size=count, p=outs / outs.sum())
    outs = outs[pick]
    done = np.floor(shape_rng.random(count) * outs).astype(np.int64)
    prompts = draw_lengths(mix["prompt_len"], count, shape_rng) + done
    rng = np.random.default_rng([int(seed), 1])
    return [Planned(index=-1 - i, due=0.0,
                    prompt=rng.integers(1, vocab, int(prompts[i])).tolist(),
                    max_new=int(outs[i] - done[i]))
            for i in range(count)]
