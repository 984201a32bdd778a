"""Find the knee of an open-loop cell once, by a sweep of fixed rates on
the chip: the highest offered rate whose served rate keeps up with it
without a queue that grows over the window.

    python3 -m chipbench.sweep --workload smollm-reason-open \
        --rates 0.4,0.5,0.6,0.7

One engine serves every rate in turn (lowest first), each under the
cell's own conditions: the steady state's requests put in flight first
(the mix's ``warm_live``, scaled to the rate), then ``ramp_s`` of
traffic and a window of ``--seconds``; what is left is cancelled before
the next rate.  The sweep stops after the first rate whose queue grew.
For each rate it prints one JSON line: offered and completed requests
per second, the queue (arrived, not yet admitted) at the window's start
and end, the output tokens per second, the mean number of live slots and
the p90 time to first token.  The result is written into the mix file by
hand.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from chipbench import loadgen
from chipbench.record import quantile
from chipbench.run import (
    ROOT,
    _warm,
    build_engine,
    config_of,
    find_cell,
    load_json,
    steady_state,
)
from chipbench import weights as wlib


def one_rate(eng, mix: dict, rate: float, seed: int, seconds: float) -> dict:
    """Offer ``rate`` requests/s for ramp + ``seconds``; measure the window."""
    from repro.serving import Request

    vocab = eng.mcfg.vocab_size
    n_warm = round(float(mix.get("warm_live", 0)) * rate / mix["rate_per_s"])
    mix = {**mix, "rate_per_s": rate}
    planned = loadgen.plan(mix, seed, seconds, vocab)
    stamps, due, reqs = {}, {}, {}
    for r in steady_state(eng, loadgen.warm(mix, seed, vocab, n_warm),
                          lambda q, _t: None):
        reqs[r.uid] = r
    t0 = time.perf_counter()
    t_open = t0 + mix["ramp_s"]
    t_close = t_open + seconds
    q_open = None
    live = []
    done_in_window = 0
    nxt = 0
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if q_open is None and now >= t_open:
            q_open = eng.scheduler.pending(now)
        while nxt < len(planned) and t0 + planned[nxt].due <= now:
            p = planned[nxt]
            r = Request(uid=nxt, prompt=list(p.prompt),
                        max_new_tokens=p.max_new, arrival_time=t0 + p.due,
                        on_token=lambda q, _t: stamps[q.uid].append(
                            time.perf_counter()))
            stamps[nxt], due[nxt], reqs[nxt] = [], t0 + p.due, r
            eng.submit(r)
            nxt += 1
        for r in eng.poll():
            if t_open <= time.perf_counter() < t_close:
                done_in_window += 1
        if q_open is not None:
            live.append(sum(s is not None for s in eng.slots))
    q_close = eng.scheduler.pending(time.perf_counter())
    # Cancel what is left (the engine's deadline expiry) instead of
    # serving it out: the next rate starts from an empty engine.
    end = time.perf_counter()
    for r in reqs.values():
        if not r.done:
            r.deadline = end
    eng._has_deadlines = True
    while len(eng.scheduler) or any(s is not None for s in eng.slots):
        eng.poll()
    eng.sync()
    ttft = [stamps[u][0] - due[u] for u in due
            if t_open <= due[u] < t_close and stamps[u]]
    tokens = sum(1 for ts in stamps.values() for x in ts
                 if t_open <= x < t_close)
    offered = sum(1 for u in due if t_open <= due[u] < t_close)
    return {"rate": rate, "offered_per_s": offered / seconds,
            "completed_per_s": done_in_window / seconds,
            "queue_open": q_open, "queue_close": q_close,
            "output_tokens_per_s": tokens / seconds,
            "live_slots_mean": sum(live) / max(1, len(live)),
            "ttft_p90_s": quantile(ttft, 0.9)}


def main(argv=None) -> int:
    """Sweep the rates given on the command line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per rate (default: run_seconds)")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    cfg = config_of(bench, cell)
    mix = loadgen.load_mix(cell["traffic"])
    eng = build_engine(cfg, wlib.make_weights(cfg, args.seed))
    eng.warmup()
    _warm(eng, cfg["vocab_size"])
    for rate in sorted(float(r) for r in args.rates.split(",")):
        row = one_rate(eng, mix, rate, args.seed,
                       args.seconds or bench["run_seconds"])
        print(json.dumps(row), flush=True)
        if row["queue_close"] > row["queue_open"] + 4:
            break       # past the knee: higher rates only queue more
    eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
