"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``chipbench/configs/<name>.json``) and a traffic mix
(``chipbench/traffic/<name>.json``).  A run:

1. makes the weights from ``--seed`` on the device in one jitted call,
   packs them (the program's own packing, jitted), builds the serving
   engine the configuration describes, compiles (or loads from the
   persistent cache) every executable, and serves a few warm requests that
   run each prefill bucket and the decode step once;
2. puts ``warm_live`` requests in flight at the steady state's spread of
   progress (``loadgen.warm``) and serves them until each has its first
   token, then starts the traffic and lets it run ``ramp_s`` seconds, so
   the window opens on the steady state's occupancy (all of this is
   ``setup_s``);
3. measures for ``--seconds`` seconds.  With ``--trace 1`` a few seconds
   of the window are traced with the JAX profiler: in an open loop from
   just before a planned arrival whose prompt needs a pass of the largest
   prefill bucket, so the trace holds one; in a closed loop mid-window;
4. after the window, waits (open loop) until every request that was due
   in it has its first token, frees the program, and checks a sample of
   finished requests against the float32 reference (``correct``).

The last line of standard output is one JSON object; the numbers the
correctness check compared are printed last on standard error as well.
It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import loadgen, reference  # noqa: E402
from chipbench import weights as wlib  # noqa: E402
from chipbench.record import RunRecord, quantile, read_metrics  # noqa: E402
from chipbench.trace_reduce import find_xplane, reduce_trace  # noqa: E402
from chipbench.work import Pass  # noqa: E402

BENCHMARK = ROOT / "BENCHMARK.json"
PEAKS = ROOT / "chipbench" / "peaks.json"
WINDOW_SPAN = "chipbench_traced_window"
SPANS = ("submit", "poll", "idle_wait", "deliver", "sync")
TRACE_S = 2.0
TRACE_LEAD_S = 0.3      # trace start ahead of the arrival it is placed on
STEADY_UID = 1 << 29    # requests in flight from set-up (loadgen.warm)
WARM_UID = 1 << 30      # requests that only warm the executables
# The end-to-end metrics this harness computes, by name.
E2E_METRICS = ("setup_s", "itl_p99_s", "itl_mean_s", "output_tokens_per_s")
# Multipliers the program does not have: a configuration must leave them
# at these values.
PROGRAM_FIXED = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
                 "logits_scaling": 1.0, "rms_norm_eps": 1e-6,
                 "tie_word_embeddings": False}


def load_json(path: Path) -> dict:
    """A JSON file as a dict."""
    return json.loads(Path(path).read_text())


def find_cell(bench: dict, workload: str) -> dict:
    """The ``workloads`` entry named ``workload``."""
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def config_of(bench: dict, cell: dict) -> dict:
    """The configuration file of a cell, by the name it gives."""
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"no configuration {cell['config']!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, kind: str) -> List[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    for key, want in PROGRAM_FIXED.items():
        if key in cfg and cfg[key] != want:
            raise ValueError(f"{cfg['name']}: the program runs {key}={want}, "
                             f"the configuration asks {cfg[key]}")
    hd = cfg["head_dim"]
    if not math.isclose(cfg.get("attention_multiplier", hd ** -0.5),
                        hd ** -0.5, rel_tol=1e-9):
        raise ValueError(f"{cfg['name']}: the program scales attention "
                         f"scores by head_dim ** -0.5")
    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=hd,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp_type="swiglu", rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg.get("num_local_experts", 0),
        experts_per_token=cfg.get("num_experts_per_tok", 0),
        kv_quant=bool(cfg["numerics"]["kv_quant"]))


def quant_config(numerics: dict):
    """The program's QuantConfig for a configuration's numerics."""
    from repro.core.abfp import QuantConfig

    keys = ("tile_width", "bits_w", "bits_x", "bits_y", "gain", "noise_lsb")
    return QuantConfig(mode=numerics["mode"],
                       **{k: numerics[k] for k in keys if k in numerics})


def build_engine(cfg: dict, weights: dict):
    """Pack the weights (the program's packing, jitted as one call) and
    build the serving engine the configuration describes."""
    import jax

    from repro.models.packing import pack_model_params
    from repro.serving import ServingEngine

    mcfg, quant, e = model_config(cfg), quant_config(cfg["numerics"]), \
        cfg["engine"]
    params = weights
    if quant.mode in ("abfp_packed", "abfp_fused"):
        params = jax.jit(pack_model_params, static_argnums=(1, 2))(
            weights, quant, mcfg)
    jax.block_until_ready(params)
    return ServingEngine(
        params, mcfg, capacity=e["capacity"], max_len=e["max_len"],
        quant=quant, seed=0, prefill_chunks=tuple(e["prefill_chunks"]),
        paged=e["paged"], clock=time.perf_counter, overlap=e["overlap"],
        inflight=e["inflight"])


class DispatchLog:
    """Wraps the engine's pass dispatch to record each pass's real work:
    per live slot, the tokens it adds and its cache length after them.
    Installed in traced runs only."""

    def __init__(self, eng):
        self.passes: List[Pass] = []
        self._eng = eng
        self._call = eng._call
        eng._call = self

    def __call__(self, shape_key, args):
        eng, slots = self._eng, []
        need = args[3] if shape_key[0] == "prefill" else None
        for i, r in enumerate(eng.slots):
            if r is None:
                continue
            if need is not None and r.prompt_pos < len(r.prompt):
                slots.append((int(need[i]), r.prompt_pos + int(need[i])))
            else:
                slots.append((1, len(r.prompt) + r.dispatched))
        bucket = shape_key[1] if shape_key[0] == "prefill" else 1
        self.passes.append(Pass(shape_key[0], tuple(slots), bucket))
        return self._call(shape_key, args)


class CompileCounter:
    """Counts compiles and compile-cache loads from a point on."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        self.armed = False

        def on_event(name, **_):
            if self.armed and name == "/jax/compilation_cache/cache_hits":
                self.n += 1

        def on_duration(name, _secs, **_):
            if self.armed and name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def _span(tracing: bool, name: str):
    if not tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _warm(eng, vocab: int):
    """One request per prefill bucket, each run to completion: every
    executable the window uses has run once before traffic starts."""
    from repro.serving import Request

    rng = np.random.default_rng(0)
    for j, bucket in enumerate(eng.prefill_chunks):
        n = max(1, bucket - 2)
        r = Request(uid=WARM_UID + j, prompt=rng.integers(1, vocab, n).tolist(),
                    max_new_tokens=3, arrival_time=time.perf_counter())
        eng.submit(r)
        while not r.done:
            eng.poll()
    eng.sync()


def steady_state(eng, warm: List[loadgen.Planned], on_token) -> list:
    """Put the steady state's requests in flight and serve them until each
    has its first token: the window then opens on the occupancy and the
    spread of lengths that traffic at the cell's rate keeps up."""
    from repro.serving import Request

    reqs = [Request(uid=STEADY_UID + j, prompt=list(p.prompt),
                    max_new_tokens=p.max_new,
                    arrival_time=time.perf_counter(), on_token=on_token)
            for j, p in enumerate(warm)]
    for r in reqs:
        eng.submit(r)
    while any(not r.generated for r in reqs):
        eng.poll()
    return reqs


def trace_start(mix: dict, planned: List[loadgen.Planned], seconds: float,
                chunks) -> float:
    """Seconds from the start of traffic at which the profiler starts.

    An open loop's trace starts ``TRACE_LEAD_S`` before the first arrival
    past the middle of the window whose prompt needs a pass of the largest
    prefill bucket, so it holds that pass with the decode steps around
    it; a closed loop's is centred in the window."""
    ramp = float(mix.get("ramp_s", 0.0))
    mid = ramp + max(0.0, (seconds - TRACE_S) / 2)
    if mix["loop"] == "open":
        big = sorted(chunks)[-2] if len(chunks) > 1 else 0
        for p in planned:
            if (p.due - TRACE_LEAD_S >= mid and len(p.prompt) > big
                    and p.due + TRACE_S <= ramp + seconds):
                return p.due - TRACE_LEAD_S
    return mid


class Tracer:
    """The profiler around a few seconds of the window.  Starting and
    stopping drain the engine's in-flight passes first, so the trace holds
    exactly the passes dispatched inside it."""

    def __init__(self, eng, log: DispatchLog, trace_dir: Optional[str]):
        self._eng, self._log = eng, log
        self._tmp = None if trace_dir else tempfile.TemporaryDirectory(
            prefix="chipbench_trace_")
        self.dir = trace_dir or self._tmp.name
        self.started: Optional[float] = None
        self.running = False
        self._ann = None
        self._idx = [0, 0]

    def start(self):
        import jax

        with jax.profiler.TraceAnnotation("sync"):
            self._eng.sync()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the harness's spans only
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self._idx[0] = len(self._log.passes)
        self.started, self.running = time.perf_counter(), True

    def stop(self):
        import jax

        with jax.profiler.TraceAnnotation("sync"):
            self._eng.sync()
        self._idx[1] = len(self._log.passes)
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def passes(self) -> List[Pass]:
        """The passes dispatched while the profiler ran."""
        return self._log.passes[self._idx[0]:self._idx[1]]

    def reduce(self):
        """The reduced trace (None when none was written)."""
        xplane = find_xplane(self.dir)
        out = reduce_trace(xplane, WINDOW_SPAN, SPANS) if xplane else None
        if self._tmp is not None:
            self._tmp.cleanup()
        return out


def serve(cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
          cell_metrics: Dict[str, list], breaker=None,
          trace_dir: Optional[str] = None) -> dict:
    """Run the cell on whatever device JAX has; returns the result object.

    ``breaker``, for tests, is called with the engine after set-up and may
    break the served path (the check must then come out false).  A trace
    goes to a temporary directory, or is kept in ``trace_dir``."""
    import jax

    from repro.serving import Request

    dev = jax.devices()[0]
    vocab = cfg["vocab_size"]
    setup = {}
    t = time.perf_counter()
    weights = wlib.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    setup["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = build_engine(cfg, weights)
    del weights
    setup["pack_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng.warmup()
    setup["compile_s"] = time.perf_counter() - t
    log = DispatchLog(eng) if trace else None
    if breaker is not None:
        breaker(eng)
    t = time.perf_counter()
    _warm(eng, vocab)
    setup["warm_s"] = time.perf_counter() - t
    compiles = CompileCounter()

    planned = loadgen.plan(mix, seed, seconds, vocab)
    stamps: Dict[int, List[float]] = {}

    def on_token(req, _tok):
        with _span(trace, "deliver"):
            stamps[req.uid].append(time.perf_counter())

    t = time.perf_counter()
    warm = loadgen.warm(mix, seed, vocab, int(mix.get("warm_live", 0)))
    for j in range(len(warm)):
        stamps[STEADY_UID + j] = []
    steady = steady_state(eng, warm, on_token)
    setup["steady_s"] = time.perf_counter() - t

    closed = mix["loop"] == "closed"
    outstanding = int(mix.get("outstanding", 0))
    reqs: Dict[int, "Request"] = {r.uid: r for r in steady}
    t0 = time.perf_counter()
    due: Dict[int, float] = {r.uid: t0 for r in steady}
    sent: Dict[int, float] = dict(due)
    t_open = t0 + float(mix.get("ramp_s", 0.0))
    t_close = t_open + seconds
    tracer = Tracer(eng, log, trace_dir) if trace else None
    t_trace = t0 + trace_start(mix, planned, seconds, eng.prefill_chunks)
    live: List[float] = []      # live share of the slots at each poll
    cached: List[int] = []      # positions the live slots hold, each poll
    opened = False
    nxt = 0
    in_flight = len(steady)

    def submit(now: float):
        nonlocal nxt, in_flight
        p = planned[nxt % len(planned)]
        uid = nxt
        at = now if closed else t0 + p.due
        r = Request(uid=uid, prompt=list(p.prompt), max_new_tokens=p.max_new,
                    arrival_time=at, on_token=on_token)
        stamps[uid] = []
        reqs[uid], due[uid] = r, at
        with _span(trace, "submit"):
            eng.submit(r)
        sent[uid] = time.perf_counter()
        nxt += 1
        in_flight += 1

    def poll():
        nonlocal in_flight
        with _span(trace, "poll"):
            done = eng.poll()
        in_flight -= sum(1 for r in done if r.uid < WARM_UID)

    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            setup_s = now - T_START
            compiles.armed = True
        if now >= t_close:
            break
        if tracer is not None:
            if tracer.started is None and now >= t_trace:
                tracer.start()
            elif tracer.running and now >= tracer.started + TRACE_S:
                tracer.stop()
        if closed:
            while in_flight < outstanding:
                submit(now)
        else:
            while nxt < len(planned) and t0 + planned[nxt].due <= now:
                submit(now)
        if (in_flight == 0 and all(s is None for s in eng.slots)
                and not len(eng.scheduler)):
            # Nothing to serve until the next arrival: wait for it.
            nd = (t0 + planned[nxt].due) if nxt < len(planned) else t_close
            with _span(trace, "idle_wait"):
                time.sleep(max(0.0, min(nd, t_close) - now, 0.0005))
            continue
        poll()
        if opened:
            held = [len(r.prompt) + len(r.generated)
                    for r in eng.slots if r is not None]
            live.append(len(held) / eng.capacity)
            cached.append(sum(held))
    compiles.armed = False
    n_compiles = compiles.n
    if tracer is not None and tracer.running:
        tracer.stop()

    # Requests due in the window get their first token before we stop.
    in_window = [u for u in reqs if t_open <= due[u] < t_close]
    if not closed:
        cap = time.perf_counter() + float(mix.get("drain_cap_s", 60.0))
        while (time.perf_counter() < cap
               and any(not stamps[u] for u in in_window)):
            eng.poll()
    eng.sync()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    finished = [r for r in reqs.values()
                if r.done and len(r.generated) == r.max_new_tokens]
    capacity = eng.capacity
    eng.close()
    eng._call = None
    if log is not None:
        del log._eng
    del eng
    gc.collect()

    # -- end-to-end metrics ------------------------------------------------
    window_tokens = sum(1 for ts in stamps.values() for x in ts
                        if t_open <= x < t_close)
    gaps = [b - a for ts in stamps.values() for a, b in zip(ts, ts[1:])
            if t_open <= b < t_close]
    failed = sum(1 for u in in_window if not stamps[u]) if not closed else 0
    attempted = len(in_window) if not closed else sum(
        1 for u in reqs if sent[u] < t_close and due[u] >= t_open)
    e2e = {
        "setup_s": setup_s,
        "itl_p99_s": quantile(gaps, 0.99),
        "itl_mean_s": float(np.mean(gaps)) if gaps else None,
        "output_tokens_per_s": window_tokens / seconds,
    }

    # -- correctness -------------------------------------------------------
    checks = check_served(cfg, seed, finished)

    result = {"correct": passes(checks) and len(finished) > 0,
              "attempted": attempted, "failed": failed}
    if trace:
        reduced = tracer.reduce()
        record = RunRecord(
            cfg=cfg, mix=mix, peaks=peaks_for(dev.device_kind), seconds=seconds,
            occupancy=live, traced_passes=tracer.passes(),
            trace=reduced)
        result["metrics"] = read_metrics(record, cell_metrics["per_layer"])
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell_metrics["end_to_end"]
                             if e2e.get(m["name"]) is not None}
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": peak}
    if trace and reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s()
        result["device"]["window_s"] = reduced.window_s
        ops = sorted(reduced.op_seconds().items(), key=lambda kv: -kv[1])
        idle = sorted(reduced.idle_by_span().items(), key=lambda kv: -kv[1])
        result["breakdown"] = {"device_ops": [[reduced.label(n), t]
                                              for n, t in ops[:10]],
                               "idle_gaps": [list(x) for x in idle[:10]]}
    thirds = [live[i * len(live) // 3:(i + 1) * len(live) // 3]
              for i in range(3)]
    result["info"] = {"setup": setup, "compiles_in_window": n_compiles,
                      "requests_due_in_window": len(in_window),
                      "finished": len(finished), "gaps": len(gaps),
                      "window_tokens": window_tokens,
                      "cached_positions_mean": (float(np.mean(cached))
                                                if cached else None),
                      "live_slots_by_third": [
                          round(capacity * float(np.mean(x)), 2) if x else None
                          for x in thirds]}
    result["checks"] = checks
    return result


def peaks_for(kind: str) -> dict:
    """The chip's peaks by ``device_kind``; a chip not in the table is an
    error, never a default."""
    table = load_json(PEAKS)
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


def check_served(cfg: dict, seed: int, finished) -> Dict[str, dict]:
    """Hold a seeded sample of finished requests, the longest among them,
    to the float32 reference: the widest and the mean gap by which a
    served (greedy) token's reference logit lies below the reference's
    best.  A number whose limit the configuration leaves null is reported
    and not compared."""
    import jax

    limit = cfg["correct"]
    names = ("max_logit_gap", "mean_logit_gap")
    if not finished:
        return {k: {"value": float("inf"), "limit": limit[k]} for k in names}
    longest = max(finished, key=lambda r: (len(r.generated), -r.uid))
    rest = sorted((r for r in finished if r is not longest),
                  key=lambda r: r.uid)
    rng = np.random.default_rng(int(seed))
    k = min(len(rest), int(limit["sample_requests"]) - 1)
    sample = [longest] + [rest[i] for i in
                          sorted(rng.choice(len(rest), k, replace=False))]
    weights = wlib.make_weights(cfg, seed)
    widest, total, n = 0.0, 0.0, 0
    for r in sample:
        w, s = reference.served_gaps(cfg, weights, r.prompt, r.generated,
                                     cfg["engine"]["max_len"])
        widest, total, n = max(widest, w), total + s, n + len(r.generated)
    del weights
    jax.clear_caches()
    values = {"max_logit_gap": widest, "mean_logit_gap": total / max(1, n)}
    return {k: {"value": values[k], "limit": limit[k], "tokens": n}
            for k in names}


def passes(checks: Dict[str, dict]) -> bool:
    """Every compared number at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values()
               if c["limit"] is not None)


def print_result(result: dict) -> None:
    """The compared numbers on standard error, then the result line."""
    info = result.get("info", {})
    print(f"compiles in the window: {info.get('compiles_in_window')}; "
          f"set-up {json.dumps(info.get('setup'))}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    """Parse the arguments, find the chip, run the cell, print the result."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(BENCHMARK)
    cell = find_cell(bench, args.workload)
    cfg = config_of(bench, cell)
    mix = loadgen.load_mix(cell["traffic"])

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devs[0].platform}); nothing run",
              file=sys.stderr)
        return 3
    if len(devs) < int(cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 3
    peaks_for(devs[0].device_kind)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    metrics = {k: metrics_of(bench, cell["name"], k)
               for k in ("end_to_end", "per_layer")}
    result = serve(cfg, mix, args.seed, args.seconds, bool(args.trace),
                   metrics)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
