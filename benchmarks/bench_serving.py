"""Serving benchmark: closed-loop TTFT (prefill-in-decode vs chunked
prefill) plus an OPEN-LOOP load sweep with per-request SLO metrics, across
numerics modes (float / abfp-kernel / abfp-packed).

Closed loop: each (mode, chunked) cell builds a fresh engine, runs a small
warmup workload that touches every jit shape the timed run needs (decode
tick + each prefill bucket), then times one full workload: TTFT is wall
time from first admission until EVERY request has its first token
(requests == capacity, all admitted at once); throughput is generated
tokens over the full run.

Open loop: the engine runs on the WALL clock (``clock=time.perf_counter``)
and requests arrive by a Poisson process whose rate is a ``--loads``
multiple of the calibrated closed-loop service rate.  Reported per cell:
p50/p99 TTFT, p50 TPOT, and goodput (requests finishing within the TTFT
SLO per second; the SLO is 3x the calibrated per-request p50 TTFT).
Full runs measure each (mode, load) cell twice — BLOCKING dispatch
(fetch-per-tick) and the OVERLAPPED pipeline (on-device sampling,
background delivery, dispatch-ahead) — and record ``tick_utilization``
(device-busy over engine-active wall time) for both.
``--utilization-gate`` runs only the blocking-vs-overlapped comparison at
load 0.9 and writes BENCH_serving_utilization.json (the CI async gate:
overlap must not utilize the device less than blocking).

    PYTHONPATH=src python benchmarks/bench_serving.py         # BENCH_serving.json
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke # tiny shapes; writes
                                                              # pass/fail + ratio to
                                                              # BENCH_serving_smoke.json

The smoke gate (`make bench-smoke`, part of `make test-fast` and CI) fails
when chunked prefill is slower than prefill-in-decode; its JSON artifact
records the measured ratio either way so CI shows the number when the gate
trips.

Full (non-smoke) runs also sweep sharded serving over mesh shapes
(dp, tp) in {(1,1), (2,1), (1,2), (2,4)} on forced placeholder CPU
devices — one subprocess per shape, since the XLA device-count flag binds
at first jax use — and record per-shape closed-loop rows under
``mesh_sweep`` in BENCH_serving.json (``--no-mesh-sweep`` skips).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_params, param_count
from repro.serving import FaultConfig, Request, ServingEngine


def _quant(mode: str) -> QuantConfig:
    if mode == "float":
        return QuantConfig(mode="float")
    jmode = {"abfp-kernel": "abfp_kernel", "abfp-packed": "abfp_packed"}[mode]
    return QuantConfig(mode=jmode, tile_width=32, gain=8.0, noise_lsb=0.5)


def _workload(mcfg, n, prompt_len, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, mcfg.vocab_size,
                                        prompt_len).tolist(),
                    max_new_tokens=max_new)
            for i in range(n)]


def _run(eng, reqs):
    """Admit everything, serve to completion.  Returns (ttft_s, total_s,
    generated_tokens, ticks)."""
    ticks0 = eng.ticks
    t0 = time.perf_counter()
    for r in reqs:
        assert eng.try_admit(r), "workload must fit capacity"
    ttft = None
    while any(s is not None for s in eng.slots):
        eng.step()
        if ttft is None and all(r.generated for r in reqs):
            ttft = time.perf_counter() - t0
    total = time.perf_counter() - t0
    return ttft, total, sum(len(r.generated) for r in reqs), eng.ticks - ticks0


def _warm(eng, mcfg, *, chunked, chunks, capacity, max_len):
    """Compile every shape a timed run could hit: the decode tick and
    (chunked only) each prefill bucket."""
    warm_lens = ({min(c, max_len - 2) for c in chunks} if chunked else {2})
    for warm_prompt in sorted(warm_lens):
        _run(eng, _workload(mcfg, min(2, capacity), warm_prompt, 2, seed=99))


def bench_cell(params, mcfg, *, mode, chunked, capacity, prompt_len,
               max_new, max_len, chunks, seed, mesh=None):
    eng = ServingEngine(params, mcfg, capacity=capacity, max_len=max_len,
                        quant=_quant(mode), seed=seed, chunked=chunked,
                        prefill_chunks=chunks, mesh=mesh)
    # Warm prompts are capped at max_len - 2 (admission guard); the cap
    # selects the same bucket as the largest admissible timed prompt, so
    # every reachable bucket still gets warmed.
    _warm(eng, mcfg, chunked=chunked, chunks=chunks, capacity=capacity,
          max_len=max_len)
    ttft, total, toks, ticks = _run(
        eng, _workload(mcfg, capacity, prompt_len, max_new, seed=seed))
    return {"mode": mode, "chunked": chunked, "ttft_s": round(ttft, 4),
            "total_s": round(total, 4), "tok_per_s": round(toks / total, 2),
            "ticks": ticks}


def calibrate_open_loop(params, mcfg, *, mode, capacity, prompt_len,
                        max_new, max_len, chunks, seed, slo_scale=3.0):
    """Closed-loop calibration on a BLOCKING wall-clock engine: service
    rate (req/s at full occupancy) and the TTFT SLO every open-loop cell
    of this mode is judged against.  Shared between the blocking and the
    overlapped cells so their SLOs (and arrival processes) are identical."""
    eng = ServingEngine(params, mcfg, capacity=capacity, max_len=max_len,
                        quant=_quant(mode), seed=seed, chunked=True,
                        prefill_chunks=chunks, policy="fcfs",
                        clock=time.perf_counter)
    _warm(eng, mcfg, chunked=True, chunks=chunks, capacity=capacity,
          max_len=max_len)
    eng.metrics.reset()
    _, total_s, _, _ = _run(
        eng, _workload(mcfg, capacity, prompt_len, max_new, seed=seed + 1))
    service_rps = capacity / total_s
    slo_ttft = slo_scale * eng.metrics.summary()["ttft"]["p50"]
    return {"service_rps": service_rps, "slo_ttft": slo_ttft}


def bench_open_loop(params, mcfg, *, mode, load, capacity, prompt_len,
                    max_new, max_len, chunks, seed, n_requests,
                    slo_scale=3.0, overlap=False, calib=None):
    """One open-loop cell: wall-clock engine, Poisson arrivals at ``load``
    x the calibrated service rate, FCFS admission.  ``overlap=True`` runs
    the same cell through the overlapped dispatch pipeline (on-device
    sampling, background delivery); the row then also reports tick
    utilization (device-busy over engine-active wall time)."""
    if calib is None:
        calib = calibrate_open_loop(
            params, mcfg, mode=mode, capacity=capacity,
            prompt_len=prompt_len, max_new=max_new, max_len=max_len,
            chunks=chunks, seed=seed, slo_scale=slo_scale)
    slo_ttft = calib["slo_ttft"]

    eng = ServingEngine(params, mcfg, capacity=capacity, max_len=max_len,
                        quant=_quant(mode), seed=seed, chunked=True,
                        prefill_chunks=chunks, policy="fcfs",
                        clock=time.perf_counter, overlap=overlap)
    # AOT-compile the decode tick + every prefill bucket, then run a small
    # warm workload to compile the per-admission jits (slot reset/attach)
    # too — no compile may land inside the timed window.  The warm pass
    # also pays the first-dispatch overhead per shape, so both cells start
    # steady-state; metrics (incl. the utilization gauges) reset after.
    eng.warmup()
    _warm(eng, mcfg, chunked=True, chunks=chunks, capacity=capacity,
          max_len=max_len)
    eng.sync()
    eng._drain_delivered()
    eng.metrics.reset()

    rate = load * calib["service_rps"]
    rng = np.random.default_rng(seed)
    offsets = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    t0 = time.perf_counter()
    for i, off in enumerate(offsets):
        eng.submit(Request(uid=10_000 + i,
                           prompt=rng.integers(
                               1, mcfg.vocab_size, prompt_len).tolist(),
                           max_new_tokens=max_new,
                           arrival_time=t0 + float(off)))
    done = eng.drain()
    duration = time.perf_counter() - t0
    s = eng.metrics.summary()
    good = eng.metrics.goodput(slo_ttft, duration=duration)
    eng.close()

    def _round(v, nd=4):
        return None if v is None else round(v, nd)

    tu = s["tick_utilization"]
    return {"mode": mode, "load": load, "overlap": overlap,
            "arrival_rate_rps": round(rate, 2),
            "ttft_p50_s": _round(s["ttft"]["p50"]),
            "ttft_p99_s": _round(s["ttft"]["p99"]),
            "tpot_p50_s": _round(s["tpot"]["p50"]),   # None when max_new==1
            "slo_ttft_s": round(slo_ttft, 4),
            "goodput_rps": _round(good, 2),
            "finished": len(done),
            "max_queue_depth": s["queue_depth"]["max"],
            "tick_utilization": _round(tu["value"]),
            "device_busy_s": _round(tu["device_busy_s"]),
            "active_s": _round(tu["active_s"])}


# ---------------------------------------------------------------------------
# Goodput under fault injection: rate sweep, recovery on vs off
# ---------------------------------------------------------------------------

FAULT_RATES = (0.001, 0.01, 0.05)

# Stamped into every BENCH json this script writes; bump when row fields
# change shape so downstream tooling can dispatch on it.
SCHEMA_VERSION = 3


# ---------------------------------------------------------------------------
# Overlapped-dispatch utilization gate: overlap must not idle the device
# more than blocking does on the same host at the same load
# ---------------------------------------------------------------------------

def bench_utilization_gate(params, mcfg, *, seed, load=0.9,
                           prompt_len=48, capacity=4, max_new=8,
                           max_len=128, chunks=(8, 16), n_requests=16):
    """Blocking vs overlapped open-loop cells at the SAME load on the SAME
    host, sharing one calibration (identical arrival process + SLO).  The
    gate passes when the overlapped pipeline's tick utilization is at
    least the blocking engine's (small epsilon for run-to-run jitter):
    dispatching ahead must never leave the device MORE host-starved than
    synchronous fetch-per-tick does."""
    cell = dict(mode="float", capacity=capacity, prompt_len=prompt_len,
                max_new=max_new, max_len=max_len, chunks=chunks, seed=seed)
    calib = calibrate_open_loop(params, mcfg, **cell)
    blocking = bench_open_loop(params, mcfg, load=load, overlap=False,
                               calib=calib, n_requests=n_requests, **cell)
    overlapped = bench_open_loop(params, mcfg, load=load, overlap=True,
                                 calib=calib, n_requests=n_requests, **cell)
    b, o = blocking["tick_utilization"], overlapped["tick_utilization"]
    ok = (b is not None and o is not None and o >= b - 0.02)
    return {"load": load, "blocking": blocking, "overlapped": overlapped,
            "pass": bool(ok)}


def bench_fault_sweep(params, mcfg, *, mode, seed,
                      rates=FAULT_RATES, n_requests=24) -> list:
    """Open-loop goodput vs per-tick fault rate, recovery on vs off.

    Runs on the SIMULATED clock (deterministic: same seeds -> same fault
    trace and the same arrivals for every cell), small shapes — this
    measures robustness accounting, not kernel throughput.  Goodput
    excludes corrupted requests (tokens computed against unrepaired
    faulted weights); ``degraded_goodput`` counts them anyway.  Every cell
    asserts request conservation after drain."""
    capacity, prompt_len, max_new, max_len = 4, 8, 8, 64
    chunks = (4, 8)

    def _arrivals(rng):
        return np.cumsum(rng.exponential(1.0, n_requests))

    # Fault-free calibration fixes the TTFT SLO for every cell.
    eng = ServingEngine(params, mcfg, capacity=capacity, max_len=max_len,
                        quant=_quant(mode), seed=seed, chunked=True,
                        prefill_chunks=chunks)
    rng = np.random.default_rng(seed)
    arrivals = _arrivals(rng)
    for i, at in enumerate(arrivals):
        eng.submit(Request(uid=i,
                           prompt=rng.integers(
                               1, mcfg.vocab_size, prompt_len).tolist(),
                           max_new_tokens=max_new, arrival_time=float(at)))
    eng.drain()
    calib = eng.metrics.summary()
    slo_ttft = 3.0 * calib["ttft"]["p50"]

    rows = []
    for rate in rates:
        for recovery in (True, False):
            eng = ServingEngine(
                params, mcfg, capacity=capacity, max_len=max_len,
                quant=_quant(mode), seed=seed, chunked=True,
                prefill_chunks=chunks,
                # horizon ~ the trace length so the >=1-event floor lands
                # inside the run even at the 0.1% rate.
                faults=FaultConfig(rate=rate, seed=seed + 17, horizon=48),
                recovery=recovery, detect_every=2)
            rng = np.random.default_rng(seed)
            arrivals = _arrivals(rng)
            for i, at in enumerate(arrivals):
                eng.submit(Request(
                    uid=i,
                    prompt=rng.integers(
                        1, mcfg.vocab_size, prompt_len).tolist(),
                    max_new_tokens=max_new, arrival_time=float(at)))
            eng.drain()
            cons = eng.metrics.conservation()
            assert cons["ok"], (rate, recovery, cons)
            s = eng.metrics.summary()
            good = eng.metrics.goodput(slo_ttft)
            degraded = eng.metrics.goodput(slo_ttft,
                                           include_corrupted=True)
            rows.append({
                "mode": mode, "fault_rate": rate, "recovery": recovery,
                "slo_ttft": round(slo_ttft, 4),
                "goodput_per_tick": None if good is None else round(good, 4),
                "degraded_goodput_per_tick": (
                    None if degraded is None else round(degraded, 4)),
                "injected": s["faults"]["injected"],
                "detected": s["faults"]["detected"],
                "cols_remapped": s["faults"]["cols_remapped"],
                "tiles_requantized": s["faults"]["tiles_requantized"],
                "reshards": s["faults"]["reshards"],
                "corrupted": s["requests"]["corrupted"],
                "requeued": s["requests"]["requeued"],
                "timed_out": s["requests"]["timed_out"],
                "conservation_ok": cons["ok"],
                "ticks": s["ticks"],
            })
    return rows


def fault_gate(rows) -> bool:
    """Recovery-on must beat recovery-off on goodput at every rate."""
    by_rate = {}
    for r in rows:
        by_rate.setdefault(r["fault_rate"], {})[r["recovery"]] = (
            r["goodput_per_tick"] or 0.0)
    return all(pair.get(True, 0.0) > pair.get(False, 0.0)
               for pair in by_rate.values())


# ---------------------------------------------------------------------------
# Overload robustness: paged capacity gate + goodput-under-overload sweep
# ---------------------------------------------------------------------------

OVERLOAD_LOADS = (1.2, 1.6, 2.0)


def _drive_trace(eng, reqs):
    """Arrival-driven serve: submit each request only once the simulated
    clock reaches its arrival (so admission backpressure sees true queue
    state), then drain.  Returns the finished list."""
    pending = sorted(reqs, key=lambda r: r.arrival_time)
    finished = []
    while pending or len(eng.scheduler) \
            or any(s is not None for s in eng.slots) or eng._returned:
        while pending and pending[0].arrival_time <= eng.now:
            r = pending.pop(0)
            if not eng.submit(r):
                finished.append(r)
        got = eng.poll()
        finished.extend(got)
        if not got and pending and not len(eng.scheduler) \
                and all(s is None for s in eng.slots):
            eng.now = pending[0].arrival_time    # idle: jump to next arrival
    return finished


def bench_capacity_gate(params, mcfg, *, seed) -> dict:
    """Max concurrent requests at a FIXED KV budget of 256 token-slots:
    unpaged spends it as 4 slots x max_len 64; paged spends the same 256
    tokens as a 16-page x 16-token pool shared by 12 slots, so short
    requests (~1 page each) stack 3x deeper.  Simulated clock; the gate is
    STRICT (paged > unpaged)."""
    n, prompt_len, max_new = 16, 8, 4

    def _measure(**ekw):
        eng = ServingEngine(params, mcfg, quant=_quant("float"), seed=seed,
                            chunked=True, prefill_chunks=(4, 8), **ekw)
        reqs = _workload(mcfg, n, prompt_len, max_new, seed=seed)
        for r in reqs:
            r.arrival_time = 0.0
        peak = 0
        for r in reqs:
            eng.submit(r)
        while len(eng.scheduler) or any(s is not None for s in eng.slots):
            eng.poll()
            peak = max(peak, sum(s is not None for s in eng.slots))
        cons = eng.metrics.conservation()
        assert cons["ok"], cons
        return peak, eng.ticks

    unpaged_peak, unpaged_ticks = _measure(capacity=4, max_len=64)
    paged_peak, paged_ticks = _measure(capacity=12, max_len=64, paged=True,
                                       page_size=16, pool_pages=16)
    return {"kv_budget_tokens": 256, "prompt_len": prompt_len,
            "max_new": max_new, "n_requests": n,
            "unpaged": {"capacity": 4, "max_concurrent": unpaged_peak,
                        "ticks": unpaged_ticks},
            "paged": {"capacity": 12, "page_size": 16, "pool_pages": 16,
                      "max_concurrent": paged_peak, "ticks": paged_ticks},
            "pass": bool(paged_peak > unpaged_peak)}


def bench_overload_sweep(params, mcfg, *, seed, loads=OVERLOAD_LOADS,
                         n_requests=32) -> list:
    """Goodput at 1.2-2.0x the calibrated service rate, robust (paged +
    preemption + admission watermarks, 12 slots on the same 256-token KV
    budget) vs the unpaged shed-nothing seed engine (4 slots).  Simulated
    clock, deterministic arrivals per seed; TTFT SLO fixed by a fault-free
    closed-loop calibration of the SEED engine.  Every cell asserts
    request conservation (extended with preemption accounting)."""
    # 20-token requests (2 pages of 16): 12 robust slots want up to 24
    # pages against a 16-page pool, so page pressure and preemption are
    # actually exercised at the high load points.
    prompt_len, max_new, max_len = 8, 12, 64
    chunks = (4, 8)
    base_kw = dict(quant=_quant("float"), seed=seed, chunked=True,
                   prefill_chunks=chunks, max_len=max_len)

    # Calibrate the seed engine closed-loop: service rate in req/tick and
    # the TTFT SLO (3x unloaded p50) every cell is judged against.
    eng = ServingEngine(params, mcfg, capacity=4, **base_kw)
    reqs = _workload(mcfg, 8, prompt_len, max_new, seed=seed + 1)
    for r in reqs:
        r.arrival_time = 0.0
    t0 = eng.ticks
    eng.run(reqs)
    service_rate = 8 / max(1, eng.ticks - t0)       # req per tick
    slo_ttft = 3.0 * eng.metrics.summary()["ttft"]["p50"]

    rows = []
    for load in loads:
        rate = load * service_rate
        for robust in (False, True):
            if robust:
                eng = ServingEngine(params, mcfg, capacity=12, paged=True,
                                    page_size=16, pool_pages=16,
                                    queue_watermark=3 * 12, **base_kw)
            else:
                eng = ServingEngine(params, mcfg, capacity=4, **base_kw)
            rng = np.random.default_rng(seed + int(load * 100))
            arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
            reqs = [Request(uid=i,
                            prompt=rng.integers(1, mcfg.vocab_size,
                                                prompt_len).tolist(),
                            max_new_tokens=max_new,
                            arrival_time=float(arrivals[i]))
                    for i in range(n_requests)]
            _drive_trace(eng, reqs)
            cons = eng.metrics.conservation()
            assert cons["ok"] and cons["preempt_ok"], (load, robust, cons)
            s = eng.metrics.summary()
            good = eng.metrics.goodput(slo_ttft)
            rows.append({
                "load": load, "robust": robust,
                "arrival_rate_per_tick": round(rate, 4),
                "slo_ttft_ticks": round(slo_ttft, 2),
                "goodput_per_tick": None if good is None else round(good, 4),
                "finished": s["requests"]["finished"],
                "shed": s["requests"]["shed"],
                "preempted": s["requests"]["preempted"],
                "resumed": s["requests"]["resumed"],
                "ttft_p50": (None if s["ttft"]["p50"] is None
                             else round(s["ttft"]["p50"], 2)),
                "max_queue_depth": s["queue_depth"]["max"],
                "conservation_ok": cons["ok"],
                "ticks": s["ticks"],
            })
    return rows


def overload_gate(rows) -> bool:
    """Robust (paged+preemption+backpressure) goodput must be >= the
    shed-nothing seed at EVERY load point."""
    by_load = {}
    for r in rows:
        by_load.setdefault(r["load"], {})[r["robust"]] = (
            r["goodput_per_tick"] or 0.0)
    return all(pair.get(True, 0.0) >= pair.get(False, 0.0)
               for pair in by_load.values())


# ---------------------------------------------------------------------------
# Heterogeneous fleet: multiplexed multi-model serving + per-arch quality grid
# ---------------------------------------------------------------------------

FLEET_ARCHS = ("whisper-base", "recurrentgemma-2b", "xlstm-350m")

# Serving-path numerics envelope: ABFP(+read noise) logits on the runner
# prefill/decode path must track float within this normalized error
# (median |l_q - l_f| over the float logit std).  Top-1 agreement is
# recorded but NOT gated: smoke models are untrained, so near-uniform
# logits make argmax flips noise, not signal.
FLEET_QUALITY_ENVELOPE = 0.35


def _fleet_models(archs, seed) -> dict:
    models = {}
    for i, a in enumerate(archs):
        cfg = smoke_config(a)
        models[a] = (init_params(jax.random.PRNGKey(seed + i), cfg), cfg)
    return models


def _fleet_features(runner, seed, uid):
    from repro.models import frontends
    key = jax.random.fold_in(jax.random.PRNGKey(seed), uid)
    return np.asarray(
        frontends.audio_stub_features(
            key, 1, runner.enc_len, runner.mcfg.d_model)[0], np.float32)


def _fleet_workload(models, runners, *, n_per_model, prompt_len, max_new,
                    seed) -> list:
    """Round-robin across models so every tick interleaves lanes; enc-dec
    requests carry per-request stub frontend features."""
    rng = np.random.default_rng(seed)
    names = list(models)
    reqs = []
    for i in range(n_per_model * len(names)):
        name = names[i % len(names)]
        mcfg = models[name][1]
        r = Request(uid=i,
                    prompt=rng.integers(1, mcfg.vocab_size,
                                        prompt_len).tolist(),
                    max_new_tokens=max_new, model=name)
        if runners[name].needs_admission:
            r.features = _fleet_features(runners[name], seed, i)
        reqs.append(r)
    return reqs


def bench_fleet(models, *, mode, seed, n_per_model=4, prompt_len=8,
                max_new=4, max_len=64, capacity_per_model=2) -> dict:
    """Multiplexed fleet vs sequential per-model serving of the SAME
    workload.  Multiplexed: one FleetEngine, shared clock, round-robin
    lanes.  Sequential: one single-model engine per arch, run back to
    back.  Reports per-arch TTFT/TPOT through the fleet lanes plus the
    tick and wall-throughput comparison; asserts per-model request
    conservation on the multiplexed run."""
    from repro.serving.runners import runner_for

    names = list(models)
    runners = {n: runner_for(cfg) for n, (_, cfg) in models.items()}
    chunks = (4, 8)

    fleet = ServingEngine(
        models={n: (models[n][0], models[n][1], runners[n]) for n in names},
        capacity=capacity_per_model * len(names), max_len=max_len,
        quant=_quant(mode), seed=seed, chunked=True, prefill_chunks=chunks)
    reqs = _fleet_workload(models, runners, n_per_model=n_per_model,
                           prompt_len=prompt_len, max_new=max_new, seed=seed)
    t0 = time.perf_counter()
    done = fleet.run(reqs)
    mux_wall = time.perf_counter() - t0
    mux_ticks = fleet.ticks
    mux_tokens = sum(len(r.generated) for r in done)
    cons = fleet.conservation()
    summaries = fleet.summary()

    per_arch = []
    for n in names:
        s, c = summaries[n], cons[n]
        def _r(v):
            return None if v is None else round(float(v), 4)

        per_arch.append({
            "arch": n, "runner": type(runners[n]).__name__,
            "slots": fleet.lanes[n].capacity,
            "ttft_p50": _r(s["ttft"]["p50"]),
            "ttft_p99": _r(s["ttft"]["p99"]),
            "tpot_p50": _r(s["tpot"]["p50"]),
            "completed": c["completed"], "submitted": c["submitted"],
            "preempted": c["preempted"],
            "conservation_ok": bool(c["ok"])})

    # Sequential baseline: same per-model workload through isolated
    # single-model engines, one after another.
    seq_wall, seq_ticks, seq_tokens = 0.0, 0, 0
    for n in names:
        eng = ServingEngine(models[n][0], models[n][1], runner=runners[n],
                            capacity=capacity_per_model, max_len=max_len,
                            quant=_quant(mode), seed=seed, chunked=True,
                            prefill_chunks=chunks)
        sub = [r for r in _fleet_workload(
            models, runners, n_per_model=n_per_model, prompt_len=prompt_len,
            max_new=max_new, seed=seed) if r.model == n]
        t0 = time.perf_counter()
        fin = eng.run(sub)
        seq_wall += time.perf_counter() - t0
        seq_ticks += eng.ticks
        seq_tokens += sum(len(r.generated) for r in fin)

    return {
        "archs": names, "mode": mode, "n_requests": len(reqs),
        "per_arch": per_arch,
        "multiplexed": {"ticks": mux_ticks, "wall_s": round(mux_wall, 3),
                        "tokens": mux_tokens,
                        "tok_per_s": round(mux_tokens / max(mux_wall, 1e-9),
                                           1)},
        "sequential": {"ticks": seq_ticks, "wall_s": round(seq_wall, 3),
                       "tokens": seq_tokens,
                       "tok_per_s": round(seq_tokens / max(seq_wall, 1e-9),
                                          1)},
        "conservation_ok": bool(all(c["ok"] for c in cons.values())),
    }


def fleet_quality_rows(models, *, seed, prompt_len=16,
                       envelope=FLEET_QUALITY_ENVELOPE) -> list:
    """Reduced DNF-style accuracy grid over the SERVING path: for each
    arch, prefill one prompt through the runner's own closures in float
    and in ABFP(+0.5 LSB read noise) and compare last-token logits —
    normalized rel_err must stay inside the envelope.  Also reports the
    per-layer differential-noise stds (core.dnf over forward_capture) so
    regressions point at the offending layer, and top-1 agreement
    (recorded, not gated — see FLEET_QUALITY_ENVELOPE)."""
    import jax.numpy as jnp

    from repro.core.dnf import NoiseHistogram
    from repro.models import forward_capture
    from repro.models.layers import Numerics
    from repro.serving.runners import runner_for

    qa = QuantConfig(mode="abfp_ref", tile_width=32, gain=8.0, noise_lsb=0.5)
    rows = []
    for name, (params, mcfg) in models.items():
        runner = runner_for(mcfg)
        rng = np.random.default_rng(seed)
        prompt = rng.integers(1, mcfg.vocab_size, prompt_len)
        tokens = jnp.asarray(prompt[None])
        n_tok = jnp.full((1,), prompt_len, jnp.int32)
        feats = (_fleet_features(runner, seed, 0)
                 if runner.needs_admission else None)
        akey = jax.random.PRNGKey(seed + 7)

        def last_logits(quant):
            state = runner.init_state(1, 2 * prompt_len)
            if runner.needs_admission:
                state = runner.make_admit(quant, None)(
                    params, state, jnp.asarray(feats), jnp.int32(0), akey)
            logits, _ = jax.jit(runner.make_prefill(quant, None))(
                params, state, tokens, n_tok, jax.random.PRNGKey(seed))
            return np.asarray(logits[0], np.float32)

        lf = last_logits(QuantConfig(mode="float"))
        lq = last_logits(qa)
        rel_err = float(np.median(np.abs(lq - lf)) / max(lf.std(), 1e-9))
        top1 = bool(int(lf.argmax()) == int(lq.argmax()))

        # Per-layer differential noise on the same prompt (paper Fig. 3
        # capture, reused from the DNF pipeline).
        counter = [0]

        def _factory():
            counter[0] += 1
            return Numerics(qa, jax.random.fold_in(
                jax.random.PRNGKey(seed + 13), counter[0]))

        _, deltas = forward_capture(
            params, tokens, mcfg, Numerics(QuantConfig(mode="float"),
                                           jax.random.PRNGKey(seed)),
            _factory,
            encoder_features=(jnp.asarray(feats)[None]
                              if feats is not None else None))
        layer_stds = [round(float(NoiseHistogram.fit(d).std), 6)
                      for d in deltas]

        rows.append({
            "arch": name, "runner": type(runner).__name__,
            "prompt_len": prompt_len, "quant": "abfp_ref t32 g8 n0.5",
            "rel_err": round(rel_err, 4), "envelope": envelope,
            "top1_agree": top1,
            "dnf_layer_std": layer_stds,
            "pass": bool(rel_err <= envelope)})
    return rows


def fleet_gate(fleet_row, quality_rows) -> bool:
    """Per-model conservation on the multiplexed run AND every arch's
    serving-path ABFP logits inside the quality envelope."""
    return bool(fleet_row["conservation_ok"]
                and all(r["completed"] == r["submitted"]
                        for r in fleet_row["per_arch"])
                and all(q["pass"] for q in quality_rows))


# ---------------------------------------------------------------------------
# Per-mesh-shape sweep: sharded serving throughput at forced CPU meshes
# ---------------------------------------------------------------------------

MESH_SHAPES = ((1, 1), (2, 1), (1, 2), (2, 4))


def mesh_one(args) -> None:
    """Child-process entry (--mesh-one dp,tp): one closed-loop cell per mode
    on that mesh, rows printed as ``MESH_ROW <json>`` for the parent.  The
    parent forces dp*tp placeholder CPU devices via XLA_FLAGS before spawn
    (the flag must be set before first jax use, hence the subprocess)."""
    dp, tp = (int(v) for v in args.mesh_one.split(","))
    mesh = make_mesh((dp, tp), ("data", "model"))
    mcfg = smoke_config(args.arch)
    params = init_params(jax.random.PRNGKey(args.seed), mcfg)
    chunks = tuple(int(c) for c in args.chunks.split(","))
    for mode in args.modes.split(","):
        row = bench_cell(params, mcfg, mode=mode, chunked=True,
                         capacity=args.capacity, prompt_len=args.prompt_len,
                         max_new=args.max_new, max_len=args.max_len,
                         chunks=chunks, seed=args.seed, mesh=mesh)
        row["mesh"] = [dp, tp]
        print("MESH_ROW " + json.dumps(row), flush=True)


def mesh_sweep(args) -> list:
    """Spawn one subprocess per mesh shape (XLA device-count forcing is a
    process-level, first-jax-use flag) and collect the MESH_ROW lines."""
    import os
    import subprocess

    rows = []
    for dp, tp in MESH_SHAPES:
        env = dict(os.environ)
        # Placeholder CPU devices, by design: the child must not contend
        # with this process (or another) for an accelerator.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={dp * tp}").strip()
        cmd = [sys.executable, __file__, "--mesh-one", f"{dp},{tp}",
               "--arch", args.arch, "--modes", "float,abfp-packed",
               "--capacity", "4", "--prompt-len", "8", "--max-new", "4",
               "--max-len", "32", "--chunks", "4,8",
               "--seed", str(args.seed)]
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=1200)
        got = [json.loads(ln.split(" ", 1)[1])
               for ln in r.stdout.splitlines() if ln.startswith("MESH_ROW ")]
        if r.returncode != 0 or not got:
            print(f"  mesh ({dp},{tp}): FAILED\n{r.stdout}{r.stderr}")
            raise SystemExit(1)
        for row in got:
            print(f"  mesh ({dp},{tp}) {row['mode']:12s} "
                  f"tok/s {row['tok_per_s']:8.1f}  ttft {row['ttft_s']:.3f}s "
                  f"ticks {row['ticks']}")
        rows += got
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=320)
    ap.add_argument("--modes", default="float,abfp-kernel,abfp-packed")
    ap.add_argument("--chunks", default="16,64,128")
    ap.add_argument("--loads", default="0.5,0.9",
                    help="open-loop arrival rates as multiples of the "
                         "calibrated closed-loop service rate")
    ap.add_argument("--open-requests", type=int, default=None,
                    help="requests per open-loop cell (default 2*capacity)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: BENCH_serving.json at "
                         "the repo root; BENCH_serving_smoke.json with "
                         "--smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, float only; gates on the chunked "
                         "path not being slower than prefill-in-decode and "
                         "writes a machine-readable pass/fail JSON")
    ap.add_argument("--mesh-one", default=None,
                    help="internal (child of the mesh sweep): run one "
                         "closed-loop cell per mode on a dp,tp mesh and "
                         "print MESH_ROW json lines")
    ap.add_argument("--no-mesh-sweep", action="store_true",
                    help="skip the per-mesh-shape sharded-serving sweep "
                         "(full runs only; --smoke never sweeps)")
    ap.add_argument("--faults-only", action="store_true",
                    help="run ONLY the goodput-under-fault-rate sweep and "
                         "write BENCH_serving_faults.json; exits nonzero "
                         "when recovery-on fails to beat recovery-off at "
                         "any rate (the CI fault gate)")
    ap.add_argument("--fault-rates", default=None,
                    help="comma-separated per-tick fault rates for the "
                         "sweep (default 0.001,0.01,0.05)")
    ap.add_argument("--no-fault-sweep", action="store_true",
                    help="skip the fault sweep on full runs")
    ap.add_argument("--overload-only", action="store_true",
                    help="run ONLY the paged capacity gate + the goodput-"
                         "under-overload sweep and write "
                         "BENCH_serving_overload.json; exits nonzero when "
                         "paged does not beat unpaged concurrency at the "
                         "fixed KV budget or robust goodput drops below "
                         "the seed at any load (the CI overload gate)")
    ap.add_argument("--overload-loads", default=None,
                    help="comma-separated overload multiples of the "
                         "calibrated service rate (default 1.2,1.6,2.0)")
    ap.add_argument("--no-overload-sweep", action="store_true",
                    help="skip the capacity gate + overload sweep on "
                         "full runs")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run ONLY the heterogeneous-fleet bench (whisper + "
                         "recurrentgemma + xlstm multiplexed on one engine) "
                         "plus the per-arch serving-path quality grid and "
                         "write BENCH_serving_fleet.json; exits nonzero on "
                         "per-model conservation failure or a quality-"
                         "envelope miss (the CI fleet gate)")
    ap.add_argument("--fleet-archs", default=None,
                    help="comma-separated archs for the fleet bench "
                         "(default whisper-base,recurrentgemma-2b,"
                         "xlstm-350m)")
    ap.add_argument("--no-fleet", action="store_true",
                    help="skip the fleet bench + quality grid on full runs")
    ap.add_argument("--utilization-gate", action="store_true",
                    help="run ONLY the blocking-vs-overlapped tick-"
                         "utilization comparison at open-loop load 0.9 and "
                         "write BENCH_serving_utilization.json; exits "
                         "nonzero when the overlapped pipeline utilizes the "
                         "device less than the blocking engine on this "
                         "host (the CI async gate)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mesh_one:
        mesh_one(args)
        return

    if args.utilization_gate:
        mcfg = smoke_config(args.arch)
        params = init_params(jax.random.PRNGKey(args.seed), mcfg)
        print("[bench_serving] utilization gate: blocking vs overlapped "
              "at open-loop load 0.9")
        gate = bench_utilization_gate(params, mcfg, seed=args.seed)
        for label in ("blocking", "overlapped"):
            r = gate[label]
            print(f"  {label:10s} tick_utilization {r['tick_utilization']} "
                  f"(device busy {r['device_busy_s']}s of {r['active_s']}s "
                  f"active)  ttft p50 {r['ttft_p50_s']}s  "
                  f"goodput {r['goodput_rps']} req/s")
        out = args.out
        if out is None:
            root = Path(__file__).resolve().parent.parent
            out = str(root / "BENCH_serving_utilization.json")
        Path(out).write_text(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "benchmark": "serving_utilization",
            "arch": args.arch, "reduced": True,
            "backend": jax.default_backend(),
            "utilization_gate": gate,
            "gate": {"pass": gate["pass"],
                     "metric": "overlapped tick_utilization >= blocking "
                               "(epsilon 0.02) at load 0.9"},
        }, indent=2) + "\n")
        print(f"[bench_serving] wrote {out}")
        if not gate["pass"]:
            print("[bench_serving] utilization gate FAIL: overlapped "
                  "pipeline utilized the device less than blocking")
            sys.exit(1)
        print("[bench_serving] utilization gate OK")
        return

    fault_rates = (tuple(float(x) for x in args.fault_rates.split(","))
                   if args.fault_rates else FAULT_RATES)
    if args.faults_only:
        mcfg = smoke_config(args.arch)
        params = init_params(jax.random.PRNGKey(args.seed), mcfg)
        print(f"[bench_serving] fault sweep only: rates={fault_rates}, "
              f"mode=abfp-packed")
        fault_rows = bench_fault_sweep(params, mcfg, mode="abfp-packed",
                                       seed=args.seed, rates=fault_rates)
        for r in fault_rows:
            print(f"  rate {r['fault_rate']:6.3f} "
                  f"recovery={'on ' if r['recovery'] else 'off'} "
                  f"goodput {r['goodput_per_tick']} "
                  f"(degraded {r['degraded_goodput_per_tick']})  "
                  f"inj {r['injected']} corrupt {r['corrupted']} "
                  f"requeue {r['requeued']} reshards {r['reshards']}")
        ok = fault_gate(fault_rows)
        out = args.out
        if out is None:
            root = Path(__file__).resolve().parent.parent
            out = str(root / "BENCH_serving_faults.json")
        Path(out).write_text(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "benchmark": "serving_fault_sweep",
            "arch": args.arch, "reduced": True,
            "backend": jax.default_backend(),
            "fault_sweep": fault_rows,
            "gate": {"pass": bool(ok),
                     "metric": "goodput recovery-on > recovery-off",
                     "rates": list(fault_rates)},
        }, indent=2) + "\n")
        print(f"[bench_serving] wrote {out}")
        if not ok:
            print("[bench_serving] fault gate FAIL: recovery-on did not "
                  "beat recovery-off at every rate")
            sys.exit(1)
        print("[bench_serving] fault gate OK")
        return

    fleet_archs = (tuple(a for a in args.fleet_archs.split(",") if a)
                   if args.fleet_archs else FLEET_ARCHS)
    if args.fleet_only:
        models = _fleet_models(fleet_archs, args.seed)
        print(f"[bench_serving] fleet only: archs={fleet_archs}")
        fleet_row = bench_fleet(models, mode="float", seed=args.seed)
        for r in fleet_row["per_arch"]:
            print(f"  {r['arch']:20s} {r['runner']:15s} "
                  f"ttft p50 {r['ttft_p50']} p99 {r['ttft_p99']}  "
                  f"tpot p50 {r['tpot_p50']}  "
                  f"completed {r['completed']}/{r['submitted']} "
                  f"preempted {r['preempted']}")
        print(f"  multiplexed {fleet_row['multiplexed']['ticks']} ticks "
              f"({fleet_row['multiplexed']['tok_per_s']} tok/s) vs "
              f"sequential {fleet_row['sequential']['ticks']} ticks "
              f"({fleet_row['sequential']['tok_per_s']} tok/s)")
        quality = fleet_quality_rows(models, seed=args.seed)
        for q in quality:
            print(f"  quality {q['arch']:20s} rel_err {q['rel_err']:.4f} "
                  f"(envelope {q['envelope']})  top1_agree "
                  f"{q['top1_agree']}  "
                  f"{'OK' if q['pass'] else 'FAIL'}")
        ok = fleet_gate(fleet_row, quality)
        out = args.out
        if out is None:
            root = Path(__file__).resolve().parent.parent
            out = str(root / "BENCH_serving_fleet.json")
        Path(out).write_text(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "benchmark": "serving_fleet",
            "archs": list(fleet_archs), "reduced": True,
            "backend": jax.default_backend(),
            "fleet": fleet_row,
            "quality": quality,
            "gate": {"pass": bool(ok),
                     "metric": "per-model conservation AND serving-path "
                               "rel_err <= envelope per arch",
                     "envelope": FLEET_QUALITY_ENVELOPE},
        }, indent=2, default=str) + "\n")
        print(f"[bench_serving] wrote {out}")
        if not ok:
            print("[bench_serving] fleet gate FAIL: conservation or "
                  "quality envelope miss")
            sys.exit(1)
        print("[bench_serving] fleet gate OK")
        return

    overload_loads = (tuple(float(x) for x in args.overload_loads.split(","))
                      if args.overload_loads else OVERLOAD_LOADS)
    if args.overload_only:
        mcfg = smoke_config(args.arch)
        params = init_params(jax.random.PRNGKey(args.seed), mcfg)
        print(f"[bench_serving] overload only: loads={overload_loads}")
        cap = bench_capacity_gate(params, mcfg, seed=args.seed)
        print(f"  capacity @ {cap['kv_budget_tokens']}-token KV budget: "
              f"unpaged {cap['unpaged']['max_concurrent']} "
              f"-> paged {cap['paged']['max_concurrent']} concurrent "
              f"({'OK' if cap['pass'] else 'FAIL'})")
        over_rows = bench_overload_sweep(params, mcfg, seed=args.seed,
                                         loads=overload_loads)
        for r in over_rows:
            print(f"  load {r['load']:3.1f}x "
                  f"{'robust' if r['robust'] else 'seed  '} "
                  f"goodput {r['goodput_per_tick']} "
                  f"ttft p50 {r['ttft_p50']}  shed {r['shed']} "
                  f"preempted {r['preempted']} qdepth<= "
                  f"{r['max_queue_depth']}")
        ok = cap["pass"] and overload_gate(over_rows)
        out = args.out
        if out is None:
            root = Path(__file__).resolve().parent.parent
            out = str(root / "BENCH_serving_overload.json")
        Path(out).write_text(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "benchmark": "serving_overload",
            "arch": args.arch, "reduced": True,
            "backend": jax.default_backend(),
            "capacity_gate": cap,
            "overload_sweep": over_rows,
            "gate": {"pass": bool(ok),
                     "metric": "paged capacity > unpaged AND robust "
                               "goodput >= seed at every load",
                     "loads": list(overload_loads)},
        }, indent=2) + "\n")
        print(f"[bench_serving] wrote {out}")
        if not ok:
            print("[bench_serving] overload gate FAIL")
            sys.exit(1)
        print("[bench_serving] overload gate OK")
        return

    if args.smoke:
        args.prompt_len, args.capacity, args.max_new = 48, 2, 2
        args.max_len, args.modes, args.chunks = 64, "float", "8,16"
        args.loads = "0.8"

    mcfg = smoke_config(args.arch)
    chunks = tuple(int(c) for c in args.chunks.split(","))
    loads = tuple(float(x) for x in args.loads.split(","))
    n_open = args.open_requests or 2 * args.capacity
    params = init_params(jax.random.PRNGKey(args.seed), mcfg)
    print(f"[bench_serving] {args.arch} (reduced): "
          f"{param_count(params)/1e6:.1f}M params, prompt_len="
          f"{args.prompt_len}, capacity={args.capacity}, chunks={chunks}")

    rows, speedups = [], {}
    for mode in args.modes.split(","):
        cell = dict(capacity=args.capacity, prompt_len=args.prompt_len,
                    max_new=args.max_new, max_len=args.max_len,
                    chunks=chunks, seed=args.seed)
        base = bench_cell(params, mcfg, mode=mode, chunked=False, **cell)
        chnk = bench_cell(params, mcfg, mode=mode, chunked=True, **cell)
        rows += [base, chnk]
        speedups[mode] = round(base["ttft_s"] / chnk["ttft_s"], 2)
        print(f"  {mode:12s} ttft {base['ttft_s']:8.3f}s -> "
              f"{chnk['ttft_s']:8.3f}s  ({speedups[mode]:5.1f}x)   "
              f"tok/s {base['tok_per_s']:8.1f} -> {chnk['tok_per_s']:8.1f}   "
              f"ticks {base['ticks']} -> {chnk['ticks']}")

    open_rows = []
    for mode in args.modes.split(","):
        cell = dict(capacity=args.capacity, prompt_len=args.prompt_len,
                    max_new=args.max_new, max_len=args.max_len,
                    chunks=chunks, seed=args.seed)
        calib = calibrate_open_loop(params, mcfg, mode=mode, **cell)
        for load in loads:
            for overlap in ((False, True) if not args.smoke
                            else (False,)):
                row = bench_open_loop(
                    params, mcfg, mode=mode, load=load, overlap=overlap,
                    calib=calib, n_requests=n_open, **cell)
                open_rows.append(row)
                tu = row["tick_utilization"]
                print(f"  {mode:12s} load {load:3.1f} "
                      f"{'overlap ' if overlap else 'blocking'} "
                      f"ttft p50 {row['ttft_p50_s']:7.3f}s "
                      f"p99 {row['ttft_p99_s']:7.3f}s  "
                      f"goodput {row['goodput_rps']} req/s "
                      f"(slo {row['slo_ttft_s']:.3f}s)  "
                      f"util {'-' if tu is None else f'{tu:.2f}'}  "
                      f"qdepth<= {row['max_queue_depth']}")

    mesh_rows = []
    if not args.smoke and not args.no_mesh_sweep:
        print("[bench_serving] per-mesh-shape sweep (forced CPU devices, "
              "subprocess per shape)")
        mesh_rows = mesh_sweep(args)

    fault_rows = []
    if not args.smoke and not args.no_fault_sweep:
        print("[bench_serving] goodput-under-fault-rate sweep "
              "(abfp-packed, simulated clock)")
        fault_rows = bench_fault_sweep(params, mcfg, mode="abfp-packed",
                                       seed=args.seed, rates=fault_rates)
        for r in fault_rows:
            print(f"  rate {r['fault_rate']:6.3f} "
                  f"recovery={'on ' if r['recovery'] else 'off'} "
                  f"goodput {r['goodput_per_tick']} "
                  f"(degraded {r['degraded_goodput_per_tick']})  "
                  f"inj {r['injected']} corrupt {r['corrupted']} "
                  f"requeue {r['requeued']} reshards {r['reshards']}")
        if not fault_gate(fault_rows):
            print("[bench_serving] WARNING: recovery-on did not beat "
                  "recovery-off at every fault rate")

    cap_row, over_rows = None, []
    if not args.smoke and not args.no_overload_sweep:
        print("[bench_serving] capacity gate + overload sweep "
              "(simulated clock)")
        cap_row = bench_capacity_gate(params, mcfg, seed=args.seed)
        over_rows = bench_overload_sweep(params, mcfg, seed=args.seed,
                                         loads=overload_loads)
        if not (cap_row["pass"] and overload_gate(over_rows)):
            print("[bench_serving] WARNING: overload gate failed "
                  "(capacity or goodput regression)")

    fleet_block = None
    if not args.smoke and not args.no_fleet:
        print(f"[bench_serving] heterogeneous fleet bench "
              f"(archs={fleet_archs})")
        fmodels = _fleet_models(fleet_archs, args.seed)
        fleet_row = bench_fleet(fmodels, mode="float", seed=args.seed)
        quality = fleet_quality_rows(fmodels, seed=args.seed)
        for q in quality:
            print(f"  quality {q['arch']:20s} rel_err {q['rel_err']:.4f} "
                  f"{'OK' if q['pass'] else 'FAIL'}")
        fleet_block = {"fleet": fleet_row, "quality": quality,
                       "gate_pass": bool(fleet_gate(fleet_row, quality))}
        if not fleet_block["gate_pass"]:
            print("[bench_serving] WARNING: fleet gate failed "
                  "(conservation or quality envelope)")

    gate_ok = (speedups.get("float", 1.0) >= 1.0)
    result = {
        "schema_version": SCHEMA_VERSION,
        "benchmark": "serving_smoke" if args.smoke else "serving_ttft",
        "arch": args.arch, "reduced": True,
        "prompt_len": args.prompt_len, "capacity": args.capacity,
        "max_new": args.max_new, "prefill_chunks": list(chunks),
        "backend": jax.default_backend(),
        "rows": rows, "speedup_ttft": speedups,
        "open_loop": open_rows,
        "mesh_sweep": mesh_rows,
        "fault_sweep": fault_rows,
        "capacity_gate": cap_row,
        "overload_sweep": over_rows,
        "fleet": fleet_block,
    }
    if args.smoke:
        # Machine-readable gate verdict: CI uploads this artifact, so the
        # measured ratio is visible even (especially) when the gate trips.
        result["gate"] = {"pass": bool(gate_ok),
                          "metric": "speedup_ttft.float",
                          "measured": speedups.get("float"),
                          "threshold": 1.0}

    out = args.out
    if out is None:
        root = Path(__file__).resolve().parent.parent
        out = str(root / ("BENCH_serving_smoke.json" if args.smoke
                          else "BENCH_serving.json"))
    Path(out).write_text(json.dumps(result, indent=2) + "\n")
    print(f"[bench_serving] wrote {out}")

    if args.smoke:
        if not gate_ok:
            print(f"[bench_serving] smoke FAIL: chunked prefill slower "
                  f"than prefill-in-decode ({speedups['float']}x < 1.0)")
            sys.exit(1)
        print(f"[bench_serving] smoke OK: chunked {speedups['float']}x "
              f"faster TTFT")


if __name__ == "__main__":
    main()
