"""Serving driver: closed-loop batch or arrival-driven open-loop serving
through the continuous-batching engine, in FLOAT or ABFP (the
AMS-deployment simulation).

Closed loop (historical behavior — admit everything, run to completion):

    PYTHONPATH=src python -m repro.launch.serve --requests 16 --quant abfp

Open loop (Poisson arrivals on the simulated clock, scheduling policy,
SLO metrics):

    PYTHONPATH=src python -m repro.launch.serve --arrival-rate 2.0 \
        --policy sjf --tenants 2

Trace replay: ``--trace FILE`` where FILE is a JSON list of requests,
each ``{"arrival_time": float, "prompt": [ints]}`` or
``{"arrival_time": float, "prompt_len": int}`` plus optional
``max_new_tokens`` / ``priority`` / ``tenant`` / ``temperature``.

Open-loop runs print p50/p99 TTFT, TPOT, and E2E in simulated ticks (one
tick = one jitted pass) plus goodput against ``--slo-ttft``;
``--metrics-out`` dumps the full percentile summary as JSON
(see ``repro.serving.metrics``).

Sharded serving: ``--mesh dp,tp`` builds a (data, model) device mesh and
runs the engine tensor-parallel (column-parallel weights over 'model',
slot state over 'data').  When the host exposes fewer than dp*tp devices
the driver forces placeholder CPU devices via
``--xla_force_host_platform_device_count`` BEFORE first jax use, so the
whole path runs on CPU CI:

    PYTHONPATH=src python -m repro.launch.serve --mesh 2,4 --quant abfp-packed

Greedy decode under any mesh shape emits bit-identical tokens to the
single-device engine for the same seed (tests/test_sharded_serving.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config, list_archs, smoke_config
from repro.core.abfp import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import frontends, init_params, param_count
from repro.serving import FaultConfig, Request, ServingEngine
from repro.serving.runners import EncDecRunner, runner_for


class Served(NamedTuple):
    """What one ``main`` call served: the engine (its params, counters and
    compiled steps), the finished requests, and the wall seconds spent
    compiling before traffic and serving it."""

    engine: ServingEngine
    requests: List[Request]
    compile_s: float
    serve_s: float


def parse_mesh(arg: Optional[str]) -> Optional[Tuple[int, int]]:
    """'dp,tp' -> (dp, tp); None passes through (single-device engine)."""
    if arg is None:
        return None
    try:
        dp, tp = (int(v) for v in arg.split(","))
    except ValueError:
        raise SystemExit(f"--mesh expects 'dp,tp' (got {arg!r})")
    if dp < 1 or tp < 1:
        raise SystemExit(f"--mesh axes must be >= 1 (got {arg!r})")
    return dp, tp


def force_host_devices(n: int) -> None:
    """Ensure >= n CPU devices exist, forcing placeholders if needed.

    Must run BEFORE anything initializes the jax backend: XLA reads
    ``--xla_force_host_platform_device_count`` exactly once, at first use.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def resolve_archs(args) -> List[str]:
    """Validated arch list: ``--archs a,b,c`` (fleet) or ``--arch`` (single).
    Unknown names fail FAST with the registry listed — before any params
    are initialized or jax warms up."""
    names = ([a.strip() for a in args.archs.split(",") if a.strip()]
             if args.archs else [args.arch])
    known = sorted(list_archs())
    bad = [a for a in names if a not in known]
    if bad or not names:
        what = f"unknown arch(es) {bad}" if bad else "no archs given"
        raise SystemExit(
            f"[serve] {what}; registered archs: {', '.join(known)}")
    return names


def parse_model_split(arg: Optional[str]) -> Optional[dict]:
    """'name=slots,name=slots' -> {name: slots}; None passes through."""
    if arg is None:
        return None
    out = {}
    for part in arg.split(","):
        if not part.strip():
            continue
        try:
            name, slots = part.split("=")
            out[name.strip()] = int(slots)
        except ValueError:
            raise SystemExit(
                f"--model-split expects 'name=slots,...' (got {arg!r})")
    return out or None


def attach_features(reqs: List[Request], runners: dict, seed: int) -> None:
    """Stub frontend features for requests routed to enc-dec lanes: each
    request gets its own deterministic (enc_len, d_model) audio-frame
    embedding keyed by (seed, uid)."""
    for r in reqs:
        runner = runners.get(r.model)
        if not isinstance(runner, EncDecRunner):
            continue
        key = jax.random.fold_in(jax.random.PRNGKey(seed), r.uid)
        r.features = np.asarray(
            frontends.audio_stub_features(
                key, 1, runner.enc_len, runner.mcfg.d_model)[0],
            np.float32)


def poisson_workload(mcfg, args, rng: np.random.Generator) -> List[Request]:
    """Mixed-tenant Poisson arrivals: exponential inter-arrival gaps at
    ``--arrival-rate`` requests per simulated tick, prompt lengths drawn
    uniformly from [1, 2 * --prompt-len - 1]."""
    gaps = rng.exponential(1.0 / args.arrival_rate, args.requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(1, max(2, 2 * args.prompt_len)))
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(1, mcfg.vocab_size, plen).tolist(),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
            arrival_time=float(arrivals[i]),
            priority=int(rng.integers(0, 3)),
            tenant=f"t{int(rng.integers(args.tenants))}"))
    return reqs


def trace_workload(mcfg, args, rng: np.random.Generator) -> List[Request]:
    entries = json.loads(open(args.trace).read())
    reqs = []
    for i, e in enumerate(entries):
        prompt = e.get("prompt")
        if prompt is None:
            plen = int(e.get("prompt_len", args.prompt_len))
            prompt = rng.integers(1, mcfg.vocab_size, plen).tolist()
        reqs.append(Request(
            uid=i, prompt=list(prompt),
            max_new_tokens=int(e.get("max_new_tokens", args.max_new)),
            temperature=float(e.get("temperature", args.temperature)),
            arrival_time=float(e.get("arrival_time", 0.0)),
            priority=int(e.get("priority", 0)),
            tenant=str(e.get("tenant", "default"))))
    return reqs


def serve_fleet(built: dict, quant: QuantConfig, mesh, args) -> Served:
    """Multi-model fleet serving: one lane per ``--archs`` entry on a
    shared clock, requests routed round-robin across models (enc-dec lanes
    get stub frontend features per request)."""
    runners = {name: runner_for(cfg) for name, (_, cfg) in built.items()}
    eng = ServingEngine(
        models={name: (p, cfg, runners[name])
                for name, (p, cfg) in built.items()},
        capacity=args.capacity,
        model_split=parse_model_split(args.model_split),
        max_len=args.max_len, quant=quant, seed=args.seed,
        chunked=not args.no_chunked, policy=args.policy,
        prefill_chunks=tuple(int(c) for c in args.prefill_chunks.split(",")),
        mesh=mesh, paged=args.paged, page_size=args.page_size,
        pool_pages=args.pool_pages, prefix_cache=not args.no_prefix_cache)
    lanes = {n: l.capacity for n, l in eng.lanes.items()}
    print(f"[serve] fleet: {len(built)} models, slots {lanes}, "
          f"quant={args.quant}, policy={args.policy}")

    rng = np.random.default_rng(args.seed)
    names = list(built)
    if args.arrival_rate is not None or args.trace is not None:
        reqs = (trace_workload(built[names[0]][1], args, rng) if args.trace
                else poisson_workload(built[names[0]][1], args, rng))
    else:
        reqs = [Request(uid=i,
                        prompt=rng.integers(
                            1, built[names[0]][1].vocab_size,
                            args.prompt_len).tolist(),
                        max_new_tokens=args.max_new,
                        temperature=args.temperature)
                for i in range(args.requests)]
    for i, r in enumerate(reqs):
        r.model = names[i % len(names)]
        # Prompts must fit every lane's vocab (smallest wins).
        vmax = built[r.model][1].vocab_size
        r.prompt = [t % (vmax - 1) + 1 for t in r.prompt]
    attach_features(reqs, runners, args.seed)

    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.generated) for r in done)
    print(f"[serve] fleet: {len(done)} requests, {tokens} tokens in "
          f"{dt:.1f}s ({tokens / max(dt, 1e-9):.1f} tok/s, "
          f"{eng.ticks} ticks)")

    def fmt(d, key):
        v = d[key]
        return "-" if v is None else f"{v:.2f}"

    summaries = eng.summary()
    cons = eng.conservation()
    for name in names:
        s, c = summaries[name], cons[name]
        print(f"  {name}: TTFT p50 {fmt(s['ttft'], 'p50')} / "
              f"p99 {fmt(s['ttft'], 'p99')} | TPOT p50 "
              f"{fmt(s['tpot'], 'p50')} | completed "
              f"{c['completed']}/{c['submitted']} "
              f"(conservation_ok {c['ok']})")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"fleet": {n: summaries[n] for n in names},
                       "conservation": cons}, f, indent=2, default=str)
        print(f"[serve] wrote {args.metrics_out}")
    return Served(eng, done, 0.0, dt)


def main(argv: Optional[Sequence[str]] = None) -> Served:
    """Serve from the command line (``argv``; ``sys.argv`` when None) and
    return what was served."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    help="model architecture (see repro.configs.list_archs)")
    ap.add_argument("--archs", default=None,
                    help="comma-separated arch list — serve a MULTI-MODEL "
                         "FLEET (one lane per arch, multiplexed on a shared "
                         "clock; requests route round-robin across models)")
    ap.add_argument("--model-split", default=None,
                    help="'name=slots,...' per-model slot overrides for "
                         "--archs (remaining capacity splits near-equally)")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced (smoke) shapes — the default")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full-size architecture config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--quant",
                    choices=("float", "abfp", "abfp-kernel", "abfp-packed"),
                    default="float",
                    help="abfp: pure-jnp scan; abfp-kernel: fused Pallas; "
                         "abfp-packed: weights quantized once at init, "
                         "packed Pallas kernel per tick")
    ap.add_argument("--fused", action="store_true",
                    help="abfp_fused serving: packed weights carry per-tile "
                         "ADC gains (capped by --gain) and decode ticks run "
                         "the fused QKV + quantized-KV-attention kernels "
                         "(kernels.abfp_decode_fused); overrides --quant "
                         "and serves with a quantized (int8) KV cache")
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--gain", type=float, default=8.0,
                    help="ADC gain G (paper Sec. IV): scalar output "
                         "amplification in abfp modes; with --fused, the "
                         "per-tile adaptive gain cap (gains are "
                         "powers of two in [1, G] chosen per weight tile)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-chunked", action="store_true",
                    help="legacy prefill-in-decode: one prompt token per "
                         "decode tick instead of bucketed prefill chunks")
    ap.add_argument("--prefill-chunks", default="16,64,128",
                    help="comma-separated chunk buckets for prefill passes "
                         "(one jit compile each)")
    # Open-loop serving (arrival-driven; omit both for the closed loop).
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrival rate in requests per simulated "
                         "tick; enables the open-loop submit/poll path")
    ap.add_argument("--trace", default=None,
                    help="JSON trace of requests to replay (see module "
                         "docstring for the schema)")
    ap.add_argument("--policy", choices=("fcfs", "sjf", "priority"),
                    default="fcfs", help="admission scheduling policy")
    ap.add_argument("--tenants", type=int, default=2,
                    help="number of synthetic tenants for Poisson workloads")
    ap.add_argument("--slo-ttft", type=float, default=8.0,
                    help="TTFT SLO in simulated ticks (goodput threshold)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the percentile metrics summary JSON here")
    ap.add_argument("--mesh", default=None,
                    help="dp,tp — serve tensor-parallel on a (data, model) "
                         "mesh; placeholder CPU devices are forced when the "
                         "host has fewer than dp*tp (CPU-CI friendly)")
    # Fault injection / SLO-aware recovery (repro.serving.faults).
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="per-tick fault probability; enables seeded "
                         "injection into the served weights")
    ap.add_argument("--fault-kinds", default="stuck_col,scale_drift,"
                                             "shard_drop",
                    help="comma-separated subset of "
                         "stuck_col/scale_drift/shard_drop")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault trace")
    ap.add_argument("--no-recovery", action="store_true",
                    help="inject but do not detect/repair (degraded-mode "
                         "baseline for the goodput comparison)")
    ap.add_argument("--detect-every", type=int, default=4,
                    help="fingerprint-probe cadence in engine ticks")
    # Paged KV pool + overload robustness (repro.serving.pages).
    ap.add_argument("--paged", action="store_true",
                    help="serve from a paged KV pool (fixed pages aligned "
                         "to the ABFP tile, slot->page-table indirection, "
                         "copy-on-write prefix sharing) instead of "
                         "per-slot max_len strips")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: the quant tile "
                         "width, or min(16, max_len) in float mode)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="total pages in the shared pool (default: "
                         "capacity * ceil(max_len / page_size) — the "
                         "unpaged footprint)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request prefix page sharing")
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable evict-to-pool preemption under page "
                         "saturation (victims then wait instead)")
    ap.add_argument("--queue-watermark", type=int, default=None,
                    help="shed newly arrived requests once the arrived "
                         "queue depth reaches this (backpressure; shed "
                         "requests carry a retry_after hint)")
    ap.add_argument("--page-watermarks", default="0.85,0.5",
                    help="hi,lo pool-pressure fractions: degraded mode "
                         "enters at hi and exits at lo (hysteresis)")
    ap.add_argument("--degraded-max-new", type=int, default=None,
                    help="cap max_new_tokens for admissions made while "
                         "degraded (graceful degradation)")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="max pool pages a single tenant may hold "
                         "(projected footprint; noisy-neighbor isolation)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in ticks after arrival; "
                         "expired requests are cancelled and counted "
                         "timed_out")
    # Overlapped wall-clock serving (repro.serving.stream).
    ap.add_argument("--wall-clock", action="store_true",
                    help="drive the engine on time.perf_counter instead of "
                         "the simulated tick clock (latencies/SLOs are then "
                         "in SECONDS)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped dispatch pipeline: sample on device, "
                         "keep tokens unfetched, dispatch tick N+1 before "
                         "tick N's transfer resolves, deliver tokens from a "
                         "background worker; implies --wall-clock")
    ap.add_argument("--inflight", type=int, default=4,
                    help="dispatch-ahead depth for --overlap (bound on "
                         "submitted-but-undelivered passes)")
    args = ap.parse_args(argv)
    if args.overlap:
        args.wall_clock = True

    mesh_shape = parse_mesh(args.mesh)
    mesh = None
    if mesh_shape is not None:
        force_host_devices(mesh_shape[0] * mesh_shape[1])
        if len(jax.devices()) < mesh_shape[0] * mesh_shape[1]:
            raise SystemExit(
                f"--mesh {args.mesh}: needs {mesh_shape[0] * mesh_shape[1]} "
                f"devices but jax was already initialized with "
                f"{len(jax.devices())}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count yourself")
        mesh = make_mesh(mesh_shape, ("data", "model"))
    enable_compile_cache()

    archs = resolve_archs(args)
    built = {}
    for a in archs:
        cfg = smoke_config(a) if args.reduced else get_config(a)
        if args.fused:
            # The fused decode kernels attend over the int8 quantized KV
            # cache; --fused therefore serves with kv_quant on.
            cfg = dataclasses.replace(cfg, kv_quant=True)
        built[a] = (init_params(jax.random.PRNGKey(args.seed), cfg), cfg)
    mcfg = built[archs[0]][1]
    params = built[archs[0]][0]
    mode = {"float": "float", "abfp": "abfp_ref",
            "abfp-kernel": "abfp_kernel",
            "abfp-packed": "abfp_packed"}[args.quant]
    if args.fused:
        # --fused is an ABFP serving mode; with the (default) float quant
        # it upgrades to the packed config, otherwise it refines whatever
        # ABFP variant was asked for.
        mode = "abfp_fused"
        args.quant = "abfp-fused"
    quant = (QuantConfig(mode=mode, tile_width=args.tile,
                         gain=args.gain, noise_lsb=0.5)
             if mode != "float" else QuantConfig(mode="float"))

    if args.archs is not None:
        if args.fault_rate is not None:
            raise SystemExit("[serve] --archs (fleet mode) does not "
                             "compose with fault injection flags yet")
        return serve_fleet(built, quant, mesh, args)

    mesh_note = (f", mesh=({mesh_shape[0]}x{mesh_shape[1]} data x model)"
                 if mesh is not None else "")
    print(f"[serve] {args.arch}: {param_count(params)/1e6:.1f}M params, "
          f"quant={args.quant}, policy={args.policy}{mesh_note}")
    faults = None
    if args.fault_rate is not None:
        faults = FaultConfig(
            rate=args.fault_rate,
            kinds=tuple(k for k in args.fault_kinds.split(",") if k),
            seed=args.fault_seed)
        print(f"[serve] fault injection: rate={args.fault_rate}/tick, "
              f"kinds={args.fault_kinds}, seed={args.fault_seed}, "
              f"recovery={'off' if args.no_recovery else 'on'}")
    try:
        wm_hi, wm_lo = (float(v) for v in args.page_watermarks.split(","))
    except ValueError:
        raise SystemExit(f"--page-watermarks expects 'hi,lo' "
                         f"(got {args.page_watermarks!r})")
    if args.paged:
        print(f"[serve] paged KV pool: page_size="
              f"{args.page_size or 'auto'}, pool_pages="
              f"{args.pool_pages or 'auto'}, prefix_cache="
              f"{not args.no_prefix_cache}, preemption="
              f"{not args.no_preemption}, watermarks=({wm_hi}, {wm_lo})")
    if args.wall_clock:
        unit = "s"
        print(f"[serve] wall clock: overlap="
              f"{'on' if args.overlap else 'off (blocking)'}"
              + (f", inflight={args.inflight}" if args.overlap else ""))
    else:
        unit = "ticks"
    eng = ServingEngine(params, mcfg, capacity=args.capacity,
                        max_len=args.max_len, quant=quant, seed=args.seed,
                        chunked=not args.no_chunked,
                        policy=args.policy,
                        prefill_chunks=tuple(
                            int(c) for c in args.prefill_chunks.split(",")),
                        mesh=mesh,
                        faults=faults,
                        recovery=not args.no_recovery,
                        detect_every=args.detect_every,
                        paged=args.paged,
                        page_size=args.page_size,
                        pool_pages=args.pool_pages,
                        prefix_cache=not args.no_prefix_cache,
                        preemption=(False if args.no_preemption else None),
                        queue_watermark=args.queue_watermark,
                        page_watermarks=(wm_hi, wm_lo),
                        degraded_max_new=args.degraded_max_new,
                        tenant_quota=args.tenant_quota,
                        clock=time.perf_counter if args.wall_clock else None,
                        overlap=args.overlap,
                        inflight=args.inflight)
    t0 = time.perf_counter()
    eng.warmup()            # no compile inside the measured serve window
    compile_s = time.perf_counter() - t0
    print(f"[serve] compiled the decode step and "
          f"{len(eng.prefill_chunks) if eng.chunked else 0} prefill "
          f"bucket(s) in {compile_s:.1f}s")
    rng = np.random.default_rng(args.seed)

    open_loop = args.arrival_rate is not None or args.trace is not None
    if open_loop:
        reqs = (trace_workload(mcfg, args, rng) if args.trace
                else poisson_workload(mcfg, args, rng))
        if args.wall_clock:
            # Workload arrivals are relative offsets; the wall clock reads
            # an arbitrary epoch, so rebase them onto "now".
            base = time.perf_counter()
            for r in reqs:
                r.arrival_time = base + (r.arrival_time or 0.0)
        if args.deadline is not None:
            for r in reqs:
                r.deadline = (r.arrival_time or 0.0) + args.deadline
        for r in reqs:
            eng.submit(r)
        span = (max(r.arrival_time for r in reqs)
                - min(r.arrival_time for r in reqs)) if reqs else 0.0
        print(f"[serve] open-loop: {len(reqs)} requests arriving over "
              f"{span:.1f} {unit}, {args.tenants} tenants")
        t0 = time.perf_counter()
        done = eng.drain()
        dt = time.perf_counter() - t0
    else:
        reqs = [Request(uid=i,
                        prompt=rng.integers(1, mcfg.vocab_size,
                                            args.prompt_len).tolist(),
                        max_new_tokens=args.max_new,
                        temperature=args.temperature)
                for i in range(args.requests)]
        t0 = time.perf_counter()
        done = eng.run(reqs)
        dt = time.perf_counter() - t0

    tokens = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)} requests, {tokens} tokens in {dt:.1f}s "
          f"({tokens/dt:.1f} tok/s, {eng.ticks} ticks)")

    s = eng.metrics.summary()
    ttft, tpot, e2e = s["ttft"], s["tpot"], s["e2e"]

    def fmt(d, key):
        v = d[key]
        return "-" if v is None else f"{v:.2f}"

    print(f"[serve] TTFT p50 {fmt(ttft, 'p50')} / p99 {fmt(ttft, 'p99')} "
          f"{unit} | TPOT p50 {fmt(tpot, 'p50')} / p99 {fmt(tpot, 'p99')} "
          f"{unit} | E2E p50 {fmt(e2e, 'p50')} / p99 {fmt(e2e, 'p99')} "
          f"{unit}")
    good = eng.metrics.goodput(args.slo_ttft)
    util = s["utilization"]["mean"]
    print(f"[serve] goodput {good if good is None else round(good, 3)} "
          f"req/{unit.rstrip('s') or 's'} (TTFT<={args.slo_ttft}), "
          f"slot utilization "
          f"{'-' if util is None else f'{util:.0%}'}, max queue depth "
          f"{s['queue_depth']['max']}")
    pre = s["prefill"]
    if pre["rows"]:
        print(f"[serve] prefill: {pre['tokens']} tokens in {pre['rows']} "
              f"rows ({pre['pad_share']:.1%} padding)")
    if args.wall_clock:
        tu = s["tick_utilization"]
        tv = tu["value"]
        print(f"[serve] tick utilization "
              f"{'-' if tv is None else f'{tv:.1%}'} "
              f"(device busy {tu['device_busy_s']:.2f}s of "
              f"{tu['active_s']:.2f}s active)")
        eng.close()
    req_s = s["requests"]
    if args.fault_rate is not None or args.deadline is not None:
        f = s["faults"]
        cons = eng.metrics.conservation()
        print(f"[serve] faults: {f['injected']} injected "
              f"({f['injected_stuck_col']} stuck_col, "
              f"{f['injected_scale_drift']} scale_drift, "
              f"{f['injected_shard_drop']} shard_drop), "
              f"{f['detected']} detected, {f['cols_remapped']} cols "
              f"remapped, {f['tiles_requantized']} tiles requantized, "
              f"{f['reshards']} reshards")
        print(f"[serve] timed_out {req_s['timed_out']}, requeued "
              f"{req_s['requeued']}, corrupted {req_s['corrupted']}, "
              f"conservation_ok {cons['ok']}")
    if args.paged:
        pool = s["pool"]
        cons = eng.metrics.conservation()
        print(f"[serve] pool: pressure mean {pool['pressure_mean']:.2f} / "
              f"max {pool['pressure_max']:.2f}, prefix hits "
              f"{pool['prefix_hits']}, cow copies {pool['cow_copies']}, "
              f"degraded ticks {pool['degraded_ticks']}")
        print(f"[serve] overload: shed {req_s['shed']}, preempted "
              f"{req_s['preempted']}, resumed {req_s['resumed']}, "
              f"preempt_ok {cons['preempt_ok']}")
    if args.metrics_out:
        eng.metrics.to_json(args.metrics_out, policy=args.policy,
                            quant=args.quant,
                            slo_ttft=args.slo_ttft,
                            goodput_per_tick=good)
        print(f"[serve] wrote {args.metrics_out}")
    for r in done[:3]:
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.generated}")
    return Served(eng, done, compile_s, dt)


if __name__ == "__main__":
    main()
