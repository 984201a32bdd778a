"""Overlapped async serving runtime: parity, sync-bug regressions, and
the DeviceStream seam.

Two engine configurations must emit IDENTICAL token streams for greedy
same-seed workloads:

  * the simulated-clock BLOCKING engine (the parity reference every other
    suite gates on), and
  * the wall-clock OVERLAPPED engine (``overlap=True`` + a real clock):
    on-device sampling, unfetched device arrays, dispatch-ahead over a
    bounded delivery queue.

Tokens are sampled inside the jitted pass either way (greedy argmax ties
break first-occurrence, matching ``np.argmax``), so equality is exact in
float mode and bit-identical (seeded ADC noise included) for the ABFP
modes.  The three tick-loop sync bugfixes carry failing-test-first
regressions here:

  1. ``_prefill_pass`` host-synced logits even when every live slot was
     mid-prompt (no recipient) — the fetch is now skipped entirely.
  2. ``StragglerMonitor.observe`` was fed first-execution-per-shape
     dispatch overhead (compile + warmup), escalating on a cold prefill
     bucket mid-trace — first runs are now tagged and excluded.
  3. The idle nap in ``poll()`` returned with ``self.now`` stale from
     before ``time.sleep``, so the next ``submit`` stamped arrivals in
     the past and overstated queue delay — the clock is re-synced after
     the nap.

Every test here is timing-assertion-free (fake clocks only): the
``async`` lane (``make test-async``) must pass on any host, loaded or
not.  Wall-clock THROUGHPUT is benchmarked, not tested — see
``benchmarks/bench_serving.py --utilization-gate``.
"""

import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.distributed.fault import StragglerMonitor
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.serving import (
    DeviceStream,
    OverlappedStream,
    Request,
    ServingEngine,
)
from repro.serving.faults import FaultConfig

pytestmark = [getattr(pytest.mark, "async")]

FLOAT = QuantConfig(mode="float")
PACKED = QuantConfig(mode="abfp_packed", tile_width=32, gain=4.0,
                     noise_lsb=0.5)
FUSED = QuantConfig(mode="abfp_fused", tile_width=32, gain=4.0,
                    noise_lsb=0.5)

# Prompts straddle the (4, 8) prefill buckets plus a single-token prompt
# (decode-tick admission path), same shape family as the sharded suite.
PROMPTS = [[3, 5, 7, 9, 11], [2, 4, 6], [8, 1, 2, 3, 4, 5, 6, 7, 9], [13]]


@pytest.fixture(scope="module")
def tiny():
    mcfg = smoke_config("smollm-360m")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    return params, mcfg


@pytest.fixture(scope="module")
def tinyllama():
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    return params, mcfg


def _reqs(n=4, *, prompts=None, max_new=4, temp=0.0, arrival=0.0):
    prompts = prompts if prompts is not None else PROMPTS[:n]
    return [Request(uid=i, prompt=list(p), max_new_tokens=max_new,
                    temperature=temp, arrival_time=arrival)
            for i, p in enumerate(prompts)]


def _outs(done):
    return {r.uid: tuple(r.generated) for r in done}


def _serve_pair(params, mcfg, quant, *, mesh=None, reqs=None, **ekw):
    """Run the same workload through the simulated blocking engine and the
    wall-clock overlapped engine; return (reference, overlapped) outputs
    plus the overlapped engine for extra assertions."""
    kw = dict(capacity=4, max_len=64, quant=quant, seed=0,
              prefill_chunks=(4, 8), mesh=mesh, **ekw)
    ref_eng = ServingEngine(params, mcfg, **kw)
    ref = _outs(ref_eng.run(reqs() if reqs else _reqs()))
    ov_eng = ServingEngine(params, mcfg, clock=time.perf_counter,
                           overlap=True, **kw)
    ov_eng.warmup()
    got = _outs(ov_eng.run(reqs() if reqs else _reqs()))
    ov_eng.close()
    return ref, got, ov_eng


# ---------------------------------------------------------------------------
# Tentpole: overlapped wall-clock == simulated blocking, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [FLOAT, PACKED, FUSED],
                         ids=["float", "abfp_packed", "abfp_fused"])
def test_overlap_parity_single_device(tinyllama, quant):
    params, mcfg = tinyllama
    mcfg = (dataclasses.replace(mcfg, kv_quant=True)
            if quant.mode == "abfp_fused" else mcfg)
    ref, got, eng = _serve_pair(params, mcfg, quant)
    assert got == ref
    assert eng.metrics.conservation()["ok"]


@pytest.mark.dist
@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8 / make test-dist)")
@pytest.mark.parametrize("quant", [FLOAT, PACKED],
                         ids=["float", "abfp_packed"])
def test_overlap_parity_mesh_2x4(tinyllama, quant):
    """The overlapped pipeline under the full (dp, tp) = (2, 4) mesh emits
    the same tokens as the simulated blocking engine on the same mesh."""
    params, mcfg = tinyllama
    mesh = make_mesh((2, 4), ("data", "model"))
    ref, got, _ = _serve_pair(params, mcfg, quant, mesh=mesh)
    assert got == ref


def test_overlap_parity_preemption_resume(tiny):
    """A page pool tight enough to force preemptions: the overlapped
    engine preempts, replays, and resumes to the same streams the
    simulated blocking engine produces (count-based slot completion frees
    slots at dispatch, but preemption syncs in-flight passes first)."""
    params, mcfg = tiny
    reqs = lambda: [Request(uid=i, prompt=[(7 * i + j) % 97 + 1
                                           for j in range(20)],
                            max_new_tokens=8, arrival_time=0.0)
                    for i in range(8)]
    kw = dict(paged=True, page_size=16, pool_pages=6, reqs=reqs)
    ref, got, eng = _serve_pair(params, mcfg, FLOAT, **kw)
    cons = eng.metrics.conservation()
    assert cons["preempted"] > 0            # the pool actually saturated
    assert cons["ok"] and cons["preempt_ok"]
    assert got == ref


def test_overlap_parity_fault_recovery(tiny):
    """A fault plan injecting + recovering mid-trace: detection rounds run
    on tick cadence (clock-independent), recovery syncs the pipeline, and
    the requeued re-executions land on the same streams."""
    params, mcfg = tiny
    kw = dict(faults=FaultConfig(rate=0.05, seed=3, horizon=64),
              recovery=True, detect_every=2)
    ref, got, eng = _serve_pair(params, mcfg, PACKED, **kw)
    assert got == ref
    assert eng.metrics.conservation()["ok"]


def test_overlap_temperature_reproducible(tiny):
    """Temperature sampling on the overlapped path draws from the
    on-device seeded stream keyed (seed, uid, token_idx): two runs with
    the same engine seed match exactly; temp=0 slots stay greedy."""
    params, mcfg = tiny

    def run_once():
        eng = ServingEngine(params, mcfg, capacity=4, max_len=64, seed=11,
                            prefill_chunks=(4, 8),
                            clock=time.perf_counter, overlap=True)
        done = eng.run(_reqs(max_new=6, temp=0.8))
        out = _outs(done)
        eng.close()
        return out

    a, b = run_once(), run_once()
    assert a == b
    greedy = ServingEngine(params, mcfg, capacity=4, max_len=64, seed=11,
                           prefill_chunks=(4, 8),
                           clock=time.perf_counter, overlap=True)
    g = _outs(greedy.run(_reqs(max_new=6, temp=0.0)))
    greedy.close()
    assert any(a[u] != g[u] for u in a)     # temperature actually sampled


def test_overlap_streaming_callbacks_in_order(tiny):
    """on_token callbacks fire from the delivery worker in dispatch order
    per request, and every token is delivered exactly once."""
    params, mcfg = tiny
    seen = {}
    reqs = _reqs(max_new=5)
    for r in reqs:
        r.on_token = lambda req, tok: seen.setdefault(req.uid,
                                                      []).append(tok)
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, seed=0,
                        prefill_chunks=(4, 8),
                        clock=time.perf_counter, overlap=True)
    done = eng.run(reqs)
    eng.close()
    assert {u: tuple(t) for u, t in seen.items()} == _outs(done)


def test_overlap_worker_exception_surfaces(tiny):
    """A failing streaming callback on the delivery worker re-raises on
    the engine thread instead of dying silently on the daemon."""
    params, mcfg = tiny
    req = Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4,
                  arrival_time=0.0)
    req.on_token = lambda r, t: (_ for _ in ()).throw(RuntimeError("boom"))
    eng = ServingEngine(params, mcfg, capacity=1, max_len=32, seed=0,
                        clock=time.perf_counter, overlap=True)
    eng.submit(req)
    with pytest.raises(RuntimeError, match="boom"):
        eng.drain()
    eng._stream._exc = None      # don't re-raise during close
    eng.close()


# ---------------------------------------------------------------------------
# Bugfix 1: no host sync when every live slot is mid-prompt
# ---------------------------------------------------------------------------

def test_midprompt_prefill_pass_does_not_host_sync(tiny):
    """prompt=20 tokens through chunk-4 buckets is 5 prefill passes; only
    the LAST produces a token anyone records.  The blocking engine must
    fetch logits exactly once per recorded token — mid-prompt passes
    perform ZERO device->host transfers — and the streams are unchanged."""
    params, mcfg = tiny
    prompt = [(3 * j) % 97 + 1 for j in range(20)]
    max_new = 3

    def run(**ekw):
        eng = ServingEngine(params, mcfg, capacity=1, max_len=64, seed=0,
                            prefill_chunks=(4,), **ekw)
        done = eng.run([Request(uid=0, prompt=list(prompt),
                                max_new_tokens=max_new, arrival_time=0.0)])
        return eng, _outs(done)

    eng, out = run()
    assert isinstance(eng._stream, DeviceStream)
    # 5 chunk passes: 4 mid-prompt (no sync) + 1 completing (first token),
    # then max_new - 1 decode ticks -> exactly max_new fetches total.
    assert eng._stream.host_syncs == max_new
    assert len(out[0]) == max_new


# ---------------------------------------------------------------------------
# Bugfix 2: straggler monitor ignores first-execution-per-shape overhead
# ---------------------------------------------------------------------------

class _SpyMonitor(StragglerMonitor):
    def __init__(self):
        super().__init__()
        self.samples = []

    def observe(self, step_time):
        self.samples.append(step_time)
        super().observe(step_time)


def test_straggler_excludes_fresh_bucket_warmup(tiny):
    """Force a FRESH prefill bucket mid-trace (a long prompt arrives after
    the engine has only ever compiled the small bucket) on a fake perf
    clock where every first-execution-per-shape costs +99s inside the
    timed region.  The monitor must see only steady-state samples: no
    escalation, no flagged steps."""
    params, mcfg = tiny
    eng = ServingEngine(params, mcfg, capacity=1, max_len=64, seed=0,
                        prefill_chunks=(4, 8))
    spy = _SpyMonitor()
    eng.straggler = spy
    eng.metrics.straggler = spy

    t = [0.0]

    def fake_perf():
        t[0] += 0.0005
        return t[0]

    eng._perf = fake_perf
    orig = eng._executable

    def slow_first_run(shape_key, args):
        fn, warm = orig(shape_key, args)
        if warm:
            t[0] += 99.0        # first dispatch of this shape: huge
        return fn, warm

    eng._executable = slow_first_run

    # Request A exercises bucket 4 + the decode shape (>= 5 steady
    # samples); request B then forces the never-seen bucket 8 mid-trace.
    reqs = [Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=8,
                    arrival_time=0.0),
            Request(uid=1, prompt=[5, 6, 7, 8, 9, 10, 11], max_new_tokens=4,
                    arrival_time=0.0)]
    done = eng.run(reqs)
    assert len(done) == 2
    assert {("decode",), ("prefill", 4), ("prefill", 8)} <= eng._warmed_shapes
    assert spy.samples, "steady-state passes must still feed the monitor"
    assert all(dt < 1.0 for dt in spy.samples), spy.samples
    assert spy.flagged == 0


# ---------------------------------------------------------------------------
# Bugfix 3: poll() re-syncs the clock after the idle nap
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_poll_resyncs_clock_after_idle_nap(tiny, monkeypatch):
    """An idle wall-clock poll() naps toward the next arrival.  The nap
    really advances the clock, so ``self.now`` must be re-read afterwards:
    a submit landing right after the poll would otherwise be stamped with
    a pre-sleep arrival and overstate its queue delay by the nap length."""
    import repro.serving.engine as engine_mod
    params, mcfg = tiny
    clk = _FakeClock()
    slept = []

    def fake_sleep(dt):
        slept.append(dt)
        clk.t += dt

    monkeypatch.setattr(engine_mod.time, "sleep", fake_sleep)
    eng = ServingEngine(params, mcfg, capacity=1, max_len=32, seed=0,
                        clock=clk)
    # One future arrival keeps the engine idle-but-not-drained.
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=1,
                       arrival_time=0.5))
    out = eng.poll()
    assert out == [] and slept, "poll must nap toward the future arrival"
    assert eng.now == clk.t     # THE fix: clock re-synced after the nap
    # A submission right after the nap is stamped at the post-sleep time.
    eng.submit(Request(uid=1, prompt=[4, 5], max_new_tokens=1))
    assert eng.metrics.requests[1].arrival_time == clk.t


# ---------------------------------------------------------------------------
# DeviceStream seam + utilization gauge unit behavior
# ---------------------------------------------------------------------------

def test_overlapped_stream_bounded_and_drains():
    class Eng:
        def __init__(self):
            self.seen = []

        def _deliver_ticket(self, ticket):
            self.seen.append(ticket.now)

    from repro.serving.stream import Ticket
    e = Eng()
    s = OverlappedStream(depth=2)
    for k in range(5):
        s.submit(Ticket(engine=e, t0=0.0, warmup=False, sampled=None,
                        recs=[], now=float(k)))
    s.sync()
    assert e.seen == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert s.pending() == 0
    s.close()
    with pytest.raises(RuntimeError):
        s.submit(Ticket(engine=e, t0=0.0, warmup=False, sampled=None,
                        recs=[], now=9.0))


def test_device_span_union_and_windows():
    """tick_utilization merges overlapping spans (counted once) and only
    measures inside open windows — fully idle gaps don't dilute it."""
    from repro.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.window_open(0.0)
    m.on_device_span(0.0, 1.0)
    m.on_device_span(0.5, 2.0)      # overlaps: union adds only [1, 2]
    m.on_device_span(3.0, 4.0)      # gap [2, 3] is host-idle inside window
    m.window_close(4.0)
    m.window_open(10.0)             # idle [4, 10] never counted
    m.on_device_span(10.0, 11.0)
    m.window_close(11.0)
    u = m.tick_utilization()
    assert u["device_busy_s"] == pytest.approx(4.0)
    assert u["active_s"] == pytest.approx(5.0)
    assert u["value"] == pytest.approx(0.8)
