"""The serving engine's spans in the JAX profiler's trace, and the prefill
counters they agree with.

A miniature dense engine serves a few requests under
``jax.profiler.start_trace``; the ``.xplane.pb`` it leaves is read back with
``ProfileData``.  Each host thread is one line of the ``/host:CPU`` plane:
the engine's is the line that holds the ``serving.step`` spans.  A wrapper
of the engine's ``_call`` records what each dispatched pass really fed, so
the spans' args are checked against the dispatch itself.
"""

import dataclasses
import gc
import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig
from repro.models import init_params
from repro.serving import Request, ServingEngine
from repro.serving.metrics import GcSpans

PASS_SPANS = ("serving.prefill_pass", "serving.decode_tick")
# Prompts that need a bucket-8 pass, a bucket-4 pass, a decode-tick
# admission (one token) and two chunks of the largest bucket.
PROMPTS = [[3, 5, 7, 9, 11], [2, 4, 6], [13],
           [8, 1, 2, 3, 4, 5, 6, 7, 9, 10, 12, 14]]


@pytest.fixture(scope="module")
def tiny():
    mcfg = smoke_config("smollm-360m")
    return init_params(jax.random.PRNGKey(0), mcfg), mcfg


class Ev:
    """One host event: name, [start, end) in ns, args, thread line."""

    def __init__(self, e, line):
        self.name, self.line = e.name, line
        self.start, self.end = int(e.start_ns), int(e.end_ns)
        self.args = {k: int(v) for k, v in e.stats}

    def within(self, other) -> bool:
        return other.start <= self.start and self.end <= other.end


def _engine(tiny, overlap, quant=QuantConfig(mode="float")):
    params, mcfg = tiny
    if quant.mode != "float":
        mcfg = dataclasses.replace(mcfg, kv_quant=True)
    return ServingEngine(params, mcfg, capacity=3, max_len=48,
                         quant=quant, prefill_chunks=(4, 8),
                         clock=time.perf_counter, overlap=overlap)


def _serve_traced(tiny, overlap, trace_dir, **engine_kw):
    """Serve ``PROMPTS`` (four requests, three slots) under the profiler;
    returns the engine, the passes its ``_call`` dispatched as
    (kind, real tokens fed), and the trace's serving and GC events."""
    eng = _engine(tiny, overlap, **engine_kw)
    eng.warmup()
    dispatched = []
    call = eng._call

    def recording_call(shape_key, args):
        if shape_key[0] == "prefill":
            dispatched.append(("prefill", int(np.sum(args[3]))))
        else:
            dispatched.append(("decode", None))
        return call(shape_key, args)

    eng._call = recording_call
    jax.profiler.start_trace(str(trace_dir))
    try:
        for uid, prompt in enumerate(PROMPTS):
            eng.submit(Request(uid=uid, prompt=list(prompt),
                               max_new_tokens=3))
        eng.drain()
        eng.sync()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
        eng.close()
    path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(path) == 1
    events = []
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                events += [Ev(e, (plane.name, k)) for e in line.events
                           if e.name.startswith("serving.")
                           or e.name == "python.gc"]
    return eng, dispatched, events


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "blocking"])
def test_engine_spans_in_profiler_trace(tiny, tmp_path, overlap):
    eng, dispatched, events = _serve_traced(tiny, overlap, tmp_path)
    by = {}
    for e in events:
        by.setdefault(e.name, []).append(e)
    want = {"serving.admit", "serving.step", "serving.prefill_pass",
            "serving.decode_tick", "serving.launch", "serving.fetch",
            "python.gc"}
    if overlap:
        want |= {"serving.deliver", "serving.stream_wait"}
    assert want <= set(by)
    if not overlap:
        assert "serving.deliver" not in by       # tokens recorded inline

    # The engine thread is the line of the step spans; passes, launches
    # and admissions are all on it.
    engine_line = {e.line for e in by["serving.step"]}
    assert len(engine_line) == 1
    engine_line = engine_line.pop()
    for name in ("serving.admit",) + PASS_SPANS + ("serving.launch",):
        assert {e.line for e in by[name]} == {engine_line}, name
    # Delivery runs on the worker thread when overlapped, inline when not.
    fetch_lines = {e.line for e in by["serving.fetch"]}
    assert (engine_line in fetch_lines) == (not overlap)
    assert len(fetch_lines) == 1

    # Nesting: every pass inside a step, every launch inside the pass it
    # dispatches (same pass_id), admissions outside steps.
    passes = sorted((e for n in PASS_SPANS for e in by[n]),
                    key=lambda e: e.start)
    for p in passes:
        assert any(p.within(s) for s in by["serving.step"])
    for a in by["serving.admit"]:
        assert not any(a.within(s) for s in by["serving.step"])
        assert set(a.args) == {"admitted", "queued"}
    assert sum(a.args["admitted"] for a in by["serving.admit"]) \
        == len(PROMPTS)
    launches = sorted(by["serving.launch"], key=lambda e: e.start)
    assert len(launches) == len(passes)
    for p, la in zip(passes, launches):
        assert la.within(p) and la.args["pass_id"] == p.args["pass_id"]
    if not overlap:
        for f in by["serving.fetch"]:
            assert any(f.within(p) for p in passes)

    # One pass span per dispatched pass, ids in dispatch order.
    assert [p.name for p in passes] == [
        "serving.prefill_pass" if k == "prefill" else "serving.decode_tick"
        for k, _ in dispatched]
    assert [p.args["pass_id"] for p in passes] == list(range(eng.ticks))

    # Prefill args: real tokens as the dispatch fed them; rows are the
    # padded batch (capacity x bucket).
    prefill = sorted(by["serving.prefill_pass"], key=lambda e: e.start)
    assert [p.args["tokens"] for p in prefill] == [
        n for k, n in dispatched if k == "prefill"]
    for p in prefill:
        assert p.args["bucket"] in eng.prefill_chunks
        assert p.args["rows"] == eng.capacity * p.args["bucket"]
        assert 1 <= p.args["live"] <= eng.capacity
        assert p.args["live"] <= p.args["tokens"] <= p.args["rows"]
    for d in by["serving.decode_tick"]:
        assert 1 <= d.args["live"] <= eng.capacity

    # Every fetch names a pass; each overlapped delivery follows the fetch
    # of its own pass, on the same thread.
    ids = {p.args["pass_id"] for p in passes}
    assert {f.args["pass_id"] for f in by["serving.fetch"]} <= ids
    if overlap:
        fetched = {f.args["pass_id"]: f for f in by["serving.fetch"]}
        assert len(fetched) == len(by["serving.fetch"])
        for d in by["serving.deliver"]:
            f = fetched[d.args["pass_id"]]
            assert d.line == f.line and d.start >= f.end
    assert all(set(g.args) == {"generation"} for g in by["python.gc"])

    # The operator's counters agree with the spans.
    pre = eng.metrics.summary()["prefill"]
    rows = sum(p.args["rows"] for p in prefill)
    tokens = sum(p.args["tokens"] for p in prefill)
    assert (pre["rows"], pre["tokens"]) == (rows, tokens)
    assert pre["pad_share"] == pytest.approx(1 - tokens / rows)


@pytest.mark.parametrize("mode", ["abfp_fused", "abfp_packed", "float"])
def test_decode_tick_counts_fused_layers(tiny, tmp_path, mode):
    """``fused_layers`` on every decode tick: the layers whose tick runs
    the fused attention kernel on the int8 cache — all of them in the
    fused mode, none in the packed chain or in float."""
    _, mcfg = tiny
    quant = QuantConfig(mode=mode, tile_width=32, gain=1.0, noise_lsb=0.5)
    _, _, events = _serve_traced(tiny, True, tmp_path, quant=quant)
    ticks = [e for e in events if e.name == "serving.decode_tick"]
    assert ticks
    want = mcfg.num_layers if mode == "abfp_fused" else 0
    assert {e.args["fused_layers"] for e in ticks} == {want}


def test_prefill_counters_without_prefill():
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    assert m.summary()["prefill"] == {"rows": 0, "tokens": 0,
                                      "pad_share": None}
    m.on_prefill(32 * 128, 140)
    m.on_prefill(32 * 16, 40)
    assert m.summary()["prefill"]["pad_share"] == pytest.approx(
        1 - 180 / (32 * 144))
    m.reset()
    assert m.summary()["prefill"]["rows"] == 0


def test_gc_hook_goes_with_close_or_with_the_engine(tiny):
    def hooks():
        return sum(isinstance(cb, GcSpans) for cb in gc.callbacks)

    before = hooks()
    eng = _engine(tiny, overlap=True)
    assert hooks() == before + 1
    eng.close()
    eng.close()                              # idempotent
    assert hooks() == before
    eng = _engine(tiny, overlap=False)       # never closed
    assert hooks() == before + 1
    del eng
    gc.collect()
    assert hooks() == before


def test_gc_spans_pair_start_and_stop():
    hook = GcSpans()
    hook("stop", {"generation": 0})          # no open span: nothing to do
    hook("start", {"generation": 2})
    assert hook._open is not None
    hook("stop", {"generation": 2})
    assert hook._open is None
