"""The reduction from a profiler trace to busy time, time per operation
and idle gaps credited to the harness's spans: on synthetic intervals,
and on a trace of the SmolLM cell recorded on a TPU v5e, trimmed to two
decode steps by ``data/trim_xplane.py``."""

from pathlib import Path

import pytest

from chipbench.trace_reduce import (
    Event,
    Reduced,
    find_xplane,
    kernel_of,
    leaves,
    op_name,
    reduce_trace,
)

DATA = Path(__file__).parent / "data"


def _reduced():
    ops = [Event("a", 10, 15), Event("b", 25, 10), Event("a", 60, 10),
           Event("c", 95, 20)]
    spans = [Event("poll", 0, 40), Event("submit", 40, 30),
             Event("deliver", 80, 30)]
    return Reduced(window=(0, 100), modules=[[]], ops=[ops], spans=spans)


def test_busy_is_the_union_of_operations_inside_the_window():
    r = _reduced()
    # [10, 25) + [25, 35) + [60, 70) + [95, 100), clipped at the end
    assert r.busy_s() == pytest.approx(40e-9)
    assert r.window_s == pytest.approx(100e-9)


def test_op_seconds_clip_to_the_window():
    r = _reduced()
    assert r.op_seconds() == pytest.approx({"a": 25e-9, "b": 10e-9,
                                            "c": 5e-9})


def test_idle_gaps_are_credited_to_the_overlapping_span():
    r = _reduced()
    assert r.idle_gaps() == [(0, 10), (35, 60), (70, 95)]
    # (35, 60) overlaps poll by 5 and submit by 20: all of it is submit's.
    assert r.idle_by_span() == pytest.approx({"poll": 10e-9,
                                              "submit": 25e-9,
                                              "deliver": 25e-9})


def test_nested_ops_count_once():
    ops = [Event("while.5", 0, 100), Event("copy.1", 10, 20),
           Event("fusion.2", 40, 30)]
    assert [e.name for e in leaves(ops)] == ["copy.1", "fusion.2"]
    r = Reduced(window=(0, 100), modules=[[]], ops=[ops], spans=[])
    assert r.busy_s() == pytest.approx(100e-9)
    assert r.op_seconds() == pytest.approx({"copy.1": 20e-9,
                                            "fusion.2": 30e-9})


def test_names_from_hlo_text():
    text = "%abfp_matmul_packed_pallas.777 = bf16[32,49280] custom-call(x)"
    assert op_name(text) == "abfp_matmul_packed_pallas.777"
    assert kernel_of(op_name(text)) == "abfp_matmul_packed_pallas"
    assert kernel_of("while") == "while"


def test_recorded_chip_trace():
    path = find_xplane(str(DATA / "v5e_trace"))
    assert path is not None, "recorded trace missing"
    r = reduce_trace(path, "chipbench_traced_window",
                     ("submit", "poll", "idle_wait", "deliver", "sync"))
    assert len(r.ops) == 1 and len(r.modules) == 1
    assert 0 < r.busy_s() <= r.window_s
    ops = r.op_seconds()
    # Leaf ops cover nearly all busy time; the rest is control flow
    # (a while loop's own time between the ops of its body).
    assert 0.9 * r.busy_s() <= sum(ops.values()) <= r.busy_s()
    kernels = {kernel_of(n) for n in ops}
    assert {"fused_quantized_decode_attention", "fused_qkv_packed_pallas",
            "abfp_matmul_packed_pallas"} <= kernels
    steps = [e for e in r.modules[0] if "_step" in e.name]
    assert len(steps) == 2 and all(e.dur > 0 for e in steps)
    idle = r.idle_by_span()
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s(),
                                               rel=1e-6)


def test_readers_on_recorded_chip_trace():
    import json

    from chipbench.record import RunRecord, load_reader
    from chipbench.work import Pass

    root = Path(__file__).resolve().parents[2]
    cfg = json.loads((root / "chipbench/configs/smollm-360m.json").read_text())
    peaks = json.loads((root / "chipbench/peaks.json").read_text())
    r = reduce_trace(find_xplane(str(DATA / "v5e_trace")),
                     "chipbench_traced_window", ())
    # Two decode passes; three live slots is what that run had.
    passes = [Pass("decode", ((1, 300), (1, 200), (1, 100)))] * 2
    run = RunRecord(cfg=cfg, mix={}, peaks=peaks["TPU v5 lite"], seconds=1.0,
                    occupancy=[],
                    traced_passes=passes, trace=r)
    step = load_reader("decode_step_ms.open")(run)
    assert 40 < step < 70                       # ms per decode launch
    for name in ("abfp_matmul_roofline.open", "kv_attn_roofline.open",
                 "decode_mfu.open"):
        assert 0 < load_reader(name)(run) <= 100
    assert 0 <= load_reader("device_idle_share.open")(run) < 100
    # Most of that step moves the K/V cache around the attention kernel.
    copies = load_reader("kv_copy_ms.open")(run)
    assert 0.6 * step < copies < step
    # No prefill pass in this trace: its readers read nothing.
    assert load_reader("prefill_pass_ms.open")(run) is None
    assert load_reader("prefill_mfu.open")(run) is None
    # A pass count that does not match the trace's launches is an error.
    run.traced_passes = passes[:1]
    with pytest.raises(ValueError):
        load_reader("decode_step_ms.open")(run)
