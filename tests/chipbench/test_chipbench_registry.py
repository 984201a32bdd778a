"""Every part of the benchmark is found by the name BENCHMARK.json gives
it, and the file keeps to the form the benchmark's contract sets."""

import json
import re

import numpy as np
import pytest

from chipbench import loadgen
from chipbench.record import RunRecord, load_reader
from chipbench.run import E2E_METRICS, ROOT, model_config, quant_config

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _empty_run(cfg=None, mix=None):
    return RunRecord(cfg=cfg or {}, mix=mix or {}, peaks={}, seconds=1.0,
                     occupancy=[], traced_passes=[])


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads_by_name(c):
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"]
    assert c["file"].startswith("chipbench/configs/")
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    assert set(cfg["published"]) == set(c["reduced"])
    assert cfg["source"] == c["source"]
    mcfg = model_config(cfg)
    assert mcfg.d_model == cfg["hidden_size"]
    assert quant_config(cfg["numerics"]).mode == cfg["numerics"]["mode"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_traffic_plans_by_name(cell):
    mix = loadgen.load_mix(cell["traffic"])
    cfg = json.loads((ROOT / f"chipbench/configs/{cell['config']}.json")
                     .read_text())
    a = loadgen.plan(mix, 2**33 + 5, BENCH["run_seconds"], cfg["vocab_size"])
    b = loadgen.plan(mix, 17, BENCH["run_seconds"], cfg["vocab_size"])
    # Every seed gets the same lengths (and gaps), in another order.
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [p.prompt for p in a] != [p.prompt for p in b]
    for p in a:
        assert len(p.prompt) + p.max_new <= cfg["engine"]["max_len"]
        assert all(0 < t < cfg["vocab_size"] for t in p.prompt)
    if mix["loop"] == "open":
        span = mix["ramp_s"] + BENCH["run_seconds"]
        assert a[-1].due > span
        dues = [p.due for p in a]
        assert dues == sorted(dues) == [p.due for p in b]

        def window(plan):
            return sorted((len(p.prompt), p.max_new) for p in plan
                          if mix["ramp_s"] <= p.due < span)

        # The window holds the same requests' sizes for every seed.
        assert window(a) == window(b)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_reader_loads_and_reads_nothing_from_nothing(m):
    read = load_reader(m["name"])
    assert read(_empty_run()) is None


def test_end_to_end_metrics_are_computed_by_the_harness():
    assert {m["name"] for m in BENCH["end_to_end"]} <= set(E2E_METRICS)


def test_benchmark_json_form():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    cells = {c["name"] for c in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for c in BENCH["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert len(c["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        layers.add(m["layer"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for cell in cells:
        per = [m for m in BENCH["per_layer"] if cell in m["workloads"]]
        mine = [m for m in BENCH["end_to_end"]
                if cell in m.get("workloads", [cell])]
        assert per and len(mine) >= 2


def test_lengths_are_lognormal_and_clipped():
    rng = np.random.default_rng(0)
    spec = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 40,
            "max": 200}
    v = loadgen.draw_lengths(spec, 4000, rng)
    assert v.min() == 40 and v.max() == 200
    assert 90 <= np.median(v) <= 110
    with pytest.raises(ValueError):
        loadgen.draw_lengths({**spec, "dist": "uniform"}, 3, rng)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_steady_state_requests(cell):
    mix = loadgen.load_mix(cell["traffic"])
    cfg = json.loads((ROOT / f"chipbench/configs/{cell['config']}.json")
                     .read_text())
    n = mix["warm_live"]
    assert 0 < n <= cfg["engine"]["capacity"]
    a = loadgen.warm(mix, 2**33 + 5, cfg["vocab_size"], n)
    b = loadgen.warm(mix, 17, cfg["vocab_size"], n)
    # The same sizes for every seed, other token ids.
    assert [(len(p.prompt), p.max_new) for p in a] == \
        [(len(p.prompt), p.max_new) for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in b]
    for p in a:
        assert p.max_new >= 1
        assert len(p.prompt) + p.max_new <= cfg["engine"]["max_len"]
    # In flight at a random moment: long requests are over-represented and
    # half done on average, so the total length (prompt, generated and
    # still to come) exceeds a fresh request's on average.
    many = loadgen.warm(mix, 1, cfg["vocab_size"], 2000)
    fresh = loadgen.plan(mix, 1, BENCH["run_seconds"], cfg["vocab_size"])
    total = np.mean([len(p.prompt) + p.max_new for p in many])
    assert total > 1.1 * np.mean([len(p.prompt) + p.max_new for p in fresh])
