"""Fault injection / detection / recovery suite (``repro.serving.faults``
plus the engine integration).

Contracts under test:

  * Zero overhead: an engine with ``faults=None`` and an engine with a
    rate-0 plan attached emit IDENTICAL tokens — the fault machinery adds
    nothing to the hot path until an event actually fires.
  * Determinism: the same (params, FaultConfig) always yields the same
    plan, so a fault trace replays exactly across runs and recovery
    settings.
  * Injection -> detection -> repair roundtrips per kind: fingerprint
    probes flag exactly the faulted columns/tiles, and repair restores
    the packed arrays bit-exactly.
  * Conservation: ``submitted == completed + rejected + timed_out`` after
    drain, under fault traces with and without recovery.
  * SLO-aware recovery: recovery-on strictly beats recovery-off on
    corruption-excluded goodput at every nonzero rate.
  * Deadlines: past-deadline requests are cancelled (queued or in-flight),
    freed, and surfaced as ``timed_out`` in both metrics and poll results.

The mesh cases (parity with fault machinery attached, shard-drop reshard)
carry ``@dist`` and need the 8-device leg; everything else runs on one
device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.abfp import QuantConfig, packed_tile_fingerprint
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.models.packing import pack_model_params
from repro.serving import (
    FaultConfig,
    FaultPlan,
    Request,
    ServingEngine,
    drift_detect_rtol,
    make_fault_plan,
)
from repro.serving import faults as faultlib
from repro.serving.faults import FaultEvent

pytestmark = pytest.mark.fault

PACKED = QuantConfig(mode="abfp_packed", tile_width=32, gain=4.0,
                     noise_lsb=0.5)

needs_8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (run under XLA_FLAGS="
           "--xla_force_host_platform_device_count=8 / make test-dist)")


@pytest.fixture(scope="module")
def tinyllama():
    mcfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    return mcfg, params


@pytest.fixture(scope="module")
def packed_params(tinyllama):
    mcfg, params = tinyllama
    return pack_model_params(params, PACKED, mcfg)


def _workload(mcfg, n=10, max_new=6, deadline=None):
    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    prompt=[int(t) for t in
                            rng.integers(1, mcfg.vocab_size, 6)],
                    max_new_tokens=max_new, arrival_time=float(i),
                    deadline=deadline)
            for i in range(n)]


def _tokens(done):
    return {r.uid: tuple(r.generated) for r in done}


# ---------------------------------------------------------------------------
# Plans: determinism, rate semantics, site enumeration
# ---------------------------------------------------------------------------


def test_fault_sites_cover_packed_leaves(packed_params):
    sites = faultlib.fault_sites(packed_params)
    assert sites, "packed model must expose fault sites"
    assert all(s.packed for s in sites)
    assert sites == sorted(sites, key=lambda s: s.path)
    # paths address real leaves
    for s in sites[:3]:
        leaf = faultlib._get_site(packed_params, s.path)
        assert leaf.n_cols == s.n_cols


def test_plan_deterministic_and_bounded(packed_params):
    cfg = FaultConfig(rate=0.05, seed=7, horizon=64)
    p1 = make_fault_plan(packed_params, cfg, tp=4)
    p2 = make_fault_plan(packed_params, cfg, tp=4)
    assert p1.events == p2.events
    assert all(ev.tick < 64 for ev in p1.events)
    drops = [ev for ev in p1.events if ev.kind == "shard_drop"]
    assert len(drops) <= cfg.max_shard_drops
    assert all(0 <= ev.shard < 4 for ev in drops)


def test_plan_rate_zero_empty_and_rate_positive_nonempty(packed_params):
    assert make_fault_plan(packed_params,
                           FaultConfig(rate=0.0)).events == []
    # rate > 0 guarantees at least one event inside the horizon, even when
    # the Bernoulli draw comes up empty (the 0.1%-sweep floor).
    plan = make_fault_plan(packed_params,
                           FaultConfig(rate=1e-6, horizon=32))
    assert len(plan.events) >= 1
    assert plan.events[0].tick < 32


def test_fault_config_validates():
    with pytest.raises(ValueError):
        FaultConfig(kinds=("stuck_col", "bitflip"))
    with pytest.raises(ValueError):
        FaultConfig(rate=1.5)


def test_plan_due_cursor(packed_params):
    plan = FaultPlan([FaultEvent(2, "stuck_col", "a", cols=(0,)),
                      FaultEvent(5, "stuck_col", "b", cols=(1,))],
                     FaultConfig())
    evs, cur = plan.due(tick=3, cursor=0)
    assert [e.path for e in evs] == ["a"] and cur == 1
    evs, cur = plan.due(tick=3, cursor=cur)
    assert evs == [] and cur == 1               # applied exactly once
    evs, cur = plan.due(tick=9, cursor=cur)
    assert [e.path for e in evs] == ["b"] and cur == 2


# ---------------------------------------------------------------------------
# Injection -> detection -> repair roundtrips
# ---------------------------------------------------------------------------


def test_stuck_col_roundtrip(packed_params):
    site = faultlib.fault_sites(packed_params)[0]
    base = faultlib.site_fingerprint(packed_params, site)
    cols = (1, 5)
    bad = faultlib.inject_stuck_cols(packed_params, site.path, cols)
    det = faultlib.detect_site(base, faultlib.site_fingerprint(bad, site))
    assert det.stuck_cols == cols
    assert det.drifted == ()                    # dead cols aren't "drift"
    fixed = faultlib.repair_stuck(bad, packed_params, site.path,
                                  det.stuck_cols)
    leaf0 = faultlib._get_site(packed_params, site.path)
    leaf1 = faultlib._get_site(fixed, site.path)
    assert jnp.array_equal(leaf0.codes, leaf1.codes)
    assert jnp.array_equal(leaf0.scales, leaf1.scales)


def test_scale_drift_roundtrip(packed_params):
    site = faultlib.fault_sites(packed_params)[0]
    base = faultlib.site_fingerprint(packed_params, site)
    tiles = ((0, 3), (site.n_tiles - 1, 7))
    bad = faultlib.inject_scale_drift(packed_params, site.path, tiles,
                                      (1.2, 0.8))
    det = faultlib.detect_site(base, faultlib.site_fingerprint(bad, site))
    assert det.stuck_cols == ()
    assert set(det.drifted) >= set(tiles)       # both drifts flagged
    fixed = faultlib.repair_drift(bad, packed_params, site.path, det.drifted)
    leaf0 = faultlib._get_site(packed_params, site.path)
    leaf1 = faultlib._get_site(fixed, site.path)
    assert jnp.array_equal(leaf0.scales, leaf1.scales)
    assert jnp.array_equal(leaf0.codes, leaf1.codes)


def test_drift_below_tolerance_not_flagged(packed_params):
    site = faultlib.fault_sites(packed_params)[0]
    base = faultlib.site_fingerprint(packed_params, site)
    # Perturb well inside the detection tolerance: must read clean.
    cur = base * (1.0 + 0.1 * drift_detect_rtol())
    assert faultlib.detect_site(base, cur).clean


def test_shard_drop_single_device_kills_sites(packed_params):
    bad = faultlib.inject_shard_drop(packed_params, shard=0, tp=1)
    site = faultlib.fault_sites(packed_params)[0]
    leaf = faultlib._get_site(bad, site.path)
    assert not jnp.any(leaf.codes) and not jnp.any(leaf.scales)


def test_fingerprint_matches_abfp_reduction(packed_params):
    # The probe is exactly sum_i |codes| * delta * scales per (tile, col).
    site = faultlib.fault_sites(packed_params)[0]
    leaf = faultlib._get_site(packed_params, site.path)
    want = packed_tile_fingerprint(leaf)
    want = np.asarray(want.reshape(-1, *want.shape[-2:]).sum(axis=0),
                      np.float32)
    got = faultlib.site_fingerprint(packed_params, site)
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# Engine integration: zero overhead, conservation, recovery wins
# ---------------------------------------------------------------------------


def test_zero_overhead_parity(tinyllama):
    mcfg, params = tinyllama
    base = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                         seed=0)
    out0 = _tokens(base.run(_workload(mcfg)))
    gated = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                          seed=0, faults=FaultConfig(rate=0.0))
    out1 = _tokens(gated.run(_workload(mcfg)))
    assert out0 == out1
    assert gated.metrics.faults["injected"] == 0


@pytest.mark.parametrize("recovery", [True, False], ids=["on", "off"])
def test_conservation_under_faults(tinyllama, recovery):
    mcfg, params = tinyllama
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                        seed=0, faults=FaultConfig(rate=0.05, seed=3,
                                                   horizon=64),
                        recovery=recovery, detect_every=2)
    done = eng.run(_workload(mcfg, n=14))
    cons = eng.metrics.conservation()
    assert cons["ok"], cons
    assert len(done) == 14
    assert eng.metrics.faults["injected"] >= 1


def test_recovery_beats_no_recovery_on_goodput(tinyllama):
    mcfg, params = tinyllama
    good = {}
    for recovery in (True, False):
        eng = ServingEngine(params, mcfg, capacity=4, max_len=64,
                            quant=PACKED, seed=0,
                            faults=FaultConfig(rate=0.02, seed=3,
                                               horizon=64),
                            recovery=recovery, detect_every=2)
        eng.run(_workload(mcfg, n=14))
        assert eng.metrics.conservation()["ok"]
        good[recovery] = eng.metrics.goodput(slo_ttft=100.0) or 0.0
    assert good[True] > good[False]


def test_recovery_counters_and_summary(tinyllama):
    mcfg, params = tinyllama
    plan = FaultPlan([FaultEvent(4, "scale_drift",
                                 faultlib.fault_sites(
                                     pack_model_params(params, PACKED,
                                                       mcfg))[0].path,
                                 tiles=((0, 2),), factors=(1.2,))],
                     FaultConfig(rate=0.01))
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                        seed=0, faults=plan, recovery=True, detect_every=2)
    eng.run(_workload(mcfg))
    s = eng.metrics.summary()
    assert s["faults"]["injected_scale_drift"] == 1
    assert s["faults"]["detected"] >= 1
    assert s["faults"]["tiles_requantized"] >= 1
    assert s["straggler"] is not None           # monitor wired into summary
    assert s["straggler"]["escalation"] in ("log", "reslice", "remesh")


def test_single_device_shard_drop_recovers(tinyllama):
    mcfg, params = tinyllama
    plan = FaultPlan([FaultEvent(5, "shard_drop", "", shard=0)],
                     FaultConfig(rate=0.01))
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                        seed=0, faults=plan, recovery=True, detect_every=2)
    done = eng.run(_workload(mcfg))
    assert eng.metrics.faults["reshards"] == 1
    assert eng.metrics.conservation()["ok"]
    assert len(done) == 10
    assert eng.metrics.summary()["requests"]["requeued"] >= 1


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


def test_deadline_cancels_inflight_and_frees_slot(tinyllama):
    mcfg, params = tinyllama
    # capacity 1: uid 0 holds the slot past uid 1's patience; uid 0 itself
    # has a deadline it cannot meet (needs ~14 ticks, gets 6).
    reqs = [Request(uid=0, prompt=[3, 5, 7], max_new_tokens=12,
                    arrival_time=0.0, deadline=6.0),
            Request(uid=1, prompt=[2, 4, 6], max_new_tokens=2,
                    arrival_time=0.0)]
    eng = ServingEngine(params, mcfg, capacity=1, max_len=64, quant=PACKED,
                        seed=0)
    for r in reqs:
        eng.submit(r)
    done = eng.drain()
    assert reqs[0].timed_out and reqs[0].done
    assert len(reqs[0].generated) < 12          # cancelled mid-flight
    assert not reqs[1].timed_out
    assert len(reqs[1].generated) == 2          # freed slot was reused
    assert reqs[0] in done and reqs[1] in done  # timeout surfaced via poll
    cons = eng.metrics.conservation()
    assert cons == {"submitted": 2, "completed": 1, "rejected": 0,
                    "timed_out": 1, "shed": 0, "preempted": 0,
                    "resumed": 0, "preempt_ok": True, "ok": True}
    assert eng.metrics.requests[0].timed_out


def test_deadline_expires_queued_request(tinyllama):
    mcfg, params = tinyllama
    # uid 1 can never be admitted before its deadline (capacity 1, uid 0
    # runs ~10 ticks) -> expired from the QUEUE, never admitted.
    reqs = [Request(uid=0, prompt=[3, 5, 7], max_new_tokens=8,
                    arrival_time=0.0),
            Request(uid=1, prompt=[2, 4], max_new_tokens=2,
                    arrival_time=0.0, deadline=3.0)]
    eng = ServingEngine(params, mcfg, capacity=1, max_len=64, quant=PACKED,
                        seed=0)
    for r in reqs:
        eng.submit(r)
    done = eng.drain()
    assert reqs[1].timed_out and reqs[1].generated == []
    assert eng.metrics.requests[1].admit_time is None  # expired in queue
    assert reqs[1] in done
    assert eng.metrics.conservation()["ok"]


def test_deadline_zero_overhead_when_unused(tinyllama):
    mcfg, params = tinyllama
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                        seed=0)
    eng.run(_workload(mcfg, n=6))
    assert not eng._has_deadlines
    assert eng.metrics.conservation()["timed_out"] == 0


# ---------------------------------------------------------------------------
# Mesh cases (8-device leg)
# ---------------------------------------------------------------------------


@pytest.mark.dist
@needs_8
@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (1, 2), (2, 4)])
def test_mesh_parity_with_fault_machinery(tinyllama, shape):
    mcfg, params = tinyllama
    base = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                         seed=0, prefill_chunks=(4, 8))
    out0 = _tokens(base.run(_workload(mcfg)))
    mesh = make_mesh(shape, ("data", "model"))
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                        seed=0, prefill_chunks=(4, 8), mesh=mesh,
                        faults=FaultConfig(rate=0.0))
    out1 = _tokens(eng.run(_workload(mcfg)))
    assert out0 == out1, shape


@pytest.mark.dist
@needs_8
def test_mesh_shard_drop_reshards_and_conserves(tinyllama):
    mcfg, params = tinyllama
    plan = FaultPlan([FaultEvent(6, "shard_drop", "", shard=1)],
                     FaultConfig(rate=0.01))
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServingEngine(params, mcfg, capacity=4, max_len=64, quant=PACKED,
                        seed=0, prefill_chunks=(4, 8), mesh=mesh,
                        faults=plan, recovery=True, detect_every=2)
    done = eng.run(_workload(mcfg))
    # (2, 4) loses model bank 1 -> 6 chips -> largest mesh holding tp=4
    # is (1, 4).
    assert tuple(eng.mesh.devices.shape) == (1, 4)
    assert eng.metrics.faults["reshards"] == 1
    assert eng.metrics.conservation()["ok"]
    assert len(done) == 10
