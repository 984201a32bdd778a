"""``correct`` at miniature size: sound runs pass, and the control (every
ABFP bit width lowered from 8 to 4, the program's own lower-precision
path) fails, for the dense open-loop and the MoE closed-loop paths."""

import pytest

from helpers import run_tiny

CASES = [("tiny-dense", "tiny-open"), ("tiny-moe", "tiny-closed")]


@pytest.mark.parametrize("config,mix", CASES)
def test_sound_run_is_correct(config, mix):
    res = run_tiny(config, mix, 4_000_000_017)
    checks = res["checks"]
    assert res["correct"], checks
    assert res["info"]["compiles_in_window"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for c in checks.values():
        assert c["limit"] is None or c["value"] <= c["limit"]
    names = set(res["metrics"])
    assert "setup_s" in names
    assert names & {"itl_mean_s", "output_tokens_per_s"}


@pytest.mark.parametrize("config,mix", CASES)
def test_int4_control_is_not_correct(config, mix):
    res = run_tiny(config, mix, 4_000_000_017, bits=4)
    assert not res["correct"], res["checks"]
