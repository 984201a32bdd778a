"""Operations and bytes of the benchmark's kernel families against hand
counts for one SmolLM-360M layer (d 960, 15/5 heads of 64, d_ff 2560,
vocab 49152, ABFP tile 128): a 32-row decode pass and a 128-row prefill."""

import json
from pathlib import Path

import pytest

from chipbench.work import (
    Pass,
    Work,
    attention_calls,
    matmul_calls,
    min_seconds,
    model_flops,
)

CFG = json.loads((Path(__file__).resolve().parents[2] / "chipbench" / "configs"
                  / "smollm-360m.json").read_text())
ONE_LAYER = {**CFG, "num_hidden_layers": 1}


def test_decode_matmuls_hand_count():
    p = Pass("decode", tuple((1, 1000) for _ in range(32)))
    assert matmul_calls(p, ONE_LAYER) == [
        (Work(98_304_000, 1_695_232), 1),             # fused q|k|v
        (Work(58_982_400, 1_029_632), 1),             # wo
        (Work(157_286_400, 2_693_632), 2),            # wi, wg
        (Work(157_286_400, 2_640_640), 1),            # mlp wo
        (Work(3_019_898_880, 51_149_312), 1),         # lm head
    ]


def test_prefill_matmuls_hand_count():
    p = Pass("prefill", ((128, 128),))
    assert matmul_calls(p, ONE_LAYER) == [
        (Work(235_929_600, 1_307_648), 1),            # wq
        (Work(78_643_200, 519_168), 2),               # wk, wv
        (Work(235_929_600, 1_307_648), 1),            # wo
        (Work(629_145_600, 3_278_848), 2),            # wi, wg
        (Work(629_145_600, 3_074_560), 1),            # mlp wo
        (Work(94_371_840, 48_071_632), 1),            # lm head, one row
    ]


def test_layers_multiply_calls():
    p = Pass("decode", ((1, 10),))
    one = matmul_calls(p, ONE_LAYER)
    full = matmul_calls(p, CFG)
    assert [w for w, _ in one] == [w for w, _ in full]
    assert [32 * n for _, n in one[:-1]] == [n for _, n in full[:-1]]
    assert full[-1][1] == 1     # one LM head per pass


def test_decode_attention_counts_live_cache_only():
    p = Pass("decode", tuple((1, 1000) for _ in range(32)))
    assert attention_calls(p, ONE_LAYER) == [(Work(122_880_000, 21_242_880), 1)]
    assert attention_calls(Pass("prefill", ((128, 128),)), ONE_LAYER) == []


def test_model_flops_one_decode_token():
    p = Pass("decode", ((1, 1000),))
    assert model_flops(p, ONE_LAYER) == 117_872_640


def test_min_seconds_takes_each_calls_bound():
    calls = [(Work(ops=10.0, bytes=1.0), 2), (Work(ops=1.0, bytes=10.0), 1)]
    assert min_seconds(calls, 1.0, 1.0) == pytest.approx(30.0)


def test_expert_work_counts_routed_rows():
    moe = json.loads((Path(__file__).resolve().parents[2] / "chipbench"
                      / "configs" / "granite-moe-1b-a400m.json").read_text())
    moe = {**moe, "num_hidden_layers": 1}
    wa, _ = matmul_calls(Pass("prefill", ((64, 64),)), moe)[3]
    wb, _ = matmul_calls(Pass("prefill", ((128, 128),)), moe)[3]
    # 2 ops x rows x top-8 x 3 matrices of 1024 x 512
    assert wa.ops == 2.0 * 64 * 8 * 3 * 1024 * 512
    assert wb.ops == 2 * wa.ops
