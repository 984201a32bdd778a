"""The benchmark's float32 reference against the program's float path, at
smoke size on the CPU, for both model families the cells run."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench import weights as wlib
from chipbench.run import model_config

DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_reference_matches_program_float_forward(name):
    from repro.core.abfp import QuantConfig
    from repro.models import forward
    from repro.models.layers import Numerics

    cfg = json.loads((DATA / f"{name}.json").read_text())
    w = wlib.make_weights(cfg, 7)
    mcfg = dataclasses.replace(model_config(cfg), param_dtype=jnp.float32,
                               activation_dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, jnp.asarray(tokens)[None], mcfg,
                         Numerics(QuantConfig(mode="float")))
    want = reference.logits(cfg, w, tokens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_served_gaps_zero_for_reference_greedy_tokens():
    cfg = json.loads((DATA / "tiny-dense.json").read_text())
    w = wlib.make_weights(cfg, 3)
    seq = list(np.random.default_rng(1).integers(1, cfg["vocab_size"], 9))
    served = []
    for _ in range(6):
        z = reference.logits(cfg, w, seq + served)
        served.append(int(jnp.argmax(z[-1])))
    widest, total = reference.served_gaps(cfg, w, seq, served, 64)
    assert widest == 0.0 and total == 0.0
    bad = served[:3] + [(served[3] + 1) % cfg["vocab_size"]] + served[4:]
    widest, _ = reference.served_gaps(cfg, w, seq, bad, 64)
    assert widest > 0.0


def test_weights_depend_on_high_seed_bits():
    cfg = json.loads((DATA / "tiny-dense.json").read_text())
    a = wlib.make_weights(cfg, 5)["embed"]
    b = wlib.make_weights(cfg, 5 + (1 << 32))["embed"]
    c = wlib.make_weights(cfg, 5)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))
