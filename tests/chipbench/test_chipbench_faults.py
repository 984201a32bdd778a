"""A run whose served path is broken underneath must come out not correct:
the decode step returning its state (the KV cache) unchanged, half of the
batch left out (its tokens copied from the other half), and one token
altered where it is sampled.  Exchange between chips does not exist in
these one-chip cells."""

import jax
import pytest

from helpers import run_tiny


def _wrap(eng, after):
    inner = eng._call

    def call(shape_key, args):
        if shape_key[0] != "decode":
            return inner(shape_key, args)
        keep = jax.tree.map(lambda a: a.copy(), args[1])
        (logits, sampled, state), warm = inner(shape_key, args)
        return after(logits, sampled, state, keep), warm

    eng._call = call


def state_unchanged(eng):
    _wrap(eng, lambda lg, smp, st, keep: (lg, smp, keep))


def half_batch(eng):
    def after(lg, smp, st, keep):
        half = smp.shape[0] // 2
        return lg, smp.at[half:].set(smp[:half]), st

    _wrap(eng, after)


def token_altered(eng):
    vocab = eng.mcfg.vocab_size
    _wrap(eng, lambda lg, smp, st, keep:
          (lg, smp.at[0].set((smp[0] + 1) % vocab), st))


@pytest.mark.parametrize("breaker", [state_unchanged, half_batch,
                                     token_altered])
def test_broken_path_is_not_correct(breaker):
    res = run_tiny("tiny-dense", "tiny-closed", 4_000_000_019, breaker=breaker)
    assert not res["correct"], res["checks"]
