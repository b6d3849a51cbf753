"""The check's control and planted faults, driven through a whole run on
the CPU at test size (the look for a chip is skipped).

The limit here is for the test size only (chipbench/limits/ holds the
cells'), with the cells' sample (up to 32 requests): on seeds 1-8 the
bfloat16 program read a mean gap of at most 0.0214 and the float8
control at least 0.142; the three faults read 0.26 or more on seeds 3-5."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests.tiny import tiny_spec

MEAN_LIMIT = 0.06


def _run(seed, hook=None, control=False, rate=None):
    from chipbench import run
    spec = tiny_spec(mean=MEAN_LIMIT)
    if rate is not None:
        spec["mix"]["arrival"]["rate"] = rate
    return run.run_cell(spec, seed, 4.0, False,
                        log=lambda s: None, control=control,
                        engine_hook=hook)


@pytest.mark.parametrize("seed", [2, 3, 5])
def test_program_passes_and_control_fails(seed):
    res = _run(seed, control=True)
    r = res["readings"]
    assert res["correct"], res["checks"]
    assert r["mean_control_gap"] > MEAN_LIMIT > r["mean_logit_gap"], r


def _patch_decode(engine, after):
    orig = engine._exec_decode

    def wrapped():
        # the step donates the cache it is given: hold a copy of it
        before = (engine.tokens, jax.tree.map(jnp.copy, engine.cache))
        orig()
        after(engine, *before)
    engine._exec_decode = wrapped


def _token_altered(engine):
    _patch_decode(engine, lambda e, tok, cache: setattr(
        e, "tokens", (e.tokens + 1) % e.cfg.vocab))


def _state_unchanged(engine):
    _patch_decode(engine, lambda e, tok, cache: setattr(e, "cache", cache))


def _half_batch(engine):
    def keep_half(e, tok, cache):
        # every other lane left out of the step: it keeps its last token
        e.tokens = e.tokens.at[1::2].set(tok[1::2])
    _patch_decode(engine, keep_half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_fault_fails_the_check(fault):
    # several lanes in use and requests that finish in the window, so a
    # fault in any lane reaches the sample
    res = _run(3, hook=fault, rate=12.0)
    assert not res["correct"], res["checks"]
    assert np.isfinite(res["checks"]["mean_logit_gap"]["value"])
