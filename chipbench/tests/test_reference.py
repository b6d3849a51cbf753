"""The plain reference against the served path, on the CPU at test size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests.tiny import tiny_spec


def _window(spec, seed, f32=False, seconds=4.0):
    from chipbench import harness, loadgen
    from chipbench.reference import arch_from_config
    arch = arch_from_config(spec["config"]["model"])
    engine = harness.build_engine(spec["config"], arch, seed)
    if f32:
        from repro.models import init_cache
        engine.params = jax.tree.map(lambda a: a.astype(jnp.float32),
                                     engine.params)
        engine.cache = init_cache(engine.cfg, engine.max_batch,
                                  engine.max_seq, dtype=jnp.float32)
    harness.warm_up(engine)
    plan = loadgen.plan_requests(spec["mix"], seed, seconds,
                                 id_base=seed * (1 << 20) + 1)
    return arch, harness.run_window(engine, plan, seconds, arch,
                                    harness.Spans(False),
                                    harness.CompileCounter())


@pytest.mark.parametrize("seed", [1, 4])
def test_engine_in_float32_matches_reference(seed):
    """Prefill chunks then batched decode through the Engine, its weights
    and cache held in float32: every served token is the reference's first
    choice, to float32 rounding. Seeds 1 and 4 are ones whose bfloat16
    runs flip tokens, so the flips are the program's rounding."""
    from chipbench import check
    from chipbench.reference import Reference
    spec = tiny_spec()
    arch, window = _window(spec, seed, f32=True)
    picked = check.sample(window.reqs.values(), seed, 64, 8)
    r = check.readings(Reference(arch, seed), picked)
    assert r["compared_tokens"] >= 40
    assert r["max_logit_gap"] < 1e-4, r


def test_reference_weights_are_the_served_ones():
    from chipbench.reference import Reference, _layer_weights, arch_from_config
    from chipbench import harness
    spec = tiny_spec()
    arch = arch_from_config(spec["config"]["model"])
    from repro.models import init_params
    cfg = harness.arch_config(spec["config"]["model"], arch, "tiny")
    params = init_params(cfg, jax.random.PRNGKey(9))
    ref = Reference(arch, 9)
    w = _layer_weights(arch, ref._layer_keys[2])
    blk = params["blocks"][0]
    for k in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(np.asarray(blk["mixer"][k][2]),
                                      np.asarray(w[k]))
    for k in ("router", "w1", "w2", "w3"):
        np.testing.assert_array_equal(np.asarray(blk["ffn"][k][2]),
                                      np.asarray(w[k]))
