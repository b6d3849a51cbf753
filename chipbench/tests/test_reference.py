"""The plain reference against the served path, on the CPU at test size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests.tiny import tiny_spec


def _window(spec, seed, f32=False, seconds=4.0):
    from chipbench import harness, loadgen
    model = harness.model_of(spec["config"])
    engine = harness.build_engine(spec["config"], model.arch, seed)
    if f32:
        from repro.models import init_cache
        engine.params = jax.tree.map(lambda a: a.astype(jnp.float32),
                                     engine.params)
        engine.cache = init_cache(engine.cfg, engine.max_batch,
                                  engine.max_seq, dtype=jnp.float32)
    harness.warm_up(engine)
    plan = loadgen.plan_requests(spec["mix"], seed, seconds,
                                 id_base=seed * (1 << 20) + 1)
    return model, harness.run_window(engine, plan, seconds, model.cost,
                                     harness.Spans(False),
                                     harness.CompileCounter())


@pytest.mark.parametrize("seed", [1, 4])
def test_engine_in_float32_matches_reference(seed):
    """Prefill chunks then batched decode through the Engine, its weights
    and cache held in float32: every served token is the reference's first
    choice, to float32 rounding. Seeds 1 and 4 are ones whose bfloat16
    runs flip tokens, so the flips are the program's rounding."""
    from chipbench import check
    spec = tiny_spec()
    model, window = _window(spec, seed, f32=True)
    picked = check.sample(window.reqs.values(), seed, 64, 8)
    r = check.readings(model.reference(model.arch, seed), picked)
    assert r["compared_tokens"] >= 40
    assert r["max_logit_gap"] < 1e-4, r


def test_reference_weights_are_the_served_ones():
    from chipbench.reference import Reference, _layer_weights, arch_from_config
    from chipbench import harness
    spec = tiny_spec()
    arch = arch_from_config(spec["config"]["model"])
    from repro.models import init_params
    cfg = harness.arch_config(spec["config"], arch)
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


def test_logits_at_any_count_of_rows():
    """The head runs on row counts padded to a power of two and cut back:
    each row's logits are those of the row alone, whatever the count."""
    from chipbench.reference import Reference, arch_from_config
    arch = arch_from_config(tiny_spec()["config"]["model"])
    ref = Reference(arch, 5)
    seq = np.random.default_rng(5).integers(0, arch.vocab, 120)
    rows = [np.arange(3, 100), np.arange(90, 91), np.arange(40, 106)]
    got = ref.logits([seq] * 3, rows)["f32"]
    one = [ref.logits([seq], [np.array([r])])["f32"][0][0] for r in
           (3, 50, 99, 90, 40, 105)]
    assert [g.shape for g in got] == [(97, arch.vocab), (1, arch.vocab),
                                      (66, arch.vocab)]
    for g, i, o in ((got[0], 0, one[0]), (got[0], 47, one[1]),
                    (got[0], 96, one[2]), (got[1], 0, one[3]),
                    (got[2], 0, one[4]), (got[2], 65, one[5])):
        np.testing.assert_allclose(g[i], o, rtol=0, atol=1e-5)
