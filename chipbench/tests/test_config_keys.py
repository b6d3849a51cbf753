"""What a configuration file may select: its reference and FLOP modules,
settings of the program's ArchConfig, and an engine with no placement
controller. Files that leave the keys out get the granite cell's path."""

import copy
import json
import shutil
from pathlib import Path

import pytest

from chipbench.tests.tiny import tiny_spec

ROOT = Path(__file__).resolve().parents[2]
GRANITE = ROOT / "chipbench/configs/granite-moe-3b-a800m.json"
#: the test-size limit of the mean gap (test_control.py says where from)
MEAN_LIMIT = 0.06


def _served(config, seed, seconds=6.0):
    """Every request of a fixed plan, all due at once, served to its end:
    the engine's steps do not depend on the wall clock."""
    from chipbench import harness, loadgen
    model = harness.model_of(config)
    engine = harness.build_engine(config, model.arch, seed)
    harness.warm_up(engine)
    plan = [loadgen.Planned(seed * 100 + i, 0.0, n, m) for i, (n, m) in
            enumerate([(40, 12), (7, 20), (70, 6), (33, 9), (1, 15),
                       (64, 4)])]
    window = harness.run_window(engine, plan, seconds, model.cost,
                                harness.Spans(False),
                                harness.CompileCounter())
    return model, window


def test_spelled_out_modules_serve_and_check_the_same():
    from chipbench import check
    plain = tiny_spec()["config"]
    spelled = dict(plain, reference="reference.py", flops="flops.py")
    got = []
    for config in (plain, spelled):
        model, window = _served(config, 6)
        assert all(r.finished for r in window.reqs.values())
        picked = check.sample(window.reqs.values(), 6, 8192, 32)
        got.append((model, {i: r.tokens for i, r in window.reqs.items()},
                    check.readings(model.reference(model.arch, 6), picked),
                    [s.flops for s in window.steps]))
    (m0, tok0, r0, f0), (m1, tok1, r1, f1) = got
    assert vars(m0.arch) == vars(m1.arch)
    assert tok0 == tok1
    assert r0 == r1 and r0["compared_tokens"] > 0
    assert f0 == f1


def test_module_found_by_path_under_the_benchmark(tmp_path):
    from chipbench import harness
    base = tmp_path / "bench"
    (base / "refs").mkdir(parents=True)
    shutil.copy(ROOT / "chipbench/reference.py", base / "refs/other.py")
    shutil.copy(ROOT / "chipbench/flops.py", base / "flops.py")
    config = dict(tiny_spec()["config"], reference="refs/other.py")
    model = harness.model_of(config, base)
    assert model.reference.__module__ == "bench.refs.other"
    assert model.arch.n_experts == 8
    for bad in ("../reference.py", "/etc/hosts", "refs/missing.py",
                "refs"):
        with pytest.raises(ValueError, match="no Python file"):
            harness.model_of(dict(config, reference=bad), base)


def test_a_process_holds_one_copy_of_each_module():
    """The default modules are the package's own, and a second cell built
    in the process (as ``calibrate.py`` builds one a seed) loads none
    again: its reference's programs stay compiled."""
    import chipbench.flops
    import chipbench.reference
    from chipbench import harness
    config = tiny_spec()["config"]
    first, again = harness.model_of(config), harness.model_of(dict(config))
    assert first.reference is again.reference \
        is chipbench.reference.Reference
    assert type(first.cost) is type(again.cost) \
        is chipbench.flops.TokenCost


def test_no_controller_passes_the_check():
    """``"policy": null`` with the other module and program keys spelled
    out: the engine runs with no controller and the check holds."""
    from chipbench import run
    spec = tiny_spec(mean=MEAN_LIMIT)
    spec["config"] = dict(
        spec["config"], reference="reference.py", flops="flops.py",
        program={"dtype": "bfloat16"},
        engine={"policy": None, "scheduler": "fcfs", "prefill_chunk": 32,
                "max_batch": 4, "max_seq": 128})
    seen = []
    res = run.run_cell(spec, 2, 4.0, False, log=lambda s: None,
                       engine_hook=seen.append)
    assert seen[0].controller is None and seen[0].cluster is None
    assert res["correct"], res["checks"]
    assert res["checks"]["compared_tokens"]["value"] >= 16


def test_program_keys_lie_over_the_derived_ones():
    from chipbench import harness
    config = dict(tiny_spec()["config"], program={"norm_eps": 1e-5})
    model = harness.model_of(config)
    cfg = harness.arch_config(config, model.arch)
    assert cfg.norm_eps == 1e-5
    assert cfg.n_experts == 8 and cfg.family == "moe"


def test_unknown_program_key_fails_at_build():
    from chipbench import harness
    config = dict(tiny_spec()["config"], program={"qk_norm": True})
    model = harness.model_of(config)
    with pytest.raises(ValueError, match="qk_norm"):
        harness.build_engine(config, model.arch, 1)


def test_granite_builds_the_same_program_and_count():
    """The cell's configuration, which names no module and no program
    key, gives the ArchConfig and FLOP count the harness always built."""
    from chipbench import harness
    from chipbench.flops import token_cost
    from chipbench.reference import arch_from_config
    from repro.configs.base import ArchConfig
    config = json.loads(GRANITE.read_text())
    assert not {"reference", "flops", "program"} & set(config)
    model = harness.model_of(copy.deepcopy(config))
    assert harness.arch_config(config, model.arch) == ArchConfig(
        name="granite-moe-3b-a800m", family="moe", n_layers=32, d_model=1536,
        n_heads=24, n_kv_heads=8, d_ff=0, vocab=49155, head_dim=64,
        n_experts=40, top_k=8, moe_d_ff=512, moe_every=1, mlp_gated=True,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=True, source="")
    assert vars(model.cost) == vars(token_cost(arch_from_config(
        config["model"])))
    assert model.cost.fixed == 1614544896


@pytest.mark.parametrize("spans", [True, False], ids=["spans", "no_spans"])
def test_traced_run_turns_the_program_spans_on_or_off(spans, tmp_path,
                                                      monkeypatch):
    """A traced run (``run.py --trace 1``, ``layers.py --spans``) has the
    program's step spans on in its window alone, or off throughout."""
    from chipbench import run
    from repro.serving import tracing
    monkeypatch.setattr(run, "OUT", tmp_path)
    seen = set()

    def hook(engine):
        step = engine.step

        def watched():
            seen.add(tracing._on)
            return step()
        engine.step = watched
    res = run.run_cell(tiny_spec(mean=MEAN_LIMIT), 3, 2.0, True,
                       log=lambda s: None, engine_hook=hook, detail=True,
                       step_spans=spans)
    assert seen == ({False, True} if spans else {False})
    assert not tracing._on
    assert res["correct"], res["checks"]
    assert "readings" in res
