"""Traffic mixes are data: found by name, the same work for every seed."""

import json
import shutil
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def test_same_sizes_other_order():
    from chipbench import loadgen
    mix = loadgen.load_mix(ROOT / "chipbench/traffic/chat.json")
    a = loadgen.plan_requests(mix, 2147483901, 45.0, id_base=0)
    b = loadgen.plan_requests(mix, 7, 45.0, id_base=0)
    again = loadgen.plan_requests(mix, 7, 45.0, id_base=0)
    assert a != b and b == again
    assert len(a) == len(b) == round(mix["arrival"]["rate"] * 45.0)
    for f in ("prompt_len", "output_len"):
        assert sorted(getattr(p, f) for p in a) == sorted(getattr(p, f)
                                                         for p in b)
    assert all(0 < p.due < 45.0 for p in a + b)
    assert all(p.output_len >= 2 and p.prompt_len <= 768
               and p.prompt_len + p.output_len <= 1024 for p in a)
    assert np.isclose(max(p.due for p in a), max(p.due for p in b))
    # each block of positions holds the same work whatever the seed
    k = mix["block"]
    for i in range(0, len(a), k):
        assert (sorted(p.output_len for p in a[i:i + k])
                == sorted(p.output_len for p in b[i:i + k]))
        assert np.isclose(a[min(i + k, len(a)) - 1].due,
                          b[min(i + k, len(b)) - 1].due)


def test_new_traffic_file_is_found_by_name(tmp_path):
    """A cell added with nothing but data: a throwaway traffic file and an
    entry in BENCHMARK.json; no harness code names it."""
    from chipbench import run
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "chipbench/traffic/throwaway.json").write_text(json.dumps({
        "arrival": {"process": "poisson", "rate": 0.5},
        "prompt": {"mean": 64, "cv": 0.5, "min": 8, "max": 128},
        "output": {"mean": 8, "cv": 0.5, "min": 2},
        "max_total": 256, "population_seed": 3, "block": 5}))
    (tmp_path / "chipbench/limits/granite-3b.throwaway.json").write_text(
        (ROOT / "chipbench/limits/granite-3b.chat.json").read_text())
    bench["workloads"].append({"name": "granite-3b.throwaway",
                               "config": "granite-moe-3b-a800m",
                               "traffic": "throwaway", "chips": 1,
                               "why": "test"})
    spec = run.load_cell(bench, "granite-3b.throwaway", root=tmp_path)
    assert spec["mix"]["arrival"]["rate"] == 0.5
    assert spec["config"]["model"]["num_local_experts"] == 40
    names = {m["name"] for m in spec["end_to_end"]}
    assert names == {"output_tok_s", "setup_s"}
    from chipbench import loadgen
    plan = loadgen.plan_requests(spec["mix"], 11, 10.0, id_base=0)
    assert len(plan) == 5 and all(p.prompt_len <= 128 for p in plan)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run._reader(m["name"], root=tmp_path))
