"""Readings that a cell's limits are set from, with the float8 control.

    python3 chipbench/calibrate.py --workload granite-3b.chat \\
        --seeds 11,12,13 --seconds 20

One process runs the cell's whole path once per seed (new weights, a
window at the cell's own load, the served tokens against the reference)
and reads the control on the same positions. Prints one JSON line per
seed with every reading. Benchmark runs never run the control; the limits
in ``chipbench/limits/<cell>.json`` come from these readings.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args(argv)
    from chipbench import run
    spec = run.load_cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                         args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = run.run_cell(spec, seed, args.seconds, False, t_start=t0,
                           control=bool(args.control), with_readings=True,
                           log=lambda s: print(s, file=sys.stderr,
                                               flush=True))
        r = res["readings"]
        # the control in the program's place, through the same judgement
        control_correct = (run._judge(
            {**r, **{k.replace("control_gap", "logit_gap"): v
                     for k, v in r.items() if "control_gap" in k}},
            spec["limits"])[0] if args.control else None)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": control_correct,
                          "attempted": res["attempted"],
                          "readings": res.get("readings"),
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
