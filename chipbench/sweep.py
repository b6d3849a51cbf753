"""Find a cell's knee once, by a sweep of fixed arrival rates on the chip.

    python3 chipbench/sweep.py --workload granite-3b.chat --seed 5 \\
        --seconds 30 --rates 2,3,4,5,6,8

One process builds the cell's engine once and offers its traffic mix at
each rate in turn for ``--seconds``, draining the engine between rates. It
prints one JSON line per rate: the latencies, the tokens per second, and
how many requests due in the window still had no first token at its end
(a queue that grows). The benchmark's own runs never sweep: a cell's rate
is fixed in its traffic file, at about four fifths of the knee.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness, loadgen, run
    spec = run.start(args.workload)
    if spec is None:
        return 1
    import jax
    from chipbench.flops import peak_flops
    model = harness.model_of(spec["config"])
    engine = harness.build_engine(spec["config"], model.arch,
                                  args.seed % (1 << 31))
    counter = harness.CompileCounter()
    harness.warm_up(engine)
    peak = peak_flops(jax.devices()[0].device_kind)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(spec["mix"], arrival=dict(spec["mix"]["arrival"],
                                             rate=rate))
        plan = loadgen.plan_requests(mix, args.seed + i, args.seconds,
                                     id_base=(i + 1) * (1 << 30))
        window = harness.run_window(engine, plan, args.seconds, model.cost,
                                    harness.Spans(False), counter)
        view = run.RunView(window, 0.0, peak, None)
        waiting = sum(1 for r in window.reqs.values() if not r.token_times)
        line = {"rate": rate, "requests": len(plan), "no_first_token": waiting}
        for name in ("ttft_p50_ms", "ttft_p90_ms", "tpot_p90_ms",
                     "output_tok_s"):
            line[name] = run._reader(name)(view)
        line["decode_step_ms"] = view.step_ms("decode")
        line["prefill_chunk_ms"] = view.step_ms("chunk")
        line["compiles"] = window.compiles
        print(json.dumps(line), flush=True)
        t0 = time.perf_counter()
        while engine.step():
            pass
        print(f"[sweep] drained in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
