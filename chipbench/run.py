"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload granite-3b.chat --seed 7 \\
        --seconds 45 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration file
and a traffic mix under ``chipbench/``; the metrics it reports are the
entries of ``end_to_end`` (``--trace 0``) or ``per_layer`` (``--trace 1``)
that list it, each read by ``chipbench/metrics/<name>.py``. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also ``busy_s``,
``window_s`` and a ``breakdown``) and, last, ``checks``: each number
compared with its limit. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: fixed paths inside the checkout (git-ignored)
OUT = ROOT / ".chipbench_out"

__all__ = ["RunView", "load_cell", "run_cell", "main"]


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


class RunView:
    """What one window measured, as the metric readers see it."""

    def __init__(self, window, setup_s: float, peak_flops: float,
                 trace: Optional[dict]):
        self.window_s = window.t1 - window.t0
        self.setup_s = setup_s
        self.peak_flops = peak_flops
        self.trace = trace
        self.steps = window.steps
        reqs = [r for r in window.reqs.values() if r.submitted is not None
                and not r.rejected]
        self.tokens_out = sum(sum(1 for t in r.token_times if t <= window.t1)
                              for r in reqs)
        self.ttft_ms: List[float] = []
        self.tpot_ms: List[float] = []
        self.lag_ms: List[float] = []
        for r in window.reqs.values():
            if r.rejected or r.due > window.t1:
                continue
            first = r.token_times[0] if r.token_times else window.t1
            self.ttft_ms.append(1e3 * (first - r.due))
            if len(r.token_times) >= 2:
                self.tpot_ms.append(1e3 * (r.token_times[-1]
                                           - r.token_times[0])
                                    / (len(r.token_times) - 1))
            if r.submitted is not None:
                self.lag_ms.append(1e3 * (r.submitted - r.due))

    def step_ms(self, kind: str) -> Optional[float]:
        d = [s.t1 - s.t0 for s in self.steps if s.kind == kind]
        return 1e3 * sum(d) / len(d) if d else None

    def mfu_of(self, kind: str) -> Optional[float]:
        sel = [s for s in self.steps if s.kind == kind]
        t = sum(s.t1 - s.t0 for s in sel)
        f = sum(s.flops for s in sel)
        return 100.0 * f / (t * self.peak_flops) if t > 0 and f else None


def _reader(name: str, root: Path = ROOT):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """Everything a cell names, found by name: its configuration file,
    traffic mix, limits, and the metrics that list it."""
    from chipbench import loadgen
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "mix": loadgen.load_mix(root / "chipbench" / "traffic"
                                / f"{cell['traffic']}.json"),
        "limits": json.loads((root / "chipbench" / "limits"
                              / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def _judge(readings: Dict[str, float], limits: dict):
    """Each number that the cell's limits file compares, beside its limit
    (``limit``: at most; ``min``: at least), and whether all hold. A limit
    that is null has not been set from readings yet, and nothing passes
    it."""
    checks, ok = {}, True
    for name, lim in limits["compared"].items():
        v = readings[name]
        checks[name] = {"value": v, **{k: lim[k] for k in ("limit", "min")
                                       if k in lim}}
        for key, holds in (("limit", lambda b: v <= b),
                           ("min", lambda b: v >= b)):
            if key in lim:
                ok = ok and lim[key] is not None and holds(lim[key])
    return bool(ok), checks


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, log=print, control: bool = False,
             engine_hook=None, with_readings: bool = False) -> dict:
    """Set up, measure one window, check the served tokens; the result.

    ``control`` also reads the float8 control on the same sample;
    ``control`` or ``with_readings`` put every reading of the check under
    ``"readings"``; ``engine_hook(engine)`` may alter the engine before
    the window. None is used by a benchmark run."""
    import jax
    from chipbench import check, flops, harness, loadgen
    from chipbench import trace as trace_mod
    from chipbench.reference import Reference, arch_from_config

    config, mix, limits = spec["config"], spec["mix"], spec["limits"]
    arch = arch_from_config(config["model"])
    weight_seed = seed % (1 << 31)
    t_build = time.perf_counter()
    engine = harness.build_engine(config, arch, weight_seed)
    if engine_hook is not None:
        engine_hook(engine)
    counter = harness.CompileCounter()
    t_warm = time.perf_counter()
    harness.warm_up(engine)
    log(f"[setup] start to build {t_build - t_start:.1f}s, engine built in "
        f"{t_warm - t_build:.1f}s, warmed up in "
        f"{time.perf_counter() - t_warm:.1f}s")
    plan = loadgen.plan_requests(mix, seed, seconds,
                                 id_base=weight_seed * (1 << 20) + 1)
    dev = jax.devices()[0]
    peak = flops.peak_flops(dev.device_kind) if dev.platform == "tpu" \
        else float("nan")
    spans = harness.Spans(trace)
    trace_dir = OUT / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start
    if trace:
        with jax.profiler.TraceAnnotation("window"):
            window = harness.run_window(engine, plan, seconds, arch, spans,
                                        counter)
        jax.profiler.stop_trace()
    else:
        window = harness.run_window(engine, plan, seconds, arch, spans,
                                    counter)
    stats = engine.stats
    log(f"[window] {window.t1 - window.t0:.3f}s: {len(window.steps)} steps "
        f"({sum(s.kind == 'chunk' for s in window.steps)} chunks), "
        f"migrations so far {stats.migrations}; compiles in the window "
        f"{window.compiles}; expert slots per layer {engine.n_slots}")
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    del engine, stats
    gc.collect()
    reduced = None
    if trace:
        path = trace_mod.find_trace(trace_dir)
        t0 = time.perf_counter()
        reduced = trace_mod.reduce_trace(path) if path else None
        log(f"[trace] {path} ({path.stat().st_size if path else 0} B) "
            f"reduced in {time.perf_counter() - t0:.1f}s")
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = RunView(window, setup_s, peak, reduced)
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in entries:
        v = _reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # the served tokens against the reference, after the program is freed
    t0 = time.perf_counter()
    picked = check.sample(window.reqs.values(), seed,
                          limits["sample_tokens"], limits["sample_requests"])
    readings = check.readings(Reference(arch, weight_seed), picked,
                              ("f32", "fp8") if control else ("f32",))
    ok, checks = _judge(readings, limits)
    log(f"[check] {len(picked)} requests, reference "
        f"{time.perf_counter() - t0:.1f}s; widest gap "
        f"{readings['max_logit_gap']!r} (read, not compared)")
    submitted = [r for r in window.reqs.values() if r.submitted is not None]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(ok), "attempted": len(submitted),
              "failed": sum(r.rejected for r in submitted),
              "metrics": metrics, "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control or with_readings:
        result["readings"] = readings
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    _paths()
    spec = load_cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                     args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    chips = spec["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    print(f"[device] {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{cache}", file=sys.stderr, flush=True)
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      log=lambda s: print(s, file=sys.stderr, flush=True))
    for name, c in result["checks"].items():
        bound = ", ".join(f"{k} {v!r}" for k, v in c.items() if k != "value")
        print(f"[checks] {name} {c['value']!r} ({bound})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    _paths()
    raise SystemExit(main())
