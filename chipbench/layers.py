"""Per-layer reduction of a profiler trace, and a traced run of one cell.

``chipbench/trace.py`` reads device busy and idle time against the
harness's spans. This reads what the program's own names add: the XLA
module of each device op (``jit_decode_step``, ``jit_prefill_chunk``,
``jit_sample``), its named-scope path (``tf_op``, through
``chipbench/xplane.py``), and the ``step.*`` host spans of
``repro.serving.tracing`` inside ``engine.step``.

* ``modules`` -- per module name, [executions, device seconds] in the
  window (its ``XLA Modules`` events);
* ``scopes`` -- per module name, device seconds under each of
  :data:`SCOPES`: the union of the intervals of the ops whose path holds
  the scope. A container op (the layer scan's ``while``) holds no scope,
  so nothing counts twice. ``unscoped``: the module's time outside every
  scope;
* ``top_ops`` -- per module name, its eight ops with the most device
  time: [name, path, seconds] (a container counts with what it holds);
* ``program_spans`` -- per phase span, [count, seconds];
* ``idle_gaps`` -- idle seconds in the window by the innermost span open
  at the time, split exactly at span ends (``trace.py`` gives each gap
  whole to the span around its midpoint); ``engine.step`` keeps the idle
  time no phase span covers;
* ``layers`` -- ``decode_device_ms`` (mean device time of a
  ``decode_step`` execution), ``decode_moe_ms`` / ``decode_attn_ms``
  (device time under ``moe`` / ``attention`` in ``decode_step``, per
  execution), ``control_ms`` (mean self time of ``step.observe``: its
  span less its ``step.migrate`` children) and ``migrate_pct``
  (``step.migrate`` time over ``engine.step`` time).

Device and host times are read on the busiest chip, as ``trace.py`` does.

One cell, traced as ``run.py --trace 1`` runs it, with the program's
spans on (``--spans 1``, as the benchmark has them) or off::

    python3 chipbench/layers.py --workload granite-3b.chat --seed 7 \\
        --seconds 50 --spans 1

prints ``run.py``'s result line (its ``per_layer`` metrics, the check)
with the check's readings and this whole reduction under ``layers``.
"""

from __future__ import annotations

import collections
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import xplane  # noqa: E402
from chipbench.trace import SPANS, WINDOW, _Busy, _union  # noqa: E402

__all__ = ["SCOPES", "PHASES", "reduce_layers", "queue_wait_ms", "main"]

SCOPES = ("embed", "attention", "moe", "moe/router", "moe/dispatch",
          "moe/ffn", "moe/combine", "unembed", "sample")
PHASES = ("step.schedule", "step.admit", "step.launch", "step.sync",
          "step.observe", "step.migrate", "step.finish")
DECODE = "jit_decode_step"
_MODULE = re.compile(r"(.*)\((\d+)\)")


def _holds(tf_op: str, scope: str) -> bool:
    return f"/{scope}/" in f"/{tf_op}"


def _read(path: Path):
    """Per device plane its ops (start, end ns, name) and module runs
    (start, end ns, name, program id); host spans by name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[str, list] = collections.defaultdict(list)
    mods: Dict[str, list] = collections.defaultdict(list)
    host: Dict[str, list] = collections.defaultdict(list)
    named = set(SPANS) | set(PHASES) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [(ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         ev.name) for ev in line.events]
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        m = _MODULE.fullmatch(ev.name)
                        if m:
                            mods[plane.name].append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns,
                                 m.group(1), int(m.group(2))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in named:
                        host[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, mods, host


def _paths(meta: Dict, keys: List[tuple]) -> List[str]:
    """The ``tf_op`` of each (program, op name); an op whose program is not
    known takes the one path its name has, else :data:`xplane.OTHER`."""
    by_name: Dict[str, set] = collections.defaultdict(set)
    for (_, op), path in meta.items():
        by_name[op].add(path)
    out = []
    for pid, op in keys:
        path = meta.get((pid, op))
        if path is None:
            paths = by_name.get(op, {""})
            path = next(iter(paths)) if len(paths) == 1 else xplane.OTHER
        out.append(path)
    return out


def _span_stats(host, w0, w1) -> Dict[str, np.ndarray]:
    out = {}
    for name, iv in host.items():
        a = np.asarray(iv, np.float64).reshape(-1, 2)
        out[name] = a[(a[:, 0] >= w0) & (a[:, 1] <= w1)]
    return out


def _idle_gaps(busy: _Busy, spans: Dict[str, np.ndarray], w0, w1) -> dict:
    """Idle seconds of the window by the innermost span open at the time:
    the window is cut at every span's ends, and each piece's idle time
    goes to the innermost span that holds it (``none`` outside all)."""
    names = [n for n in list(SPANS) + list(PHASES) if n in spans]
    iv = sorted(((s, e, j) for j, n in enumerate(names)
                 for s, e in spans[n]), key=lambda x: (x[0], -x[1]))
    cuts = np.unique(np.concatenate(
        [[w0, w1], np.asarray([x[:2] for x in iv], np.float64).ravel()]))
    lab = np.full(len(cuts) - 1, len(names))              # "none"
    # spans nest on the one host thread: in order of start (the outer
    # first where two start together), a later span lies inside the
    # earlier one that holds it, so it overwrites that one's label
    for s, e, j in iv:
        lo, hi = np.searchsorted(cuts, [s, e])
        lab[lo:hi] = j
    idle = (cuts[1:] - cuts[:-1]) - busy.covered(cuts[:-1], cuts[1:])
    total = np.bincount(lab, weights=np.maximum(idle, 0.0) * 1e-9,
                        minlength=len(names) + 1)
    labels = names + ["none"]
    return {labels[j]: float(total[j]) for j in range(len(labels))
            if total[j] > 0}


def reduce_layers(path: Path) -> Optional[dict]:
    """The reduction above, or None where the trace holds no device op."""
    ops, mods, host = _read(path)
    ops = {k: v for k, v in ops.items() if v}
    if not ops:
        return None
    meta = xplane.op_scopes(path)
    if host.get(WINDOW):
        w0 = min(s for s, _ in host[WINDOW])
        w1 = max(e for _, e in host[WINDOW])
    else:
        w0 = min(s for v in ops.values() for s, _, _ in v)
        w1 = max(e for v in ops.values() for _, e, _ in v)
    iv = {k: np.asarray([(s, e) for s, e, _ in v], np.float64)
          for k, v in ops.items()}
    busy_of = {k: _Busy(_union(v)) for k, v in iv.items()}
    main = max(busy_of, key=lambda k: float(busy_of[k].covered(w0, w1)))
    spans = _span_stats(host, w0, w1)

    # modules: executions that start in the window, clipped to it
    runs = sorted(mods.get(main, []))
    r_s = np.asarray([r[0] for r in runs], np.float64)
    r_e = np.asarray([r[1] for r in runs], np.float64)
    modules: Dict[str, list] = {}
    for (s, e, name, _), in_w in zip(runs, (r_s >= w0) & (r_s < w1)):
        if in_w:
            m = modules.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += (min(e, w1) - s) * 1e-9

    # each op's module: the run around its start
    a = np.clip(iv[main], w0, w1)
    inside = a[:, 1] > a[:, 0]
    names = [op[2] for op, k in zip(ops[main], inside) if k]
    a = a[inside]
    k = np.searchsorted(r_s, a[:, 0], side="right") - 1
    ok = (k >= 0) & (a[:, 0] < r_e[np.maximum(k, 0)])
    run_name = np.asarray([r[2] for r in runs] + [""], object)
    run_pid = np.asarray([r[3] for r in runs] + [0], np.uint64)
    k = np.where(ok, k, len(runs))
    mod_of, pid_of = run_name[k], run_pid[k]
    # one path per distinct (program, op name)
    keys: Dict[tuple, int] = {}
    uid = np.asarray([keys.setdefault((int(p), n), len(keys))
                      for p, n in zip(pid_of.tolist(), names)], np.int64)
    key_list = list(keys)
    paths = _paths(meta.get(main, {}), key_list)
    holds = np.asarray([[_holds(p, s) for s in SCOPES] for p in paths],
                       bool).reshape(-1, len(SCOPES))
    scopes: Dict[str, Dict[str, float]] = {}
    top_ops: Dict[str, list] = {}
    dur = a[:, 1] - a[:, 0]
    for mod in sorted(set(mod_of.tolist()) - {""}):
        sel = mod_of == mod
        row = {}
        for j, scope in enumerate(SCOPES):
            m = sel & holds[uid, j]
            if m.any():
                u = _union(a[m])
                row[scope] = float(np.sum(u[:, 1] - u[:, 0])) * 1e-9
        if row and mod in modules:
            u = _union(a[sel & holds[uid].any(axis=1)])
            row["unscoped"] = max(modules[mod][1] - float(
                np.sum(u[:, 1] - u[:, 0])) * 1e-9, 0.0)
            scopes[mod] = row
        per_op = np.bincount(uid[sel], weights=dur[sel],
                             minlength=len(keys))
        top_ops[mod] = [[key_list[i][1].split(" = ", 1)[0], paths[i],
                         float(per_op[i]) * 1e-9]
                        for i in np.argsort(-per_op)[:8] if per_op[i] > 0]

    program_spans = {n: [int(len(spans[n])),
                         float(np.sum(spans[n][:, 1] - spans[n][:, 0])) * 1e-9]
                     for n in PHASES if n in spans and len(spans[n])}
    idle = _idle_gaps(busy_of[main], spans, w0, w1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "modules": modules,
        "scopes": scopes,
        "top_ops": top_ops,
        "program_spans": program_spans,
        "idle_gaps": [[n, t] for n, t in
                      sorted(idle.items(), key=lambda x: -x[1])],
        "layers": _layers(modules, scopes, spans),
    }


def _layers(modules, scopes, spans) -> Dict[str, Optional[float]]:
    out: Dict[str, Optional[float]] = dict.fromkeys(
        ("decode_device_ms", "decode_moe_ms", "decode_attn_ms",
         "control_ms", "migrate_pct"))
    n, dev = modules.get(DECODE, (0, 0.0))
    if n:
        out["decode_device_ms"] = 1e3 * dev / n
        sc = scopes.get(DECODE, {})
        out["decode_moe_ms"] = 1e3 * sc.get("moe", 0.0) / n
        out["decode_attn_ms"] = 1e3 * sc.get("attention", 0.0) / n
    obs = spans.get("step.observe", np.zeros((0, 2)))
    mig = spans.get("step.migrate", np.zeros((0, 2)))
    steps = spans.get("engine.step", np.zeros((0, 2)))
    if len(obs):
        obs = obs[np.argsort(obs[:, 0])]
        j = np.searchsorted(obs[:, 0], mig[:, 0], side="right") - 1
        inner = (j >= 0) & (mig[:, 1] <= obs[np.maximum(j, 0), 1])
        self_ns = np.sum(obs[:, 1] - obs[:, 0]) \
            - np.sum((mig[:, 1] - mig[:, 0])[inner])
        out["control_ms"] = 1e-6 * float(self_ns) / len(obs)
    step_ns = float(np.sum(steps[:, 1] - steps[:, 0]))
    if step_ns > 0 and "step.observe" in spans:
        out["migrate_pct"] = 100.0 * float(np.sum(mig[:, 1] - mig[:, 0])) \
            / step_ns
    return out


def queue_wait_ms(window, records, step_start: Dict[int, float]):
    """p90 over the window's submitted requests of (start of the
    ``engine.step`` call that admitted it - its submit time); one not
    admitted at the window's end enters at its age. None where the
    program keeps no admission step."""
    waits = []
    for r in window.reqs.values():
        if r.submitted is None or r.rejected:
            continue
        rec = records.get(r.req_id)
        if not hasattr(rec, "admitted_step"):
            return None
        t = step_start.get(rec.admitted_step, window.t1)
        waits.append(1e3 * (min(t, window.t1) - r.submitted))
    return float(np.percentile(waits, 90)) if waits else None


def main(argv=None) -> int:
    """``run.py --trace 1`` with the program's spans on or off, and the
    whole reduction above in its line."""
    import argparse
    import json
    import time

    from chipbench import run as run_mod
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    spec = run_mod.start(args.workload)
    if spec is None:
        return 1
    result = run_mod.run_cell(
        spec, args.seed, args.seconds, True, t_start,
        log=lambda s: print(s, file=sys.stderr, flush=True), detail=True,
        step_spans=bool(args.spans))
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "spans": bool(args.spans), **result}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
