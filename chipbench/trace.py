"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:`` plane; the host's spans are the harness's own annotations on
the host plane, on the same clock. Busy is the union of operation
intervals; idle is the rest of the window (the ``window`` span).

* ``busy_s`` / ``window_s`` -- busy seconds in the window, averaged over
  the chips that ran anything, and the window's length;
* ``step_idle_pct`` -- share of the time inside ``engine.step`` spans in
  which no operation ran on the device;
* ``device_ops`` -- the ten operation names that took the most device time;
* ``idle_gaps`` -- idle seconds in the window by the harness span open at
  the time (``engine.step``, ``generator.submit``, ``client.readback``,
  ``idle.wait``; ``none`` between spans), most first.
"""

from __future__ import annotations

import collections
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["find_trace", "reduce_trace"]

SPANS = ("engine.step", "generator.submit", "client.readback", "idle.wait")
WINDOW = "window"


def find_trace(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (n, 2) [start, end) intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = ends[np.append(idx[1:] - 1, len(iv) - 1)]
    return np.stack([starts, stops], axis=1)


class _Busy:
    """Covered length of any [a, b) under a sorted disjoint union."""

    def __init__(self, union: np.ndarray):
        self.s, self.e = union[:, 0], union[:, 1]
        self.cum = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def upto(self, t):
        t = np.asarray(t, np.float64)
        k = np.searchsorted(self.s, t, side="right") - 1
        kk = np.maximum(k, 0)
        part = np.clip(t - self.s[kk], 0.0, (self.e - self.s)[kk]) \
            if len(self.s) else np.zeros_like(t)
        return np.where(k >= 0, self.cum[kk] + part, 0.0)

    def covered(self, a, b) -> np.ndarray:
        return np.maximum(self.upto(b) - self.upto(a), 0.0)


def _events(pd) -> Tuple[Dict[str, np.ndarray], Dict[str, List]]:
    """Per device plane, its op intervals (ns); host spans by name."""
    dev: Dict[str, list] = collections.defaultdict(list)
    names: Dict[str, list] = collections.defaultdict(list)
    host: Dict[str, list] = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    dev[plane.name].append((s, s + d))
                    # "%fusion.3 = bf16[...] fusion(...)": keep the op's name
                    names[ev.name.split(" = ", 1)[0]].append(d)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS or ev.name == WINDOW:
                        s = ev.start_ns
                        host[ev.name].append((s, s + ev.duration_ns))
    ops = {k: np.asarray(v, np.float64) for k, v in dev.items()}
    return ops, {"spans": host, "op_ns": names}


def reduce_trace(path: Path) -> Optional[dict]:
    """The reduction above, or None where the trace holds no device op."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, info = _events(pd)
    spans = info["spans"]
    ops = {k: v for k, v in ops.items() if len(v)}
    if not ops:
        return None
    if spans.get(WINDOW):
        w0 = min(s for s, _ in spans[WINDOW])
        w1 = max(e for _, e in spans[WINDOW])
    else:
        w0 = min(float(v[:, 0].min()) for v in ops.values())
        w1 = max(float(v[:, 1].max()) for v in ops.values())
    busy_of = {k: _Busy(_union(v)) for k, v in ops.items()}
    per_chip = {k: float(b.covered(w0, w1)) for k, b in busy_of.items()}
    busy = float(np.mean(list(per_chip.values())))
    # the step's idle share and the idle gaps are read on the busiest chip
    main = max(per_chip, key=per_chip.get)
    b = busy_of[main]
    steps = np.asarray(spans.get("engine.step", []), np.float64).reshape(-1, 2)
    step_len = float(np.sum(steps[:, 1] - steps[:, 0]))
    step_busy = float(np.sum(b.covered(steps[:, 0], steps[:, 1])))
    # idle gaps inside the window, each labelled by the span around it
    starts = np.concatenate([[w0], np.clip(b.e, w0, w1)])
    stops = np.concatenate([np.clip(b.s, w0, w1), [w1]])
    keep = stops > starts
    g0, g1 = starts[keep], stops[keep]
    labelled = sorted((s_, e_, n) for n in SPANS for s_, e_ in spans.get(n, []))
    names = list(SPANS) + ["none"]
    lab = np.full(len(g0), len(SPANS))
    if labelled:
        ss = np.asarray([x[0] for x in labelled], np.float64)
        se = np.asarray([x[1] for x in labelled], np.float64)
        sn = np.asarray([SPANS.index(x[2]) for x in labelled])
        mid = 0.5 * (g0 + g1)
        i = np.searchsorted(ss, mid, side="right") - 1
        ok = (i >= 0) & (se[np.maximum(i, 0)] >= mid)
        lab = np.where(ok, sn[np.maximum(i, 0)], len(SPANS))
    idle = np.bincount(lab, weights=(g1 - g0) * 1e-9, minlength=len(names))
    by_label = {names[j]: float(idle[j]) for j in range(len(names))
                if idle[j] > 0}
    op_time = sorted(((n, float(np.sum(d)) * 1e-9)
                      for n, d in info["op_ns"].items()),
                     key=lambda x: -x[1])
    return {
        "busy_s": busy * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "step_idle_pct": (100.0 * (step_len - step_busy) / step_len
                          if step_len > 0 else None),
        "device_ops": [[n, t] for n, t in op_time[:10]],
        "idle_gaps": [[n, t] for n, t in
                      sorted(by_label.items(), key=lambda x: -x[1])[:10]],
    }
