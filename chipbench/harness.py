"""Drives the served path from the client's side and records what it saw.

Set-up builds the engine from a configuration file through the program's
public constructors and runs a few requests of the cell's own shapes. The
window then offers the planned requests when they are due on the wall
clock (``Engine.submit``), calls ``Engine.step`` in a loop, sleeps only when
the engine is idle, and reads each lane's token back to the host after the
step that produced it, as a streaming server would.

Every call into a layer is a span of its own (``generator.submit``,
``engine.step``, ``client.readback``, ``idle.wait``); with tracing on the
spans are also written into the profiler's trace, on its clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import flops, loadgen
from chipbench.reference import Arch

__all__ = ["Spans", "StepRecord", "Window", "build_engine", "warm_up",
           "run_window"]

#: request ids of warm-up requests; the window's start above them
_WARM_BASE = 1 << 40


class Spans:
    """(name, start, end) on the host clock, in seconds."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.items: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(name) if self.trace
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.items.append((name, t0, time.perf_counter()))


@dataclasses.dataclass
class StepRecord:
    kind: str                 # "chunk" | "decode"
    t0: float
    t1: float
    flops: float              # model FLOPs (chipbench.flops)


@dataclasses.dataclass
class ReqTrace:
    req_id: int
    due: float                # absolute host time
    prompt_len: int
    output_len: int
    submitted: Optional[float] = None
    rejected: bool = False
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    reqs: Dict[int, ReqTrace]
    steps: List[StepRecord]
    spans: Spans
    compiles: Dict[str, int]


def arch_config(model: dict, arch: Arch, name: str):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="moe", n_layers=arch.n_layers,
        d_model=arch.d_model, n_heads=arch.n_heads,
        n_kv_heads=arch.n_kv_heads, d_ff=0, vocab=arch.vocab,
        head_dim=arch.head_dim, n_experts=arch.n_experts, top_k=arch.top_k,
        moe_d_ff=arch.d_expert, moe_every=1, mlp_gated=True,
        rope_theta=arch.rope_theta, norm_eps=arch.norm_eps,
        tie_embeddings=arch.tied, source=model.get("source", ""))


def build_engine(config: dict, arch: Arch, weight_seed: int):
    """The engine of a configuration file, weights drawn from the seed."""
    from repro.core import (DriftConfig, ViBEConfig, ViBEController,
                            get_policy, make_cluster)
    from repro.launch.serve import derive_slot_budget
    from repro.serving import Engine, EngineConfig, SchedulerConfig

    eng = config["engine"]
    cfg = arch_config(config["model"], arch, config["name"])
    n_moe, n_slots = cfg.n_layers, cfg.n_experts
    ranks = eng["ranks"]
    cluster = make_cluster(ranks, eng["regime"], d_model=cfg.d_model,
                           d_ff=cfg.moe_d_ff,
                           experts_per_rank=max(n_slots // ranks, 1),
                           seed=eng["cluster_seed"])
    expert_bytes = 3 * cfg.d_model * cfg.moe_d_ff * 2
    budget = None
    if get_policy(eng["policy"]).capabilities.accepts_slot_budget:
        # "auto" sizes slots from free device memory, which no cell may
        # leave to chance: a cell states its budget ("default" or a number)
        if eng["slots_per_rank"] == "auto":
            raise ValueError(f"{config['name']}: slots_per_rank must be "
                             "'default' or a number of slots per rank")
        budget = derive_slot_budget(ranks, cfg.n_experts,
                                    expert_bytes * n_moe, 0,
                                    eng["slots_per_rank"])
    controller = ViBEController(
        n_moe, n_slots, ranks, cluster.fit_models(),
        ViBEConfig(policy=eng["policy"], adaptive=True,
                   drift=DriftConfig(**eng["drift"]),
                   expert_bytes=expert_bytes, slot_budget=budget))
    econfig = EngineConfig(
        max_batch=eng["max_batch"], max_seq=eng["max_seq"], moe_impl="ragged",
        seed=weight_seed, weighted_routing=True,
        scheduler=SchedulerConfig(name=eng["scheduler"],
                                  prefill_chunk=eng["prefill_chunk"]))
    return Engine(cfg, econfig, controller=controller, cluster=cluster)


def _request(p: loadgen.Planned, arrival: float):
    from repro.serving.workload import Request
    return Request(p.req_id, arrival, p.prompt_len, p.output_len)


def warm_up(engine, n_requests: int = 2) -> None:
    """Run a few requests of the cell's shapes: one prefill-chunk width,
    one decode batch, and the host read-back of tokens."""
    chunk = engine.config.scheduler.prefill_chunk
    plan = [loadgen.Planned(_WARM_BASE + i, 0.0, max(chunk // 2, 1), 4)
            for i in range(n_requests)]
    engine.submit([_request(p, 0.0) for p in plan])
    while engine.step():
        np.asarray(engine.tokens)


class CompileCounter:
    """Counts JAX compile events while armed."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "backend",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
              "/jax/core/compile/jaxpr_trace_duration": "trace"}

    def __init__(self):
        self.armed = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def run_window(engine, plan: List[loadgen.Planned], seconds: float,
               arch: Arch, spans: Spans, counter: CompileCounter) -> Window:
    cost = flops.token_cost(arch)
    st = engine.stats
    B = engine.max_batch
    t0 = time.perf_counter()
    end = t0 + seconds
    reqs = {p.req_id: ReqTrace(p.req_id, t0 + p.due, p.prompt_len,
                               p.output_len) for p in plan}
    steps: List[StepRecord] = []
    nxt = 0
    counter.armed = True
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        batch = []
        while nxt < len(plan) and t0 + plan[nxt].due <= now:
            batch.append(plan[nxt])
            nxt += 1
        if batch:
            with spans.span("generator.submit"):
                rejected = engine.submit([_request(p, p.due) for p in batch])
            t_sub = time.perf_counter()
            for p in batch:
                reqs[p.req_id].submitted = t_sub
            for rec in rejected:
                reqs[rec.req_id].rejected = True
        before = list(engine.slot_req)
        pos_before = np.array(engine.pos)
        c0, d0 = st.chunk_steps, st.decode_steps
        p0, tok0 = st.prefill_steps, st.prefill_tokens
        with spans.span("engine.step"):
            t_s = time.perf_counter()
            busy = engine.step()
            t_e = time.perf_counter()
        if not busy:
            wake = end if nxt >= len(plan) else min(end, t0 + plan[nxt].due)
            with spans.span("idle.wait"):
                time.sleep(max(wake - time.perf_counter(), 0.0))
            continue
        after = engine.slot_req
        produced = []                              # (lane, req_id, last)
        if st.decode_steps > d0:
            active = [b for b in range(B) if before[b] is not None]
            ctx = int(sum(pos_before[b] + 1 for b in active))
            f = cost.tokens(len(active), ctx) + cost.unembed * len(active)
            steps.append(StepRecord("decode", t_s, t_e, f))
            produced = [(b, before[b].req_id, after[b] is not before[b])
                        for b in active]
        elif st.chunk_steps > c0:
            n_valid = st.prefill_tokens - tok0
            moved = [b for b in range(B) if engine.pos[b] != pos_before[b]
                     or after[b] is not before[b]]
            # the prefilling lane's position is parked at its prefilled
            # count; where that left it unmoved, count the chunk from 0
            end_pos = int(engine.pos[moved[0]]) if moved else n_valid
            first = st.prefill_steps > p0
            ctx = n_valid * (end_pos - n_valid) + n_valid * (n_valid + 1) // 2
            f = cost.tokens(n_valid, ctx) + (cost.unembed if first else 0)
            steps.append(StepRecord("chunk", t_s, t_e, f))
            lane = next((b for b in range(B) if after[b] is not None
                         and after[b] is not before[b]), None)
            if first and lane is not None:
                produced = [(lane, after[lane].req_id, False)]
        if produced:
            with spans.span("client.readback"):
                toks = np.asarray(engine.tokens)[:, 0]
            t_tok = time.perf_counter()
            for b, rid, last in produced:
                rt = reqs.get(rid)
                if rt is None:
                    continue
                rt.tokens.append(int(toks[b]))
                rt.token_times.append(t_tok)
                rt.finished = rt.finished or last
    t1 = time.perf_counter()
    counter.armed = False
    return Window(t0, t1, reqs, steps, spans, dict(counter.counts))
