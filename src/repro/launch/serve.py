"""Serving driver: the JAX engine with ViBE end-to-end on real routing.

Brings up a smoke-scale model (``--full``: the published config) in the
continuous-batching engine, profiles the cluster (Alg 1 Phase 1),
computes the initial placement (Phase 2), serves with drift-aware
recalibration (Phase 3) and reports SLO metrics against the virtual clock
(DESIGN.md §4).

The engine side is configured through :class:`EngineConfig`: pick a
scheduler from the registry (``--scheduler slo_edf``), enable chunked
prefill (``--prefill-chunk 12``), size the paged KV block pool
(``--kv-blocks/--block-size``), and feed either a single workload family
or a multi-tenant arrival trace (``--workload bursty``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-235b-a22b \
        --requests 12 --policy vibe --scheduler slo_edf --workload bursty

At published width on one TPU v5e (granite fits the chip whole):

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m \
        --full --policy vibe_r --max-batch 8 --max-seq 2048 \
        --prefill-chunk 256 --requests 8
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Union

import jax
import numpy as np

from repro.configs import get, get_smoke
from repro.core import (DriftConfig, PerfDriftConfig, SCENARIOS, StealConfig,
                        ViBEConfig, ViBEController, default_slots_per_rank,
                        get_policy, make_cluster, make_scenario, parse_topology,
                        registered_policies)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_cache, init_params, moe_perm_shape
from repro.serving import (ChaosReport, Engine, EngineConfig, FaultSchedule,
                           KVCacheConfig, SchedulerConfig, TRACES, WORKLOADS,
                           registered_schedulers, run_chaos,
                           run_with_failure, sample_requests, sample_trace,
                           summarize)

__all__ = ["serve", "derive_slot_budget", "main"]


def derive_slot_budget(n_ranks: int, n_experts: int, slot_bytes: int,
                       reserved_bytes: int = 0,
                       spec: Union[str, int, None] = "auto"):
    """Per-rank physical slot budget from device memory telemetry.

    ``slot_bytes`` is what one physical expert slot costs on the device:
    its w1/w3/w2 over *every* MoE layer. ``reserved_bytes`` is what the
    rest of the engine will hold next to the experts (non-expert
    parameters, the KV cache and the step's fresh output cache).

    ``spec``:

    * ``"auto"``  — read the local device's allocator
      (``jax.Device.memory_stats``). Of the free bytes left after
      ``reserved_bytes``, 80% may hold expert slots plus the transient of
      a migration, which regathers one of the three expert matrices at a
      time (a third of the slots' bytes on top). ``n_ranks`` emulated
      ranks share that room. Devices without memory telemetry (the CPU)
      fall back deterministically to the policy-default budget, so CPU
      runs are identical across hosts.
    * ``"default"`` / ``None`` — policy-default budget (returns None).
    * an integer — uniform per-rank budget, passed through.

    Returns a ``(n_ranks,)`` int array or None (= let the policy choose).
    Raises when even the policy default does not fit the device.
    """
    if spec in (None, "default", ""):
        return None
    if not isinstance(spec, str) or spec.lstrip("-").isdigit():
        return np.full(n_ranks, int(spec), dtype=np.int64)
    if spec != "auto":
        raise ValueError("slots_per_rank must be 'auto', 'default' or an "
                         f"integer, got {spec!r}")
    base = default_slots_per_rank(n_experts, n_ranks)
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        # deterministic CPU fallback: exactly the policy-default budget
        return np.full(n_ranks, base, dtype=np.int64)
    free = int(stats["bytes_limit"]) - int(stats["bytes_in_use"])
    room = 0.8 * (free - reserved_bytes)
    fit = int(room / (n_ranks * slot_bytes * 4 / 3))
    if fit < base:
        raise ValueError(
            f"{n_ranks} ranks x {base} expert slots of {slot_bytes} B do not "
            f"fit: {free} B free, {reserved_bytes} B reserved")
    # clamp to [policy default, E) so the budget always solves
    per_rank = min(fit, max(n_experts - 1, base))
    return np.full(n_ranks, per_rank, dtype=np.int64)


def _resident_bytes(cfg, max_batch: int, max_seq: int) -> int:
    """Device bytes the engine holds besides its expert slots: the
    non-expert parameters, and the KV cache twice. The step programs now
    update the cache in place, so one copy is live; the second stays
    reserved until the slot budget is re-derived (it changes placement)."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    experts = 3 * cfg.d_model * cfg.moe_d_ff * cfg.n_experts * 2
    n_moe, _ = moe_perm_shape(cfg, None, "train")
    cache = jax.eval_shape(lambda: init_cache(cfg, max_batch, max_seq))
    kv = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    return int(total - experts * n_moe + 2 * kv)


def serve(arch: str, *, smoke: bool = True, policy: str = "vibe",
          n_requests: int = 12,
          qps: float = 50.0, workload: str = "sharegpt",
          regime: str = "mi325x", max_batch: int = 4, max_seq: int = 96,
          adaptive: bool = True, weighted_routing: bool = True,
          moe_impl: str = "ragged", scheduler: str = "fcfs",
          prefill_chunk: int = 0, kv_blocks: Optional[int] = None,
          block_size: int = 16, slots_per_rank: Union[str, int, None] = "auto",
          variability_scenario: str = "none",
          scenario_start: float = 0.0, scenario_duration: float = 2.0,
          perf_drift_delta: float = 0.0, steal: bool = False,
          steal_headroom: float = 0.1, topology: Optional[str] = None,
          fail_rank: int = -1, fail_at_step: int = 5,
          chaos: Optional[str] = None, shed_watermark: float = 0.0,
          preempt: bool = False, seed: int = 0):
    """Returns ``(engine, records, report)``; ``report`` is None unless
    ``fail_rank >= 0`` ran the elasticity drill (:class:`FailureReport`)
    or ``chaos`` ran the chaos drill (:class:`ChaosReport`)."""
    if chaos and fail_rank >= 0:
        raise SystemExit("--chaos and --fail-rank are mutually exclusive "
                         "(a chaos schedule already includes rank faults)")
    cfg = get_smoke(arch) if smoke else get(arch)
    if not cfg.is_moe:
        raise SystemExit(f"{arch} has no MoE layers — ViBE serving n/a")
    n_moe, n_slots = moe_perm_shape(cfg, None, "train")
    ranks = min(8, n_slots)
    # hardware-drift schedule: the ground-truth cluster changes over the
    # virtual clock (thermal ramp, power cap, interference, replacement)
    events = ([] if variability_scenario in ("none", "")
              else make_scenario(variability_scenario, ranks,
                                 t0=scenario_start,
                                 duration=scenario_duration))
    cluster = make_cluster(ranks, regime, d_model=cfg.d_model,
                           d_ff=cfg.moe_d_ff,
                           experts_per_rank=max(n_slots // ranks, 1),
                           seed=seed, events=events)
    perf = cluster.fit_models()                    # Phase 1: profiling (t=0)
    topo = None
    if topology:
        # fleet topology spec ("2x4" = 2 nodes x 4 devices, "8" = flat):
        # threads into the solver (vibe_h node binning) and both pricing
        # paths (migration / broadcast costs see the ICI/DCN asymmetry)
        topo = parse_topology(topology, ici_bw=cluster.ici_bw)
        if topo.n_ranks != ranks:
            raise SystemExit(f"topology {topology!r} has {topo.n_ranks} "
                             f"ranks but the engine runs {ranks}")
    expert_bytes = 3 * cfg.d_model * cfg.moe_d_ff * 2
    # replication-capable policies honour a per-rank physical slot budget
    # derived from device memory telemetry (paper §5.1's non-uniform
    # allocation); other policies keep their fixed footprint.
    budget = None
    if get_policy(policy).capabilities.accepts_slot_budget:
        budget = derive_slot_budget(
            ranks, cfg.n_experts, expert_bytes * n_moe,
            _resident_bytes(cfg, max_batch, max_seq), slots_per_rank)
    controller = ViBEController(
        n_moe, n_slots, ranks, perf,
        ViBEConfig(policy=policy, adaptive=adaptive,
                   drift=DriftConfig(window=20, interval=5, cooldown=5),
                   perf_drift=(PerfDriftConfig(delta_perf=perf_drift_delta,
                                               window=64, interval=5,
                                               cooldown=10, min_samples=8)
                               if perf_drift_delta > 0 else None),
                   expert_bytes=expert_bytes,
                   slot_budget=budget,
                   steal=(StealConfig(headroom=steal_headroom)
                          if steal else None),
                   topology=topo))
    # weighted_routing threads the vibe_r solver's per-copy traffic shares
    # into the dispatch tables (share-weighted replica routing); disabling
    # it keeps the legacy uniform split for A/B comparison.
    econfig = EngineConfig(
        max_batch=max_batch, max_seq=max_seq, moe_impl=moe_impl, seed=seed,
        weighted_routing=weighted_routing,
        scheduler=SchedulerConfig(name=scheduler,
                                  prefill_chunk=prefill_chunk,
                                  shed_watermark=shed_watermark,
                                  preempt_decodes=preempt),
        kv=(KVCacheConfig(block_size=block_size, n_blocks=kv_blocks)
            if kv_blocks else None),
        topology=topo)
    engine = Engine(cfg, econfig, controller=controller, cluster=cluster)
    if workload in TRACES:
        reqs = sample_trace(TRACES[workload], n_requests, qps=qps, seed=seed)
    else:
        reqs = sample_requests(WORKLOADS[workload], n_requests, qps=qps,
                               seed=seed)
    reqs = [dataclasses.replace(r, prompt_len=min(r.prompt_len, max_seq // 2),
                                output_len=min(r.output_len,
                                               max_seq // 2 - 1))
            for r in reqs]
    if chaos:
        # chaos drill: serve under a declarative fault schedule, then
        # audit the invariants (leaks, completion-or-reject, token ledger)
        schedule = FaultSchedule.parse(chaos, ranks)
        report = run_chaos(engine, reqs, schedule)
        return engine, report.records, report
    if fail_rank >= 0:
        # elasticity drill: kill a rank mid-traffic, serve through it
        records, report = run_with_failure(engine, reqs, fail_rank,
                                           at_step=fail_at_step)
        return engine, records, report
    engine.submit(reqs)
    records = engine.run()
    return engine, records, None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-235b-a22b")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="serve the published config instead of its smoke "
                         "reduction")
    ap.add_argument("--policy", default="vibe",
                    choices=list(registered_policies()))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--workload", default="sharegpt",
                    choices=sorted(WORKLOADS) + sorted(TRACES),
                    help="a workload family (Poisson arrivals) or a "
                         "multi-tenant arrival trace (bursty/diurnal/flat)")
    ap.add_argument("--qps", type=float, default=50.0)
    ap.add_argument("--regime", default="mi325x")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=list(registered_schedulers()),
                    help="continuous-batching scheduler (serving/"
                         "scheduler.py registry)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split prompts into fixed chunks of this many "
                         "tokens, interleaved with decode steps "
                         "(0 = whole-prompt prefill)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged KV cache pool size in blocks (0 = pool "
                         "sized to exactly cover the decode lanes)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block")
    ap.add_argument("--slots-per-rank", default="auto",
                    help="replica slot budget per rank for replication-"
                         "capable policies: 'auto' (device memory "
                         "telemetry, deterministic CPU fallback), "
                         "'default', or an integer")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--static", dest="adaptive", action="store_false")
    ap.add_argument("--uniform-replica-routing", dest="weighted_routing",
                    action="store_false",
                    help="ignore the solver's per-copy traffic shares and "
                         "split assignments uniformly across replicas "
                         "(share-oblivious A/B baseline; vibe_r only)")
    ap.add_argument("--moe-impl", choices=("ragged", "capacity"),
                    default="ragged",
                    help="grouped-FFN implementation the virtual clock "
                         "prices: 'ragged' (default) = sort-based dropless "
                         "dispatch, MoE cost tracks realized routed tokens; "
                         "'capacity' = fixed per-slot buckets, every rank "
                         "pays slots×capacity rows and overflow drops "
                         "(legacy baseline)")
    ap.add_argument("--variability-scenario", default="none",
                    choices=("none",) + tuple(sorted(SCENARIOS)),
                    help="hardware-drift schedule applied to the ground-"
                         "truth cluster over the virtual clock (thermal "
                         "ramp on one device, fleet power cap, transient "
                         "interference, device replacement)")
    ap.add_argument("--scenario-start", type=float, default=0.0,
                    help="virtual-clock time (s) the drift scenario begins")
    ap.add_argument("--scenario-duration", type=float, default=2.0,
                    help="ramp/transient length (s) for scenarios that "
                         "have one")
    ap.add_argument("--steal", action="store_true",
                    help="dispatch-time token rescheduling (work stealing): "
                         "between recalibrations, shift bounded traffic "
                         "shares off the predicted-slowest rank's replica "
                         "copies toward copies on faster ranks (replication-"
                         "capable policies only, e.g. --policy vibe_r)")
    ap.add_argument("--steal-headroom", type=float, default=0.1,
                    help="steal only when the hottest rank's predicted "
                         "latency exceeds the fleet mean by this relative "
                         "margin (default 0.1)")
    ap.add_argument("--topology", default=None,
                    help="fleet topology spec: 'KxD' (K nodes x D devices, "
                         "ICI within a node, ~8x-slower DCN between nodes) "
                         "or 'G' (flat). Threads into the solver (vibe_h "
                         "bins experts by node) and the virtual clock's "
                         "migration/broadcast pricing")
    ap.add_argument("--fail-rank", type=int, default=-1,
                    help="elasticity drill: kill this EP rank after a few "
                         "engine steps — drain its lanes, mask it out of "
                         "the solve, remap onto the survivors, re-admit "
                         "(-1 = no failure)")
    ap.add_argument("--chaos", default=None,
                    help="chaos drill: serve under a declarative fault "
                         "schedule and audit the invariants (no leaked KV, "
                         "complete-or-typed-reject, token conservation). "
                         "'default' / 'default:SEED' draws a randomized "
                         "fail+stall+dcn+recover drill; or a comma list "
                         "like 'fail@4:1,stall@6:2x0.4+0.5,recover@9:1'")
    ap.add_argument("--shed-watermark", type=float, default=0.0,
                    help="overload protection: once KV-pool utilization "
                         "reaches this fraction, shed waiting requests "
                         "whose TTFT deadline already lapsed (typed "
                         "rejection; 0 = never shed)")
    ap.add_argument("--preempt", action="store_true",
                    help="overload protection: under KV starvation, evict "
                         "the youngest decode lane (free its KV, requeue "
                         "the request, bounded retries) so waiting work "
                         "can admit")
    ap.add_argument("--perf-drift-delta", type=float, default=0.0,
                    help="enable online performance-drift recalibration: "
                         "refit f_g and re-solve when any rank's windowed "
                         "relative latency residual exceeds this threshold "
                         "(0 = routing-only recalibration, the default)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    engine, records, report = serve(args.arch, smoke=args.smoke,
                            policy=args.policy,
                            n_requests=args.requests, qps=args.qps,
                            workload=args.workload, regime=args.regime,
                            max_batch=args.max_batch, max_seq=args.max_seq,
                            adaptive=args.adaptive,
                            weighted_routing=args.weighted_routing,
                            moe_impl=args.moe_impl,
                            scheduler=args.scheduler,
                            prefill_chunk=args.prefill_chunk,
                            kv_blocks=args.kv_blocks or None,
                            block_size=args.block_size,
                            slots_per_rank=args.slots_per_rank,
                            variability_scenario=args.variability_scenario,
                            scenario_start=args.scenario_start,
                            scenario_duration=args.scenario_duration,
                            perf_drift_delta=args.perf_drift_delta,
                            steal=args.steal,
                            steal_headroom=args.steal_headroom,
                            topology=args.topology,
                            fail_rank=args.fail_rank,
                            chaos=args.chaos,
                            shed_watermark=args.shed_watermark,
                            preempt=args.preempt,
                            seed=args.seed)
    s = summarize(records)
    st = engine.stats
    routing = ("share-weighted" if args.weighted_routing
               else "uniform") + f" replica routing, {args.moe_impl} FFN"
    sched = (f"{args.scheduler}"
             + (f", chunk={args.prefill_chunk}" if args.prefill_chunk
                else ", whole-prompt"))
    print(f"[serve] {args.policy} on {args.arch} ({routing}; {sched}): "
          f"{st.steps} steps "
          f"({st.prefill_steps} prefill / {st.chunk_steps} chunks / "
          f"{st.decode_steps} decode), "
          f"virtual time {st.virtual_time:.3f}s")
    print(f"[serve] TTFT p50/p90 = {s['ttft_p50']:.4f}/{s['ttft_p90']:.4f}s "
          f"TPOT p50 = {s['tpot_p50']:.5f}s")
    print(f"[serve] KV pool: {engine.kv.config.n_blocks} blocks x "
          f"{engine.kv.config.block_size} tokens, peak used "
          f"{engine.kv.peak_blocks}")
    kinds = {}
    for u in engine.controller.updates:
        kinds[u.kind] = kinds.get(u.kind, 0) + 1
    by_kind = (" (" + ", ".join(f"{k}: {v}" for k, v in sorted(kinds.items()))
               + ")") if kinds else ""
    print(f"[serve] recalibrations: {st.migrations}{by_kind}, migrated slots "
          f"{st.migrated_slots}, bytes {st.migration_bytes}, dropped "
          f"assignments {st.dropped_assignments:.0f}")
    if st.rejected or st.preemptions:
        by_r = ", ".join(f"{k}: {v}" for k, v in sorted(st.rejected.items()))
        print(f"[serve] overload: rejected {sum(st.rejected.values())}"
              + (f" ({by_r})" if by_r else "")
              + f", preemptions {st.preemptions}")
    if isinstance(report, ChaosReport):
        print(f"[serve] {report.summary()}")
        for spec, why in report.skipped:
            print(f"[serve]   skipped {spec.kind}@{spec.at_step}: {why}")
        finished = sum(1 for r in records if np.isfinite(r.finished_at))
        print(f"[serve] chaos drill: {finished}/{len(records)} finished, "
              "token ledger prefill+decode="
              f"{st.prefill_tokens + st.decode_tokens} vs useful+lost="
              f"{st.useful_tokens + st.lost_tokens}")
        if not report.ok:
            for v in report.violations:
                print(f"[serve] CHAOS VIOLATION: {v}")
            return 1
    elif report is not None:
        finished = sum(1 for r in records if np.isfinite(r.finished_at))
        print(f"[serve] failure drill: rank {report.rank} died at "
              f"t={report.at_time:.3f}s — drained "
              f"{report.drained_prefills} prefills / "
              f"{report.drained_decodes} decodes, "
              f"{report.redone_tokens} tokens redone, "
              f"{report.moved_experts} expert slots remapped; "
              f"{finished}/{len(records)} requests completed, "
              f"KV blocks in use after drain: {engine.kv.used_blocks}")
        if finished < len(records) or engine.kv.used_blocks != 0:
            print("[serve] FAILURE DRILL FAILED: incomplete requests or "
                  "leaked KV blocks")
            return 1
    if args.steal:
        rs = engine.controller.rescheduler
        print(f"[serve] stealing: {st.steal_updates} share updates "
              f"({rs.steals} steal steps, {rs.share_moved:.3f} total share "
              f"moved, headroom {args.steal_headroom:g})")
    if args.variability_scenario != "none":
        print(f"[serve] hardware drift: scenario {args.variability_scenario} "
              f"from t={args.scenario_start:.2f}s, perf-drift delta "
              f"{args.perf_drift_delta:g}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
