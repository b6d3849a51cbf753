"""JAX serving engine: continuous batching + KV cache + ViBE integration.

This is the *real-system* integration layer: the actual JAX model runs
(prefill + batched decode with per-slot positions), the router's tallies
feed the ViBE controller, and a placement update migrates the stacked
expert weights via :func:`repro.models.moe.apply_placement` and swaps the
slot-lookup tables **without recompiling** the step functions.

Configuration is one frozen :class:`EngineConfig` (serving/config.py):

* **Paged KV cache** — admission is gated by a block pool
  (:class:`~repro.serving.kvcache.PagedKVCache`), not a hardcoded batch
  cap; the default pool exactly covers the lanes, so legacy behavior is
  unchanged until a pool is configured.
* **Scheduler-driven steps** — each :meth:`step` asks a registered
  scheduler (serving/scheduler.py) what to run: a prefill chunk, a decode
  step, or idle. The default (``fcfs``, ``prefill_chunk=0``) replicates
  the legacy prefill-priority whole-prompt loop bit-for-bit.
* **Chunked prefill** — with ``prefill_chunk > 0`` long prompts run as
  fixed-width chunks (:func:`repro.models.model.prefill_chunk_fn`)
  interleaved with decode steps, and each chunk is priced on the virtual
  clock individually, so long-context requests stop head-of-line-blocking
  TTFT.
* **Host spans** — each phase of :meth:`Engine.step` (schedule, admit,
  launch, sync, observe, migrate, finish) is a ``step.*`` span of
  :mod:`repro.serving.tracing`: profiler annotations while a trace is
  taken, one shared no-op otherwise. ``RequestRecord.admitted_step`` is
  the step count at admission, so a client can time queue wait.

Because this host has one CPU device, wall-clock here is meaningless for
multi-rank behaviour; the engine keeps a *virtual clock* driven by the same
ground-truth cluster model the simulator uses (DESIGN.md §4), applied to
the *real* per-step routing tallies the model just produced. On a real
multi-chip deployment the virtual clock is replaced by measured step times
(pass them to :meth:`observe_step`); nothing else changes.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import (ClusterVariability, ReplicatedPlacement,
                        ViBEController)
from repro.models import (ShardingRules, decode_fn, init_cache, init_params,
                          make_moe_tables, moe_perm_shape, prefill_chunk_fn,
                          prefill_fn, refresh_moe_share_tables)
from repro.models.model import block_layout
from repro.models.moe import apply_placement
from .config import EngineConfig
from .kvcache import PagedKVCache
from .metrics import RejectReason, RequestRecord
from .scheduler import (RequestView, SchedulerContext, get_scheduler,
                        shed_victims)
from .simulator import (capacity_bucket_rows, rank_latency_matrix,
                        realized_rank_loads)
from .tracing import span
from .workload import Request

__all__ = ["Engine", "EngineStats", "EngineConfig"]


@jax.jit
def sample(logits):
    """Greedy next token of each row of ``logits``: its argmax."""
    with jax.named_scope("sample"):
        return jnp.argmax(logits, -1).astype(jnp.int32)


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    prefill_steps: int = 0           # requests whose prefill completed
    chunk_steps: int = 0             # individual prefill-chunk model calls
    decode_steps: int = 0
    migrations: int = 0
    migrated_slots: int = 0
    migration_bytes: int = 0
    steal_updates: int = 0           # share-only table refreshes (stealing)
    dropped_assignments: float = 0.0  # capacity-overflow drops (all layers)
    virtual_time: float = 0.0
    # token-conservation ledger (chaos-drill invariant): every token the
    # model processed is either useful (belongs to a finished request's
    # prompt + decode stream) or lost (thrown away by a rank-failure drain
    # or a preemption and replayed later) — when the engine is idle,
    # prefill_tokens + decode_tokens == useful_tokens + lost_tokens.
    prefill_tokens: int = 0          # prompt tokens run through prefill
    decode_tokens: int = 0           # decode-lane participations run
    useful_tokens: int = 0           # processed tokens of finished requests
    lost_tokens: int = 0             # processed tokens discarded by
    #                                  drains/preemptions (replayed later)
    preemptions: int = 0             # decode lanes evicted under KV pressure
    rejected: Dict[str, int] = dataclasses.field(default_factory=dict)
    #                                  RejectReason.value → count


@dataclasses.dataclass
class _Prefilling:
    """An admitted request whose prompt is (partially) in the cache."""

    req: Request
    lane: int
    prompt: np.ndarray               # (1, prompt_len) generated tokens
    prefilled: int = 0


class Engine:
    """Continuous-batching engine for one (smoke-scale) model.

    ``Engine(cfg, EngineConfig(...), controller=..., cluster=...)`` is the
    configured surface; the legacy keyword form
    ``Engine(cfg, max_batch=..., max_seq=..., ...)`` still works through
    :meth:`EngineConfig.from_kwargs` (bit-identical, ``DeprecationWarning``).
    """

    # class-level fallback: skeleton engines built without __init__
    # (pricing-path tests use Engine.__new__) read default knobs here
    config = EngineConfig()
    # the last step's kind ("chunk" | "prefill" | "decode" | "idle"), an
    # arg of every host span; "" before the first step
    _kind = ""

    def __init__(self, cfg: ArchConfig,
                 config: Optional[EngineConfig] = None, *,
                 rules: Optional[ShardingRules] = None,
                 controller: Optional[ViBEController] = None,
                 cluster: Optional[ClusterVariability] = None,
                 **legacy):
        if legacy:
            if config is not None:
                raise TypeError("pass either an EngineConfig or legacy "
                                "keyword arguments, not both")
            config = EngineConfig.from_kwargs(**legacy)
        elif config is None:
            config = EngineConfig()
        if not isinstance(config, EngineConfig):
            raise TypeError("config must be an EngineConfig, "
                            f"got {type(config).__name__}")
        self.config = config = config.resolve()
        self.cfg = cfg
        self.rules = rules
        self.controller = controller
        self.cluster = cluster
        self.max_batch = config.max_batch
        self.max_seq = config.max_seq
        # which grouped-FFN implementation the virtual clock prices:
        # "ragged" (dropless — cost is the realized dispatched load, the
        # model layer's default) or "capacity" (fixed buckets — every rank
        # pays slots_per_rank × capacity rows regardless of skew). Defaults
        # to the sharding rules' resolved impl so clock and dispatch agree.
        moe_impl = config.moe_impl
        if moe_impl is None:
            moe_impl = (rules.moe_impl_resolved if rules is not None
                        else "ragged")
        self.moe_impl = moe_impl
        # share-weighted replica routing: fold the controller placement's
        # per-copy traffic shares into the dispatch tables so the model
        # steers tokens the way the solver's latency objective assumes.
        # False = share-oblivious uniform split over copies (same selector,
        # flat CDF) — the A/B + regression knob.
        self.weighted_routing = config.weighted_routing
        self.stats = EngineStats()
        key = jax.random.PRNGKey(config.seed)
        self.params = init_params(cfg, key, rules)
        self.n_moe, self.n_slots = (moe_perm_shape(cfg, rules, "train")
                                    if cfg.is_moe else (0, 0))
        self._perm = (np.tile(np.arange(self.n_slots, dtype=np.int32),
                              (self.n_moe, 1)) if cfg.is_moe else None)
        self._share: Optional[np.ndarray] = None
        self._r_max: Optional[int] = None
        if cfg.is_moe and controller is not None:
            # Replication-capable policies: when the controller's placement
            # uses a slot budget beyond one-per-expert (replicated copies),
            # grow the stacked expert tensors to match. The budget is read
            # off the placement itself, so engine and controller cannot
            # disagree. Placements are always the unified
            # ReplicatedPlacement (singleton = r_max 1 degenerate), so no
            # type-switching here.
            want = controller.placement.perm.shape[1]
            if want > self.n_slots:
                self._expand_slots(want)
            # pin the copy-axis width to its reachable maximum (≤ one
            # copy per rank, ≤ spare slots + 1; exactly 1 for singleton
            # policies) so recalibrations that change replication degrees
            # keep table shapes — and the compiled step functions — stable.
            self._r_max = min(controller.G,
                              self.n_slots - controller.E + 1)
        if controller is not None \
                and getattr(controller, "rescheduler", None) is not None \
                and not self.weighted_routing:
            # stolen shares can only steer dispatch through the weighted
            # CDF tables; with a uniform split they'd be silently inert
            raise ValueError("controller has work stealing enabled "
                             "(ViBEConfig.steal) but weighted_routing is "
                             "False — stolen shares would never reach "
                             "dispatch")
        if config.topology is not None and controller is not None \
                and config.topology.n_ranks != controller.G:
            raise ValueError(f"topology has {config.topology.n_ranks} ranks "
                             f"but the controller has {controller.G}")
        self._steal_version = 0
        if controller is not None:
            self._apply_perm(self._controller_perm(), charge=False)
        else:
            self.moe_tables = make_moe_tables(
                cfg, rules, perm=self._perm,
                n_slots=self.n_slots) if cfg.is_moe else None
        self._prefill = jax.jit(prefill_fn(cfg, rules))
        # the step programs take the cache donated (argument 2) and update
        # it in place; each call's output cache replaces ``self.cache``
        self._decode = jax.jit(decode_fn(cfg, rules), donate_argnums=(2,))
        # scheduling + memory: registered scheduler, paged KV admission
        self.scheduler = get_scheduler(config.scheduler.name)
        self._sched_cfg = config.scheduler
        self._chunk = config.scheduler.prefill_chunk
        self._prefill_chunk = (jax.jit(prefill_chunk_fn(cfg, rules),
                                       donate_argnums=(2,))
                               if self._chunk > 0 else None)
        self.kv = PagedKVCache(config.kv)
        self._prefill_streak = 0
        # slot state
        self.cache = init_cache(cfg, self.max_batch, self.max_seq, rules)
        self.tokens = jnp.zeros((self.max_batch, 1), jnp.int32)
        self.pos = np.zeros(self.max_batch, np.int64)
        self.slot_req: List[Optional[Request]] = [None] * self.max_batch
        self.slot_left = np.zeros(self.max_batch, np.int64)
        self.records: Dict[int, RequestRecord] = {}
        self.waiting: collections.deque = collections.deque()
        self._prefilling: Dict[int, _Prefilling] = {}

    # -- placement plumbing -------------------------------------------------

    def _expand_slots(self, n_slots: int) -> None:
        """Grow stacked expert tensors to ``n_slots`` physical slots.

        New slot p starts holding logical expert p % E (round-robin replica),
        gathered from the identity layout — the slot-table application path
        (``apply_placement`` + ``make_moe_tables``) then works unchanged for
        replicated placements.
        """
        if n_slots < self.n_slots:
            raise ValueError(f"cannot shrink slots {self.n_slots}→{n_slots}")
        if n_slots == self.n_slots:
            return
        E = self.cfg.n_experts
        src = np.concatenate([np.arange(self.n_slots, dtype=np.int32),
                              np.arange(self.n_slots, n_slots,
                                        dtype=np.int32) % E])
        gi = jnp.asarray(src)
        _, specs = block_layout(self.cfg)
        for i, spec in enumerate(specs):
            if spec.ffn != "moe":
                continue
            # one matrix at a time, so only one grown copy is transient
            for k in ("w1", "w2", "w3"):
                leaf = self.params["blocks"][i]["ffn"]
                if k in leaf:
                    self.params["blocks"][i]["ffn"] = {
                        **leaf, k: jnp.take(leaf[k], gi, axis=1)}
        self._perm = np.tile(src, (self.n_moe, 1))
        self.n_slots = n_slots

    def _controller_perm(self) -> np.ndarray:
        pl = self.controller.placement
        perm = pl.perm                                  # (n_moe, n_slots)
        if perm.shape != (self.n_moe, self.n_slots):
            raise ValueError(f"controller placement {perm.shape} != "
                             f"{(self.n_moe, self.n_slots)}")
        return perm

    def _controller_share(self) -> Optional[np.ndarray]:
        """Per-slot traffic shares of the controller's placement, or None.

        None (singleton placements, or ``weighted_routing=False``) keeps the
        uniform split over copies in the dispatch tables.
        """
        if self.controller is None or not self.weighted_routing:
            return None
        # dispatch_placement = responsive (steal-adjusted) shares when the
        # controller runs a TokenRescheduler, the plan's shares otherwise
        pl = getattr(self.controller, "dispatch_placement",
                     self.controller.placement)
        return getattr(pl, "share", None)

    _AUTO_SHARE = object()      # sentinel: derive from the controller

    def _apply_perm(self, new_perm: np.ndarray, share=_AUTO_SHARE,
                    charge: bool = True) -> int:
        """Migrate expert weights + slot/share tables to a new placement.

        ``share`` defaults to the controller placement's traffic shares
        (respecting ``weighted_routing``) so dispatch tables and the
        virtual clock can never desync; pass an explicit array (or None
        for a uniform split) only to override. The share table rides along
        exactly like the slot table: both are plain array inputs to the
        jitted step functions (copy-axis width pinned via ``_r_max``), so
        recalibration — including share-only changes — never recompiles.
        """
        if share is Engine._AUTO_SHARE:
            share = self._controller_share()
        with span("step.migrate", kind=self._kind) as sp:
            moved_total = self._migrate_experts(new_perm)
            self._share = None if share is None else np.array(share)
            self.moe_tables = make_moe_tables(self.cfg, self.rules,
                                              perm=self._perm,
                                              n_slots=self.n_slots,
                                              share=self._share,
                                              r_max=self._r_max)
            moved_bytes = moved_total * 3 * self.cfg.d_model \
                * self.cfg.moe_d_ff * 2
            sp.set_metadata(slots=moved_total, bytes=moved_bytes)
        self._sync_steal_version()
        if charge:
            self.stats.migrations += 1
            self.stats.migrated_slots += moved_total
            self.stats.migration_bytes += moved_bytes
            if self.cluster is not None:
                # the weight transfer stalls serving: charge it to the
                # virtual clock so engine-measured TTFT/TPOT see the same
                # migration stalls the simulator models (sim.migration_stalls).
                # A configured topology prices the cross-node fraction at
                # DCN bandwidth (flat topology degenerates to the same
                # divide); the engine serializes migrations on one link.
                topo = self.config.topology
                if topo is not None:
                    self.stats.virtual_time += topo.migration_cost(moved_bytes)
                else:
                    self.stats.virtual_time += \
                        moved_bytes / self.cluster.ici_bw
        return moved_total

    def _migrate_experts(self, new_perm: np.ndarray) -> int:
        """Move the stacked expert weights to ``new_perm``; the number of
        (layer, slot) tensors that moved."""
        nb, specs = block_layout(self.cfg)
        m = self.n_moe // nb
        moved_total = 0
        moe_positions = [i for i, s in enumerate(specs) if s.ffn == "moe"]
        for jj, i in enumerate(moe_positions):
            old_j = self._perm[jj::m] if m else self._perm
            new_j = new_perm[jj::m]
            # one matrix at a time, so only one migrated copy is transient
            for k in ("w1", "w2", "w3"):
                leaf = self.params["blocks"][i]["ffn"]
                if k in leaf:
                    migrated, moved = apply_placement({k: leaf[k]}, old_j,
                                                      new_j)
                    self.params["blocks"][i]["ffn"] = {**leaf, **migrated}
            moved_total += moved
        self._perm = new_perm.copy()
        return moved_total

    def _observe(self, tallies: np.ndarray, tokens: float) -> None:
        if self.controller is None:
            return
        t = self._controller_tallies(tallies)
        upd = self.controller.observe(t, tokens=tokens)
        if upd is not None:
            self._apply_perm(self._controller_perm())
        elif self._steal_dirty():
            self._apply_share()

    def _steal_dirty(self) -> bool:
        rs = getattr(self.controller, "rescheduler", None)
        return rs is not None and rs.version != self._steal_version

    def _sync_steal_version(self) -> None:
        rs = getattr(self.controller, "rescheduler", None)
        self._steal_version = rs.version if rs is not None else 0

    def _apply_share(self) -> None:
        """Share-only dispatch-table refresh after a steal update.

        The slot table (and thus the weights) is untouched — only the
        cumulative-share CDF the inverse-CDF replica selector reads is
        rebuilt (:func:`refresh_moe_share_tables` reuses the existing
        ``slots_of``/``n_copies``). Shapes are pinned, so no recompile;
        the clock charges only the small share-table broadcast.
        """
        rs = self.controller.rescheduler
        with span("step.migrate", kind=self._kind, slots=0,
                  bytes=rs.share_table_bytes):
            self._share = np.array(rs.placement.share)
            self.moe_tables = refresh_moe_share_tables(
                self.cfg, self.moe_tables, self._perm, self._share)
        self._sync_steal_version()
        self.stats.steal_updates += 1
        if self.cluster is not None:
            topo = self.config.topology
            if topo is not None:
                self.stats.virtual_time += \
                    topo.broadcast_cost(rs.share_table_bytes)
            else:
                self.stats.virtual_time += \
                    rs.share_table_bytes / self.cluster.ici_bw

    def _controller_tallies(self, tallies: np.ndarray) -> np.ndarray:
        """Pad router tallies (logical experts) to the controller's width.

        The model returns (n_moe, E+1) tallies — logical-expert counts plus
        a capacity-dropped column (accounted in ``stats``, not load); strip
        the drop column first. Singleton controllers treat every physical
        slot as an expert (phantoms see zero load); a ViBE-R controller
        works on logical experts directly, so its width can be below the
        slot count."""
        t = np.asarray(tallies, dtype=np.float64)[:, :self.cfg.n_experts]
        if t.shape[1] < self.controller.E:
            t = np.pad(t, ((0, 0), (0, self.controller.E - t.shape[1])))
        return t

    # -- virtual clock -------------------------------------------------------

    def _clock_placement(self):
        """The placement whose traffic split the virtual clock prices.

        With weighted routing the dispatch follows the solver's shares, so
        the clock prices the controller placement directly. With
        ``weighted_routing=False`` the dispatch splits uniformly over
        copies — pricing the solver's shares then would hide exactly the
        gap the A/B knob exists to measure, so the clock uses a uniform-
        share view of the same slot table (cached per placement object).

        With stealing on, ``dispatch_placement`` is the responsive
        (steal-adjusted) placement — the clock prices what the dispatch
        tables actually did this step, since tables refresh *after* each
        step's observation.
        """
        pl = getattr(self.controller, "dispatch_placement",
                     self.controller.placement)
        if self.weighted_routing:
            return pl
        if getattr(self, "_uniform_clock_src", None) is not pl:
            se = pl.slot_expert
            nc_pad = np.concatenate(          # phantom col: avoid 0-division
                [pl.n_copies(), np.ones((pl.n_layers, 1))], axis=1)
            share = np.where(se < pl.n_experts,
                             1.0 / np.take_along_axis(nc_pad, se, axis=1),
                             0.0)
            self._uniform_clock_pl = ReplicatedPlacement(
                se, share, pl.n_ranks, pl.n_experts)
            self._uniform_clock_src = pl
        return self._uniform_clock_pl

    def _charge(self, tallies: np.ndarray, tokens: int) -> float:
        """Advance virtual time using ground-truth cluster latencies.

        With ``moe_impl="ragged"`` (default) loads are the *realized*
        token-granular split of the routing-mode placement
        (``realized_rank_loads``) — the dropless kernel's cost tracks
        exactly what the dispatch tables did this step, so weighted vs
        uniform replica routing shows up in TTFT/TPOT, not just in the
        tables. With ``moe_impl="capacity"`` every rank is charged its full
        bucket allocation (its real-slot count × capacity rows, zero
        padding included — non-uniform slot budgets charge each rank its
        actual bucket count) — the fixed-bucket kernel's honest,
        skew-oblivious cost.

        The per-rank (load, latency) rows also feed the controller's
        performance-drift telemetry (``observe_latency``): the virtual
        clock stands in for the kernel timers a real deployment would
        read, so a drifting ``ClusterVariability`` (events schedule) is
        observed — and recalibrated against — through exactly the samples
        serving produced.
        """
        if self.cluster is None or self.controller is None \
                or not self.cfg.is_moe:
            dt = 1e-3 * max(tokens, 1)                  # trivial fallback
            self.stats.virtual_time += dt
            return dt
        if self.moe_impl == "capacity":
            cf = self.config.capacity_factor if self.rules is None \
                else self.rules.capacity_factor
            cap = capacity_bucket_rows(tokens, self.cfg.top_k,
                                       self.n_slots, cf)
            # per-rank *real* slot counts from the placement itself:
            # non-uniform budgets mean ranks run different bucket counts
            # (phantom slots allocate nothing)
            budget = self.controller.placement.rank_slot_budget()
            rank_load = budget.astype(np.float64) * cap
        else:
            rank_load = realized_rank_loads(
                self._clock_placement(), self._controller_tallies(tallies))
        rank_time = rank_latency_matrix(self.cluster, rank_load,
                                        t=self.stats.virtual_time)
        dt = float(rank_time.max(1).sum())
        self.stats.virtual_time += dt
        upd = self.controller.observe_latency(rank_load, rank_time)
        if upd is not None:
            self._apply_perm(self._controller_perm())
        return dt

    def observe_step(self, tallies, tokens: float, latencies=None) -> float:
        """Feed one step's telemetry; returns the step's virtual duration.

        The unified observation surface (same shape as
        ``EPSimulator.observe_step``): price the step, feed the per-rank
        latency telemetry to the controller's drift detector, then feed
        the routing tallies to the skew detector — either may trigger a
        placement update, which is applied (and its migration stall
        charged) before returning.

        ``latencies`` — optional measured ``(rank_load, rank_time)`` pair
        from a real deployment's kernel timers; None (the smoke-host
        default) prices the step on the virtual clock instead.
        """
        tall = np.asarray(tallies)
        if latencies is None:
            dt = self._charge(tall, tokens)
        else:
            rank_load, rank_time = latencies
            rank_time = np.asarray(rank_time, dtype=np.float64)
            dt = float(rank_time.max(1).sum())
            self.stats.virtual_time += dt
            if self.controller is not None:
                upd = self.controller.observe_latency(rank_load, rank_time)
                if upd is not None:
                    self._apply_perm(self._controller_perm())
        self._observe(tall, float(tokens))
        return dt

    # -- request lifecycle ----------------------------------------------------

    def submit(self, reqs: List[Request]) -> List[RequestRecord]:
        """Submit requests; returns the records of the ones REJECTED.

        Rejection is typed, not an exception: an infeasible request (prompt
        beyond ``max_seq``, or a worst-case KV reservation the pool can
        never satisfy) gets a :class:`RequestRecord` carrying its
        :class:`RejectReason` — it never enters the waiting queue, and
        ``stats.rejected`` tallies the reason for the serve summary line.
        Feasible requests queue as before.
        """
        out = []
        for r in reqs:
            rec = RequestRecord(r.req_id, r.arrival, r.prompt_len,
                                r.output_len, tenant=r.tenant)
            self.records[r.req_id] = rec
            total = min(r.prompt_len + r.output_len, self.max_seq)
            floor = int(self.kv.config.n_blocks * self.kv.config.watermark)
            if r.prompt_len > self.max_seq:
                self._reject(rec, RejectReason.TOO_LONG)
            elif self.kv.config.blocks_for(total) > \
                    self.kv.config.n_blocks - floor:
                # needs more KV blocks than admission can ever hand out:
                # queueing it would wait forever behind the watermark
                self._reject(rec, RejectReason.NEVER_FITS)
            else:
                self.waiting.append(r)
                continue
            out.append(rec)
        return out

    def _reject(self, rec: RequestRecord, reason: RejectReason) -> None:
        rec.reject_reason = reason
        self.stats.rejected[reason.value] = \
            self.stats.rejected.get(reason.value, 0) + 1

    def _lane_free(self, b: int) -> bool:
        if self.slot_req[b] is not None:
            return False
        return all(p.lane != b for p in self._prefilling.values())

    def _free_slot(self) -> Optional[int]:
        for b in range(self.max_batch):
            if self._lane_free(b):
                return b
        return None

    def _insert_cache(self, slot: int, pre_cache) -> None:
        """Insert a prefilled (batch-1) cache pytree into engine slot."""
        def ins(ec, pc):
            # a KV leaf holds the prompt's positions only: pad them to
            # max_seq (recurrent states match the engine's shape already)
            pad = [(0, 0), (0, 0)] + [(0, e - n) for e, n in
                                      zip(ec.shape[2:], pc.shape[2:])]
            return ec.at[:, slot].set(jnp.pad(pc, pad)[:, 0]
                                      .astype(ec.dtype))
        self.cache = jax.tree.map(ins, self.cache, pre_cache)

    def _release(self, lane: int) -> None:
        r = self.slot_req[lane]
        self.slot_req[lane] = None
        self.kv.free_seq(r.req_id)

    # -- scheduling ----------------------------------------------------------

    def _build_context(self) -> SchedulerContext:
        prefilling = [RequestView(p.req.req_id, p.req.arrival,
                                  p.req.prompt_len, p.req.output_len,
                                  p.prefilled, p.req.ttft_slo)
                      for p in self._prefilling.values()]
        waiting, blocked = [], []
        for r in self.waiting:
            total = min(r.prompt_len + r.output_len, self.max_seq)
            view = RequestView(r.req_id, r.arrival, r.prompt_len,
                               r.output_len, 0, r.ttft_slo)
            (waiting if self.kv.can_admit(total) else blocked).append(view)
        n_free = sum(1 for b in range(self.max_batch) if self._lane_free(b))
        n_running = sum(1 for s in self.slot_req if s is not None)
        return SchedulerContext(
            now=self.stats.virtual_time, config=self._sched_cfg,
            waiting=waiting, prefilling=prefilling, n_running=n_running,
            prefill_streak=self._prefill_streak, can_start=n_free,
            chunk_budget=self._chunk if self._chunk > 0 else self.max_seq,
            blocked=blocked, kv_utilization=self.kv.utilization())

    # -- overload protection -------------------------------------------------

    def _shed_overload(self) -> None:
        """Watermark load shedding (``SchedulerConfig.shed_watermark``).

        The policy lives in the scheduler module (:func:`shed_victims` —
        under KV pressure, reject waiting requests whose TTFT deadline has
        lapsed, lowest headroom first); the engine applies it: victims
        leave the queue and their records carry ``RejectReason.SHED``.
        """
        if self._sched_cfg.shed_watermark <= 0.0 or not self.waiting:
            return
        victims = set(shed_victims(self._build_context()))
        if not victims:
            return
        keep: collections.deque = collections.deque()
        for r in self.waiting:
            if r.req_id in victims:
                self._reject(self.records[r.req_id], RejectReason.SHED)
            else:
                keep.append(r)
        self.waiting = keep

    def _maybe_preempt(self) -> None:
        """Preempt one decode lane when KV pressure starves admission.

        Fires only when ``SchedulerConfig.preempt_decodes`` is set, some
        request is waiting, and *none* of the waiting requests fits the
        free KV pool — the committing-admission deadlock a shrunken pool
        (or a rank-failure re-admission wave) can produce. The victim is
        the decode lane with the fewest produced tokens (least work lost);
        its KV is freed and the request requeued at the *tail* (backoff —
        drains use the head). A request preempted ``max_preemptions``
        times becomes immune, which bounds per-request retries and rules
        out preemption livelock.
        """
        cfgp = self._sched_cfg
        if not cfgp.preempt_decodes or not self.waiting:
            return
        if any(self.kv.can_admit(min(r.prompt_len + r.output_len,
                                     self.max_seq))
               for r in self.waiting):
            return
        victims = []
        for b in range(self.max_batch):
            r = self.slot_req[b]
            if r is None:
                continue
            if self.records[r.req_id].preemptions >= cfgp.max_preemptions:
                continue
            decoded = int(r.output_len - 1 - self.slot_left[b])
            victims.append((max(decoded, 0), b))
        if not victims:
            return
        decoded, b = min(victims)
        r = self.slot_req[b]
        self.slot_req[b] = None
        self.slot_left[b] = 0
        self.pos[b] = 0
        self.kv.free_seq(r.req_id)
        rec = self.records[r.req_id]
        rec.preemptions += 1
        rec.requeues += 1
        self.stats.preemptions += 1
        # the prompt and the produced-so-far tokens die with the KV shard
        self.stats.lost_tokens += r.prompt_len + decoded
        self.waiting.append(r)

    def step(self) -> bool:
        """One engine step, as directed by the configured scheduler:
        one prefill chunk (or whole prompt), or one batched decode.

        Overload protection runs first (both off by default): watermark
        load shedding rejects hopeless waiting requests under KV-pool
        pressure, and decode preemption evicts a running lane when KV
        starvation blocks every waiting request.

        Returns False when idle (no waiting or running requests).

        Each host phase is a :func:`~repro.serving.tracing.span` (no-op
        unless tracing is enabled) with the step's kind as an arg.
        """
        with span("step.schedule") as sp:
            self._shed_overload()
            self._maybe_preempt()
            action = self.scheduler.schedule(self._build_context())
            self._kind = action.kind
            if action.kind == "prefill" and self._chunk > 0:
                self._kind = "chunk"
            sp.set_metadata(kind=self._kind)
        if action.kind == "prefill":
            # the engine runs one chunk per step so the virtual clock
            # prices every chunk individually (the simulator's scheduled
            # loop batches a whole token budget instead)
            self._exec_prefill(action.chunks[0].req_id)
            self._prefill_streak += 1
            self.stats.steps += 1
            return True
        if action.kind == "decode":
            self._exec_decode()
            self._prefill_streak = 0
            self.stats.steps += 1
            return True
        return False

    def _exec_prefill(self, req_id: int) -> None:
        st = self._prefilling.get(req_id)
        if st is None:
            with span("step.admit", kind=self._kind, req_id=req_id):
                st = self._admit(req_id)
        if self._chunk > 0:
            self._prefill_one_chunk(st)
        else:
            self._prefill_whole(st)

    def _admit(self, req_id: int) -> _Prefilling:
        """Reserve a lane and the full worst-case KV block count (so decode
        extension can never fail mid-request) for a waiting request."""
        r = next(x for x in self.waiting if x.req_id == req_id)
        self.waiting = collections.deque(
            x for x in self.waiting if x.req_id != req_id)
        lane = self._free_slot()
        self.kv.allocate(r.req_id,
                         min(r.prompt_len + r.output_len, self.max_seq))
        rec = self.records[r.req_id]
        if rec.admitted_step is None:
            # a re-admitted request (preempted, or drained by a rank
            # failure) keeps its first admission, as it keeps its TTFT
            rec.admitted_step = self.stats.steps
        # the engine can't start before the request arrives
        self.stats.virtual_time = max(self.stats.virtual_time, r.arrival)
        prompt = np.random.default_rng(r.req_id).integers(
            0, self.cfg.vocab, size=(1, r.prompt_len))
        st = _Prefilling(r, lane, prompt)
        self._prefilling[req_id] = st
        return st

    def _sync_observe(self, tallies, tokens: float) -> None:
        """Wait for the step's routing tallies on the host, then price the
        step and feed the controller (:meth:`observe_step`)."""
        with span("step.sync", kind=self._kind):
            tall = np.asarray(tallies)
        if self.cfg.is_moe and tall.size:
            self.stats.dropped_assignments += float(tall[:, -1].sum())
        with span("step.observe", kind=self._kind):
            self.observe_step(tall, tokens)

    def _prefill_whole(self, st: _Prefilling) -> None:
        """Legacy whole-prompt prefill (``prefill_chunk = 0``)."""
        r = st.req
        with span("step.launch", kind=self._kind):
            batch = {"tokens": jnp.asarray(st.prompt, jnp.int32)}
            logits, pre_cache, tallies = self._prefill(
                self.params, batch, self.moe_tables)
            self._insert_cache(st.lane, pre_cache)
            self.tokens = self.tokens.at[st.lane, 0].set(sample(logits)[0])
            st.prefilled = r.prompt_len
            self.kv.advance(r.req_id, min(r.prompt_len, self.max_seq))
            self.stats.prefill_tokens += r.prompt_len
        self._sync_observe(tallies, float(r.prompt_len))
        with span("step.finish", kind=self._kind):
            self._finish_prefill(st)
        self.stats.prefill_steps += 1

    def _prefill_one_chunk(self, st: _Prefilling) -> None:
        """One fixed-width chunk of ``st``'s prompt into its lane."""
        r = st.req
        C = self._chunk
        off = st.prefilled
        n_valid = min(C, r.prompt_len - off)
        with span("step.launch", kind=self._kind):
            buf = np.zeros((1, C), np.int64)
            buf[0, :n_valid] = st.prompt[0, off:off + n_valid]
            logits, self.cache, tallies = self._prefill_chunk(
                self.params, jnp.asarray(buf, jnp.int32), self.cache,
                st.lane, off, n_valid, self.moe_tables)
            st.prefilled += n_valid
            self.kv.advance(r.req_id, n_valid)
            self.stats.prefill_tokens += n_valid
            # interleaved decode steps write a garbage row at pos[lane] for
            # reserved lanes; parking pos at the next chunk offset makes
            # the next chunk's first (always-valid) row overwrite it
            self.pos[st.lane] = st.prefilled
        self._sync_observe(tallies, float(n_valid))
        self.stats.chunk_steps += 1
        if st.prefilled >= r.prompt_len:
            with span("step.finish", kind=self._kind):
                self.tokens = self.tokens.at[st.lane, 0].set(
                    sample(logits)[0])
                self._finish_prefill(st)
            self.stats.prefill_steps += 1

    def _finish_prefill(self, st: _Prefilling) -> None:
        r = st.req
        del self._prefilling[r.req_id]
        self.pos[st.lane] = r.prompt_len
        self.slot_req[st.lane] = r
        self.slot_left[st.lane] = r.output_len - 1
        rec = self.records[r.req_id]
        if not np.isfinite(rec.first_token_at):
            # a re-admitted request (rank failure re-prefilled it) keeps
            # its original first-token time — TTFT measures the first
            # byte the client saw, not the recovery replay
            rec.first_token_at = self.stats.virtual_time
        if r.output_len <= 1:
            rec.finished_at = self.stats.virtual_time
            self.stats.useful_tokens += r.prompt_len
            self._release(st.lane)

    def _exec_decode(self) -> None:
        with span("step.launch", kind=self._kind):
            active = [b for b in range(self.max_batch)
                      if self.slot_req[b] is not None]
            pos = jnp.asarray(np.minimum(self.pos, self.max_seq - 1),
                              jnp.int32)
            logits, self.cache, tallies = self._decode(
                self.params, self.tokens, self.cache, pos, self.moe_tables)
            self.tokens = sample(logits)[:, None]
        self._sync_observe(tallies, float(len(active)))
        with span("step.finish", kind=self._kind):
            self.stats.decode_tokens += len(active)
            for b in active:
                if self.pos[b] < self.max_seq:
                    # the new token occupied a fresh cache row (beyond
                    # max_seq the write is clamped onto the last row)
                    self.kv.extend(self.slot_req[b].req_id)
                self.pos[b] += 1
                self.slot_left[b] -= 1
                if self.slot_left[b] <= 0 \
                        or self.pos[b] >= self.max_seq - 1:
                    r = self.slot_req[b]
                    rec = self.records[r.req_id]
                    rec.finished_at = self.stats.virtual_time
                    # decode participations so far = (output_len-1) -
                    # slot_left (exact even for the early max_seq-clamp
                    # finish)
                    self.stats.useful_tokens += r.prompt_len + max(
                        int(r.output_len - 1 - self.slot_left[b]), 0)
                    self._release(b)
        self.stats.decode_steps += 1

    def run(self, max_steps: int = 10_000) -> List[RequestRecord]:
        for _ in range(max_steps):
            if not self.step():
                break
        return list(self.records.values())
