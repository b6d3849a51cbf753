"""Chunked prefill: model-level bit-identity + engine/serve integration.

The virtual clock prices every chunk, so correctness rests on the chunk
path being *exactly* the whole-prompt computation re-sliced: masked tail
rows contribute exact zeros to attention and tallies (flash kernel's
``exp(_NEG - m)`` underflow), so logits, cache state and MoE tallies are
bit-identical across chunk widths — pinned here, not approximated.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import (DriftConfig, ViBEConfig, ViBEController,
                        make_cluster)
from repro.models import (init_cache, init_params, make_moe_tables,
                          moe_perm_shape, prefill_chunk_fn, prefill_fn)
from repro.serving import (Engine, EngineConfig, RejectReason,
                           SchedulerConfig, WORKLOADS, Request,
                           sample_requests, summarize)

ARCH = "qwen3-moe-235b-a22b"


def _chunked_run(cfg, params, cache, prompt, chunk, lane, mt):
    """Drive prefill_chunk_fn over ``prompt`` exactly as the engine does:
    fixed-width buffers, n_valid tail masking, offset = tokens done."""
    fn = jax.jit(prefill_chunk_fn(cfg))
    P = prompt.shape[1]
    tallies = None
    logits = None
    done = 0
    while done < P:
        n_valid = min(chunk, P - done)
        buf = np.zeros((1, chunk), dtype=prompt.dtype)
        buf[0, :n_valid] = prompt[0, done:done + n_valid]
        logits, cache, t = fn(params, jnp.asarray(buf), cache, lane, done,
                              n_valid, mt)
        tallies = t if tallies is None else tallies + t
        done += n_valid
    return logits, cache, tallies


class TestModelLevel:
    def setup_method(self):
        self.cfg = get_smoke(ARCH)
        self.params = init_params(self.cfg, jax.random.PRNGKey(0))
        self.mt = make_moe_tables(self.cfg, None)
        rng = np.random.default_rng(3)
        self.prompt = rng.integers(0, self.cfg.vocab, size=(1, 10))
        # dirty cache: masking bugs show up as garbage leaking into
        # attention instead of silently reading zeros
        self.S_max = 16
        zero = init_cache(self.cfg, 2, self.S_max)
        self.cache = jax.tree.map(
            lambda c: jnp.asarray(
                np.random.default_rng(7).normal(size=c.shape), c.dtype),
            zero)

    def test_bit_identical_across_chunk_widths(self):
        lg_a, cache_a, tal_a = _chunked_run(self.cfg, self.params,
                                            self.cache, self.prompt, 5, 0,
                                            self.mt)
        lg_b, cache_b, tal_b = _chunked_run(self.cfg, self.params,
                                            self.cache, self.prompt, 2, 0,
                                            self.mt)
        assert np.array_equal(np.asarray(lg_a), np.asarray(lg_b))
        assert np.array_equal(np.asarray(tal_a), np.asarray(tal_b))
        for a, b in zip(jax.tree.leaves(cache_a), jax.tree.leaves(cache_b)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_matches_whole_prompt_prefill(self):
        lg_w, _, tal_w = prefill_fn(self.cfg)(
            self.params, {"tokens": jnp.asarray(self.prompt)}, self.mt)
        lg_c, _, tal_c = _chunked_run(self.cfg, self.params, self.cache,
                                      self.prompt, 4, 1, self.mt)
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_w),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(tal_c), np.asarray(tal_w),
                                   atol=0)

    def test_cache_matches_whole_prompt_prefill(self):
        # cache leaves are (layers, lane, kv_heads, seq, head_dim); the
        # lane's prompt rows are the ones whole-prompt prefill emits
        P = self.prompt.shape[1]
        _, want, _ = prefill_fn(self.cfg)(
            self.params, {"tokens": jnp.asarray(self.prompt)}, self.mt)
        _, cache, _ = _chunked_run(self.cfg, self.params, self.cache,
                                   self.prompt, 4, 1, self.mt)
        for got, w in zip(jax.tree.leaves(cache), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(got)[:, 1, :, :P],
                                  np.asarray(w)[:, 0])

    def test_other_lane_untouched(self):
        _, cache, _ = _chunked_run(self.cfg, self.params, self.cache,
                                   self.prompt, 4, 0, self.mt)
        # cache leaves are (layers, lane, kv_heads, seq, head_dim)
        for before, after in zip(jax.tree.leaves(self.cache),
                                 jax.tree.leaves(cache)):
            assert np.array_equal(np.asarray(before)[:, 1],
                                  np.asarray(after)[:, 1])

    def test_ssm_mixers_rejected(self):
        with pytest.raises(NotImplementedError, match="recurrent"):
            prefill_chunk_fn(get_smoke("xlstm-350m"))


def _engine(config, seed=0):
    cfg = get_smoke(ARCH)
    n_moe, n_slots = moe_perm_shape(cfg, None, "train")
    cluster = make_cluster(4, "mi325x", d_model=cfg.d_model,
                           d_ff=cfg.moe_d_ff,
                           experts_per_rank=n_slots // 4, seed=seed)
    ctl = ViBEController(
        n_moe, n_slots, 4, cluster.fit_models(),
        ViBEConfig(policy="vibe", adaptive=True,
                   drift=DriftConfig(window=8, interval=4, cooldown=4),
                   expert_bytes=3 * cfg.d_model * cfg.moe_d_ff * 2))
    return Engine(cfg, config, controller=ctl, cluster=cluster)


class TestEngineChunked:
    def test_chunked_engine_serves_and_frees_kv(self):
        eng = _engine(EngineConfig(
            max_batch=2, max_seq=48, seed=0,
            scheduler=SchedulerConfig(name="slo_edf", prefill_chunk=8)))
        reqs = sample_requests(WORKLOADS["sharegpt"], 4, qps=100.0, seed=0)
        reqs = [dataclasses.replace(r, prompt_len=20, output_len=6)
                for r in reqs]
        eng.submit(reqs)
        records = eng.run(max_steps=300)
        done = [r for r in records if np.isfinite(r.finished_at)]
        assert len(done) == 4
        assert eng.stats.chunk_steps >= 4 * 3     # 20 tokens = 3 chunks of 8
        assert eng.kv.n_seqs == 0                 # every reservation freed
        assert eng.kv.used_blocks == 0
        assert eng.kv.peak_blocks > 0

    def test_oversized_prompt_rejected_at_submit(self):
        # typed rejection, not an exception: submit returns the rejected
        # records and tags them TOO_LONG (chaos invariant: every request
        # finishes or carries a typed RejectReason)
        eng = _engine(EngineConfig(max_batch=2, max_seq=48, seed=0))
        rejected = eng.submit([Request(0, 0.0, 100, 4)])
        assert len(rejected) == 1
        assert rejected[0].reject_reason is RejectReason.TOO_LONG
        assert eng.records[0].rejected
        assert eng.stats.rejected == {"too_long": 1}
        assert not eng.waiting                    # never queued
        records = eng.run(max_steps=10)
        assert summarize(records)["n_rejected"] == 1


@pytest.mark.slow
class TestSloAcceptance:
    def test_chunked_edf_beats_whole_prompt_fcfs_p90_ttft(self):
        """ISSUE 6 acceptance: on a saturating bursty mix — a burst of
        long-context requests hogging the lanes ahead of tight-SLO chat
        traffic — chunked prefill + slo_edf improves the chat tenant's
        P90 TTFT by >= 25% over the legacy whole-prompt FCFS loop: EDF
        admits chats ahead of the queued long-context backlog as lanes
        free, instead of draining the backlog in arrival order."""
        def mix():
            longs = [Request(i, 0.0, 24, 30, tenant="longctx",
                             ttft_slo=10.0) for i in range(4)]
            chats = [Request(10 + i, 0.001 + i * 1e-4, 8, 4, tenant="chat",
                             ttft_slo=0.05) for i in range(8)]
            return longs + chats

        def chat_p90(records):
            return summarize([r for r in records
                              if r.req_id >= 10])["ttft_p90"]

        legacy = _engine(EngineConfig(max_batch=2, max_seq=48, seed=0))
        legacy.submit(mix())
        p90_legacy = chat_p90(legacy.run(max_steps=2000))

        chunked = _engine(EngineConfig(
            max_batch=2, max_seq=48, seed=0,
            scheduler=SchedulerConfig(name="slo_edf", prefill_chunk=12)))
        chunked.submit(mix())
        p90_chunked = chat_p90(chunked.run(max_steps=2000))

        assert p90_chunked <= 0.75 * p90_legacy, \
            f"chat p90 TTFT {p90_chunked:.6f}s vs legacy {p90_legacy:.6f}s"

    def test_serve_e2e_thermal_ramp_with_scheduler(self):
        """vibe_r recalibration keeps recovering goodput with the full
        serving core on: slo_edf + chunked prefill + bursty trace +
        thermal-ramp hardware drift + perf-model refresh."""
        from repro.launch.serve import serve
        engine, records, _ = serve(
            ARCH, policy="vibe_r", n_requests=8, workload="bursty",
            scheduler="slo_edf", prefill_chunk=12, max_seq=96,
            variability_scenario="thermal-ramp", scenario_start=0.0,
            scenario_duration=1.0, perf_drift_delta=0.15, seed=0)
        done = [r for r in records if np.isfinite(r.finished_at)]
        assert len(done) == 8
        assert engine.stats.migrations > 0        # recalibration fired
        assert engine.stats.chunk_steps > 0
        assert engine.kv.used_blocks == 0
