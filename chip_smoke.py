"""Bring-up check on a TPU: the served path at published width, end to end.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the sharded path on four chips only

One process, no children. With one chip the phases are, in order:

1. ``kernels`` — the three Pallas kernels through ``repro.kernels.ops`` at
   granite-moe-3b-a800m widths. Each compiled program must hold a
   ``tpu_custom_call`` (compiled, not interpreted), and each output must
   match ``repro.kernels.ref`` computed in f32. The ragged dispatch around
   the ragged kernel (``moe_layer`` with ``moe_impl="ragged"``) must match
   the dense oracle.
2. ``serve`` — ``serve()`` on granite-moe-3b-a800m at its published width
   (32 layers, d_model 1536, 40 experts, top-8), policy vibe_r, 8 lanes x
   2048 positions, 256-token prefill chunks, 8 requests. Every request must
   finish; decode and chunk logits must be finite; each layer's tallies
   must sum to tokens x top_k; a second episode after warm-up must add no
   entry to any step function's jit cache.
3. ``smoke_vs_cpu`` — the smoke config's prefill and decode on the chip
   against the same calls on the CPU device of this process.

With ``--chips 4`` only the multi-chip path runs, each part against one
chip: the EP dispatch battery (a2a prefill, replicated decode, weighted
replica routing) at granite widths on a ('data', 'model') = (1, 4) mesh,
and one full-width granite prefill + decode step placed by ``make_rules``
and ``tree_shardings``.

The TTFT/TPOT that ``serve`` prints come from the engine's virtual clock
(``Engine._charge``), a model, and are labelled so. The host wall clock of
the serve run, the seconds spent compiling (XLA backend compiles, and
loads from JAX's persistent compilation cache) and the device's
``peak_bytes_in_use`` are printed next to them.

Without a TPU, or run from a directory that holds no ``src/repro``, the
script exits non-zero before any phase and prints no result. A failed
phase is reported with its traceback; the remaining phases still run and
the script exits 1. On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ARCH = "granite-moe-3b-a800m"
#: bf16 kernel tolerance, as in tests/test_kernels.py: bf16 inputs and a
#: bf16 output (rel. spacing 2^-8) against an f32 reference
KERNEL_TOL = 5e-2
#: chip vs CPU on the smoke config, max |a - b| / max |b| over the logits.
#: Both run with f32 weights under "highest" matmul precision, so what
#: remains is f32 rounding (~1e-7) grown through 3 layers and the chips'
#: own transcendentals. In bf16 the two backends round intermediates at
#: different points, and the logits differed by up to 2.6e-2 on a v5e.
SMOKE_TOL = 1e-3
#: sharded vs one-chip dispatch at granite widths (one MoE layer), same
#: measure and precision; tallies must match exactly
EP_TOL = 2e-2
#: sharded vs one-chip full-depth granite step, relative L2 error of the
#: logits. Over 32 bf16 layers a different reduction order can flip a
#: near-tie top-8 routing choice, which a per-element bound would not
#: survive; a wrong sharding gives errors of order 1.
STEP_TOL = 0.1

_compile = collections.Counter()


def _on_event(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["backend_s"] += duration
        _compile["n"] += 1
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _compile["cache_load_s"] += duration


def _rel_max(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get
    from repro.kernels import ops, ref
    from repro.kernels.ragged_moe_ffn import ragged_tile_metadata
    from repro.models import moe as MOE
    from repro.models.sharding import ShardingRules

    cfg = get(ARCH)
    D, F, E, K = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.top_k
    T, bm = 256, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    w1 = (jax.random.normal(ks[0], (E, D, F)) / np.sqrt(D)).astype(jnp.bfloat16)
    w3 = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(jnp.bfloat16)
    w2 = (jax.random.normal(ks[2], (E, F, D)) / np.sqrt(F)).astype(jnp.bfloat16)

    def oracle(fn, *args):
        """``fn`` in f32 at full matmul precision (on a TPU the default
        precision would round f32 matmul inputs to bf16)."""
        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(*[a.astype(jnp.float32) if a.dtype ==
                                   jnp.bfloat16 else a for a in args]))

    def err(y, y_ref):
        y = np.asarray(y, np.float32)
        np.testing.assert_allclose(y, y_ref, atol=KERNEL_TOL, rtol=KERNEL_TOL)
        return float(np.abs(y - y_ref).max())

    def compiled_native(fn, *args) -> None:
        text = jax.jit(fn).lower(*args).compile().as_text()
        _check("tpu_custom_call" in text,
               f"{getattr(fn, '__name__', fn)} was not compiled to a "
               "Mosaic kernel")

    # router: softmax top-k over all E experts of T tokens
    logits = jax.random.normal(ks[3], (T, E), jnp.float32)
    router = lambda l: ops.router_topk(l, K)
    compiled_native(router, logits)
    w, idx = router(logits)
    w_ref, idx_ref = ref.router_topk_ref(logits, K)
    same = float(np.mean(np.asarray(idx) == np.asarray(idx_ref)))
    _check(same == 1.0, f"router indices agree on {same:.4f} only")
    err_r = float(np.abs(np.asarray(w) - np.asarray(w_ref)).max())
    _check(err_r <= 1e-5, f"router weights max err {err_r}")

    # fused capacity-bucket FFN: E buckets of T*K/E rows
    toks = jax.random.normal(ks[4], (E, T * K // E, D)).astype(jnp.bfloat16)
    compiled_native(ops.fused_moe_ffn, w1, w3, w2, toks)
    err_f = err(ops.fused_moe_ffn(w1, w3, w2, toks),
                oracle(ref.moe_ffn_ref, w1, w3, w2, toks))

    # ragged FFN: T*K assignments over E skewed groups, bm-row tiles
    sizes = np.random.default_rng(0).multinomial(
        T * K, np.random.default_rng(1).dirichlet(np.full(E, 0.3)))
    n_tiles = T * K // bm + E
    offs, tile_group = ragged_tile_metadata(jnp.asarray(sizes), bm, n_tiles)
    rows = jax.random.normal(ks[5], (n_tiles * bm, D)).astype(jnp.bfloat16)
    compiled_native(ops.ragged_moe_ffn, w1, w3, w2, rows, tile_group)
    err_g = err(ops.ragged_moe_ffn(w1, w3, w2, rows, tile_group),
                oracle(ref.ragged_moe_ffn_ref, w1, w3, w2, rows, tile_group))
    print(f"[kernels] router/fused/ragged compiled to tpu_custom_call at "
          f"D={D} F={F} E={E} K={K}; router idx equal, weights err "
          f"{err_r:.2e}; fused / ragged max abs err {err_f:.2e} / "
          f"{err_g:.2e} vs the f32 ref (tolerance {KERNEL_TOL} abs + rel) "
          f"(ragged: {int(offs[-1]) // bm} occupied of {n_tiles} tiles)")

    # the ragged dispatch around the kernel (sort plan, buffer, combine)
    # through moe_layer on one chip, against the dense oracle. Both run at
    # the default precision: Mosaic refuses the kernel's bf16 matmuls under
    # "highest", and both compute the router identically, so the routing
    # and the tallies agree.
    p = MOE.moe_init(ks[3], d=D, f=F, n_experts=E, n_slots=E)
    x = jax.random.normal(ks[4], (4, 64, D)).astype(jnp.bfloat16)
    rules = ShardingRules(mesh=None, moe_impl="ragged", use_kernel=True)
    layer = lambda r: (lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=r))
    compiled_native(layer(rules), p, x)
    y, t, _ = jax.jit(layer(rules))(p, x)
    y_ref, t_ref, _ = jax.jit(layer(None))(p, x)
    err_d = _rel_max(y, y_ref)
    _check(err_d <= KERNEL_TOL,
           f"ragged dispatch rel. max err {err_d} > {KERNEL_TOL}")
    _check(np.array_equal(np.asarray(t), np.asarray(t_ref)),
           "ragged dispatch tallies differ from the dense oracle")
    print(f"[kernels] ragged dispatch (Pallas FFN) through moe_layer == dense "
          f"bf16 oracle: rel. max err {err_d:.2e} (<= {KERNEL_TOL}), tallies "
          f"equal")


def _cache_sizes(engine) -> dict:
    return {name: getattr(engine, name)._cache_size()
            for name in ("_prefill", "_decode", "_prefill_chunk")}


def phase_serve() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import serve
    from repro.serving import WORKLOADS, sample_requests, summarize

    B, S, C, n_req = 8, 2048, 256, 8
    before = dict(_compile)
    t0 = time.perf_counter()
    engine, records, _ = serve(ARCH, smoke=False, policy="vibe_r",
                               n_requests=n_req, max_batch=B, max_seq=S,
                               prefill_chunk=C)
    wall = time.perf_counter() - t0
    cfg = engine.cfg
    _check((cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.top_k)
           == (32, 1536, 40, 8), f"not the published config: {cfg}")
    done = [r for r in records if np.isfinite(r.finished_at)]
    _check(len(records) == n_req and len(done) == n_req,
           f"{len(done)}/{len(records)} requests finished")
    st = engine.stats
    s = summarize(records)
    print(f"[serve] {ARCH} at published width, vibe_r, {engine.n_slots} "
          f"expert slots/layer: {st.steps} steps ({st.chunk_steps} chunks / "
          f"{st.decode_steps} decode), {n_req}/{n_req} requests finished")
    print(f"[serve] virtual clock (modelled by Engine._charge, not "
          f"measured): virtual time {st.virtual_time:.4f}s, TTFT p50/p90 "
          f"{s['ttft_p50']:.4f}/{s['ttft_p90']:.4f}s, TPOT p50 "
          f"{s['tpot_p50']:.5f}s, recalibrations {st.migrations}")
    comp = _compile["backend_s"] - before.get("backend_s", 0.0)
    load = _compile["cache_load_s"] - before.get("cache_load_s", 0.0)
    print(f"[serve] host wall clock {wall:.2f}s, of which XLA compile "
          f"{comp:.2f}s ({_compile['n'] - before.get('n', 0)} compiles) and "
          f"compilation-cache loads {load:.2f}s")

    # logits and tallies of the compiled steps on the engine's live state
    warm = _cache_sizes(engine)
    pos = jnp.asarray(np.minimum(engine.pos, S - 1), jnp.int32)
    # the step programs donate the cache: the engine keeps their output
    logits, engine.cache, tall = engine._decode(
        engine.params, engine.tokens, engine.cache, pos, engine.moe_tables)
    logits, tall = np.asarray(logits), np.asarray(tall)
    _check(logits.shape == (B, cfg.vocab) and np.isfinite(logits).all(),
           f"decode logits {logits.shape} not finite")
    per_layer = tall[:, :cfg.n_experts].sum(1)
    _check(tall.shape[0] == 32 and np.all(per_layer == B * cfg.top_k),
           f"decode tallies per layer {per_layer} != {B * cfg.top_k}")
    n_valid = 100
    buf = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (1, C)), jnp.int32)
    logits, engine.cache, tall = engine._prefill_chunk(
        engine.params, buf, engine.cache, 0, 0, n_valid, engine.moe_tables)
    logits, tall = np.asarray(logits), np.asarray(tall)
    _check(np.isfinite(logits).all(), "chunk logits not finite")
    per_layer = tall[:, :cfg.n_experts].sum(1)
    _check(np.all(per_layer == n_valid * cfg.top_k),
           f"chunk tallies per layer {per_layer} != {n_valid * cfg.top_k}")

    # a second episode after warm-up compiles nothing
    more = sample_requests(WORKLOADS["sharegpt"], 2, qps=50.0, seed=1)
    more = [dataclasses.replace(r, req_id=1000 + i,
                                prompt_len=min(r.prompt_len, S // 2),
                                output_len=16)
            for i, r in enumerate(more)]
    engine.submit(more)
    records = engine.run()
    _check(sum(np.isfinite(r.finished_at) for r in records) == n_req + 2,
           "second episode did not finish")
    after = _cache_sizes(engine)
    _check(after == warm, f"jit caches grew after warm-up: {warm} -> {after}")
    stats = jax.devices()[0].memory_stats()
    print(f"[serve] decode/chunk logits finite, tallies = tokens x top_k on "
          f"all 32 layers, jit caches {after} unchanged by a second episode; "
          f"peak_bytes_in_use {stats['peak_bytes_in_use']} of bytes_limit "
          f"{stats['bytes_limit']}")


def _prefill_then_decode(pre, dec, params, tokens, mt, max_seq):
    """Prefill ``tokens``, pad the cache to ``max_seq``, decode one token
    (the prefill's argmax) at position S. Returns both logits."""
    import jax
    import jax.numpy as jnp
    S = tokens.shape[1]
    logits, cache, _ = pre(params, {"tokens": tokens}, mt)
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, 0), (0, max_seq - S),
                              (0, 0)]), cache)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((tokens.shape[0],), S, jnp.int32)
    logits_d, _, _ = dec(params, nxt, cache, pos, mt)
    return logits, logits_d


def phase_smoke_vs_cpu() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke
    from repro.models import decode_fn, init_params, make_moe_tables, \
        prefill_fn

    cfg = get_smoke(ARCH)
    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mt = make_moe_tables(cfg, None)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (2, 24))
    pre, dec = jax.jit(prefill_fn(cfg)), jax.jit(decode_fn(cfg))
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, dev in (("tpu", tpu), ("cpu", cpu)):
            p, m, t = jax.device_put((params, mt, tokens.astype(np.int32)),
                                     dev)
            out[name] = [np.asarray(a) for a in
                         _prefill_then_decode(pre, dec, p, t, m, 64)]
    errs = [_rel_max(a, b) for a, b in zip(out["tpu"], out["cpu"])]
    _check(max(errs) <= SMOKE_TOL,
           f"chip vs CPU prefill/decode rel. max err {errs} > {SMOKE_TOL}")
    print(f"[smoke_vs_cpu] {cfg.name}: prefill / decode logits on the chip "
          f"vs CPU, rel. max err {errs[0]:.2e} / {errs[1]:.2e} "
          f"(<= {SMOKE_TOL})")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_ep_battery() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models import moe as MOE
    from repro.models.sharding import ShardingRules, build_copy_cdf, \
        build_slots_of

    cfg = get(ARCH)
    E, D, F, K = cfg.n_experts, cfg.d_model, cfg.moe_d_ff, cfg.top_k
    mesh = make_mesh((1, 4), ("data", "model"))
    p = MOE.moe_init(jax.random.PRNGKey(0), d=D, f=F, n_experts=E, n_slots=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, D)).astype(
        jnp.bfloat16)
    a2a = ShardingRules(mesh=mesh, dp=("data",), ep=("model",), fsdp=None)
    rep = ShardingRules(mesh=mesh, dp=("data",), ep=("model",),
                        ep_all=("data", "model"), fsdp=None,
                        moe_dispatch="replicated")
    # weighted replicas: experts 0..7 get a second slot, shares 0.25/0.75
    perm = np.concatenate([np.arange(E), np.arange(8)])[None].astype(np.int32)
    share = np.ones((1, perm.shape[1]))
    share[0, :8], share[0, E:] = 0.25, 0.75
    pw = {k: (v if k == "router" else v[perm[0]]) for k, v in p.items()}
    so, nc = build_slots_of(perm, E, perm.shape[1])
    tables = dict(slots_of=jnp.asarray(so[0]), n_copies=jnp.asarray(nc[0]),
                  copy_cdf=jnp.asarray(build_copy_cdf(
                      perm, E, perm.shape[1], share=share)[0]))
    cases = [("a2a prefill", p, a2a, "prefill", {}),
             ("replicated decode", p, rep, "decode", {}),
             ("a2a + weighted replicas", pw, a2a, "prefill", tables),
             ("replicated + weighted replicas", pw, rep, "decode", tables)]
    with jax.default_matmul_precision("highest"):
        # the oracle: the dense dispatch on one chip
        y_ref, t_ref, _ = jax.jit(lambda p, x: MOE.moe_layer(
            p, x, top_k=K, n_experts=E, rules=None))(p, x)
        for tag, params, rules, phase, tb in cases:
            with jax.set_mesh(mesh):
                y, t, _ = jax.jit(lambda p, x: MOE.moe_layer(
                    p, x, top_k=K, n_experts=E, rules=rules, phase=phase,
                    **tb))(params, x)
            err = _rel_max(y, y_ref)
            _check(err <= EP_TOL, f"{tag}: rel. max err {err} > {EP_TOL}")
            _check(np.array_equal(np.asarray(t), np.asarray(t_ref)),
                   f"{tag}: tallies differ from the one-chip oracle")
            print(f"[ep_battery] {tag} on (1, 4) mesh == one-chip dense "
                  f"oracle at D={D} F={F} E={E} K={K}: rel. max err "
                  f"{err:.2e}, tallies equal")


def phase_sharded_step() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import cache_specs, make_rules, param_specs, \
        tree_shardings
    from repro.models import decode_fn, init_params, make_moe_tables, \
        prefill_fn

    cfg = get(ARCH)
    B, S, max_seq = 2, 256, 512
    mesh = make_mesh((1, 4), ("data", "model"))
    r_pre = make_rules(cfg, mesh, "prefill")
    r_dec = make_rules(cfg, mesh, "decode")
    # parameters are made in place, each shard on its own chip
    params = jax.jit(lambda k: init_params(cfg, k, r_pre, "prefill"),
                     out_shardings=tree_shardings(
                         mesh, param_specs(cfg, r_pre, "prefill")))(
        jax.random.PRNGKey(0))
    one = jax.device_put(params, jax.devices()[0])
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab, (B, S)).astype(np.int32)
    mt_pre = make_moe_tables(cfg, r_pre, phase="prefill")
    mt_dec = make_moe_tables(cfg, r_dec, phase="decode")
    with jax.default_matmul_precision("highest"):
        ref = _prefill_then_decode(
            jax.jit(prefill_fn(cfg)), jax.jit(decode_fn(cfg)), one, tokens,
            make_moe_tables(cfg, None), max_seq)
        nxt = np.asarray(jnp.argmax(ref[0], -1), np.int32)[:, None]
        with jax.set_mesh(mesh):
            logits, cache, tall = jax.jit(prefill_fn(cfg, r_pre))(
                params, {"tokens": tokens}, mt_pre)
            cache = jax.tree.map(
                lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, 0),
                                      (0, max_seq - S), (0, 0)]), cache)
            _, cspec = cache_specs(cfg, r_dec, B, max_seq)
            cache = jax.device_put(cache, tree_shardings(mesh, cspec))
            p_dec = jax.device_put(params, tree_shardings(
                mesh, param_specs(cfg, r_dec, "decode")))
            logits_d, _, tall_d = jax.jit(decode_fn(cfg, r_dec))(
                p_dec, nxt, cache, jnp.full((B,), S, jnp.int32), mt_dec)
    errs = [_rel_l2(logits, ref[0]), _rel_l2(logits_d, ref[1])]
    _check(max(errs) <= STEP_TOL,
           f"sharded vs one-chip prefill/decode rel. L2 err {errs}")
    for name, t, n in (("prefill", tall, B * S), ("decode", tall_d, B)):
        per_layer = np.asarray(t)[:, :cfg.n_experts].sum(1)
        _check(np.all(per_layer == n * cfg.top_k),
               f"{name} tallies per layer {per_layer} != {n * cfg.top_k}")
    print(f"[sharded_step] {ARCH} full depth on (1, 4) mesh via make_rules/"
          f"tree_shardings vs one chip: prefill / decode logits rel. L2 err "
          f"{errs[0]:.2e} / {errs[1]:.2e} (<= {STEP_TOL}); tallies = tokens "
          f"x top_k on all layers")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, serve and chip-vs-CPU phases; 4: only "
                         "the sharded EP path against one chip")
    args = ap.parse_args()
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {count}", file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x{count}; compilation "
          f"cache {cache_dir}", flush=True)
    phases = ([phase_ep_battery, phase_sharded_step] if args.chips == 4
              else [phase_kernels, phase_serve, phase_smoke_vs_cpu])
    failed = []
    t_start = time.perf_counter()
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:                 # report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAIL after {time.perf_counter() - t0:.1f}s",
                  flush=True)
            continue
        print(f"[{name}] PASS in {time.perf_counter() - t0:.1f}s", flush=True)
    print(f"[total] {time.perf_counter() - t_start:.1f}s wall, XLA compile "
          f"{_compile['backend_s']:.2f}s over {_compile['n']} compiles, "
          f"cache loads {_compile['cache_load_s']:.2f}s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
