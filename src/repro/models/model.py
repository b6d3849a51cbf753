"""Model assembly: every assigned architecture as one scanned-block program.

The model is a ``lax.scan`` over *super-blocks*. A super-block is the
smallest repeating structural unit of the architecture:

* dense / moe / audio / vlm : 1 layer  (gemma3's local/global pattern is
  *data* — a per-layer window array — not structure)
* jamba  : 8 layers (1 attention + 7 Mamba; MoE on odd positions)
* xlstm  : ``slstm_every`` layers (1 sLSTM + rest mLSTM)

All per-block params carry a leading ``n_blocks`` axis, so XLA compiles one
block body regardless of depth — essential for 94-layer dry-run compiles.

Three entry points (the dry-run lowers exactly these):

* :func:`loss_fn`     — training forward → (loss, (tallies, aux))
* :func:`prefill_fn`  — (tokens → last-position logits, filled cache)
* :func:`decode_fn`   — (one token + cache → logits, cache)  [serve_step]

MoE placement enters as the ``moe_tables`` *input* (slot lookup arrays), so
ViBE recalibration never recompiles — see models/moe.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from .attention import attn_init
from .common import apply_rope, dense_init, mlp, mlp_init, rms_norm, \
    rope_tables, softmax_xent_chunked
from .flash import flash_attention, flash_decode
from .moe import (default_perm_a2a, default_perm_replicated, moe_init,
                  moe_layer, n_slots_a2a)
from .sharding import ShardingRules, build_copy_cdf, build_slots_of
from . import ssm

__all__ = [
    "LayerSpec", "block_layout", "init_params", "make_moe_tables",
    "loss_fn", "prefill_fn", "prefill_chunk_fn", "decode_fn", "init_cache",
    "moe_perm_shape", "count_params",
]


# ---------------------------------------------------------------------------
# structural layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str                       # attn | mamba | mlstm | slstm
    ffn: str                         # dense | moe | none


def block_layout(cfg: ArchConfig) -> Tuple[int, List[LayerSpec]]:
    """(n_blocks, per-position layer specs)."""
    if cfg.family == "ssm":
        bs = cfg.slstm_every or 1
    elif cfg.attn_every:
        bs = math.lcm(cfg.attn_every, cfg.moe_every if cfg.is_moe else 1)
    else:
        bs = 1
    if cfg.n_layers % bs:
        raise ValueError(f"{cfg.name}: n_layers={cfg.n_layers} % block={bs}")
    specs = []
    for i in range(bs):
        if cfg.family == "ssm":
            mixer = "slstm" if (cfg.slstm_every and i % cfg.slstm_every == 0) \
                else "mlstm"
        elif cfg.attn_every and i % cfg.attn_every != 0:
            mixer = "mamba"
        else:
            mixer = "attn"
        if cfg.is_moe and i % cfg.moe_every == cfg.moe_offset:
            ffn = "moe"
        elif cfg.d_ff:
            ffn = "dense"
        else:
            ffn = "none"
        specs.append(LayerSpec(mixer, ffn))
    return cfg.n_layers // bs, specs


def _windows(cfg: ArchConfig) -> Optional[np.ndarray]:
    """(n_blocks, block_size) sliding-window sizes (0 = full attention)."""
    nb, specs = block_layout(cfg)
    if cfg.window <= 0:
        return None
    win = np.zeros((cfg.n_layers,), np.int32)
    for l in range(cfg.n_layers):
        is_global = cfg.global_every and (l % cfg.global_every
                                          == cfg.global_every - 1)
        win[l] = 0 if is_global else cfg.window
    return win.reshape(nb, len(specs))


def moe_perm_shape(cfg: ArchConfig, rules: Optional[ShardingRules],
                   phase: str) -> Tuple[int, int]:
    """(n_moe_layers, n_slots) for building placement permutations."""
    nb, specs = block_layout(cfg)
    n_moe = nb * sum(1 for s in specs if s.ffn == "moe")
    if rules is None or rules.mesh is None:
        return n_moe, cfg.n_experts
    if phase == "decode":
        fleet = (rules.ep_size if rules.decode_expert_tp
                 else rules.ep_all_size)
        e_loc = max(1, -(-cfg.n_experts // max(fleet, 1)))
        return n_moe, e_loc * max(fleet, 1)
    return n_moe, n_slots_a2a(cfg.n_experts, rules.ep_size)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key, rules: Optional[ShardingRules] = None,
                phase: str = "train", dtype=jnp.bfloat16) -> Dict[str, Any]:
    nb, specs = block_layout(cfg)
    _, n_slots = moe_perm_shape(cfg, rules, phase) if cfg.is_moe else (0, 0)
    d, hd = cfg.d_model, cfg.hd
    keys = jax.random.split(key, 8 + len(specs))

    def stacked(init_one, k):
        ks = jax.random.split(k, nb)
        return jax.vmap(init_one)(ks)

    layers = []
    for i, spec in enumerate(specs):
        ki = keys[8 + i]

        def init_layer(k, spec=spec):
            sub = dict(ln1=jnp.zeros((d,), jnp.float32))
            kk = jax.random.split(k, 3)
            if spec.mixer == "attn":
                sub["mixer"] = attn_init(kk[0], d, cfg.n_heads,
                                         cfg.n_kv_heads, hd, dtype)
            elif spec.mixer == "mamba":
                sub["mixer"] = ssm.mamba_init(
                    kk[0], d, expand=cfg.ssm_expand, d_state=cfg.ssm_d_state,
                    d_conv=cfg.ssm_conv, dtype=dtype)
            elif spec.mixer == "mlstm":
                sub["mixer"] = ssm.mlstm_init(
                    kk[0], d, n_heads=cfg.n_heads, expand=cfg.ssm_expand,
                    dtype=dtype)
            else:
                sub["mixer"] = ssm.slstm_init(
                    kk[0], d, n_heads=cfg.n_heads, expand=cfg.ssm_expand,
                    dtype=dtype)
            if spec.ffn != "none":
                sub["ln2"] = jnp.zeros((d,), jnp.float32)
            if spec.ffn == "dense":
                sub["ffn"] = mlp_init(kk[1], d, cfg.d_ff, cfg.mlp_gated, dtype)
            elif spec.ffn == "moe":
                sub["ffn"] = moe_init(kk[1], d=d, f=cfg.moe_d_ff,
                                      n_experts=cfg.n_experts,
                                      n_slots=n_slots, dtype=dtype)
                if cfg.n_shared_experts:
                    sub["shared"] = mlp_init(
                        kk[2], d, cfg.n_shared_experts * cfg.moe_d_ff,
                        cfg.mlp_gated, dtype)
            return sub

        layers.append(stacked(init_layer, ki))

    params: Dict[str, Any] = {
        "embed": dense_init(keys[0], cfg.vocab, d, dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "blocks": layers,
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], d, cfg.vocab, dtype)
    if cfg.frontend_dim:
        params["frontend"] = dense_init(keys[2], cfg.frontend_dim, d, dtype)
    return params


def count_params(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


def make_moe_tables(cfg: ArchConfig, rules: Optional[ShardingRules],
                    perm: Optional[np.ndarray] = None,
                    phase: str = "train",
                    n_slots: Optional[int] = None,
                    share: Optional[np.ndarray] = None,
                    r_max: Optional[int] = None):
    """Build the (slots_of, n_copies, copy_cdf) scan inputs from a placement.

    ``perm``: (n_moe_layers, n_slots) — logical expert per physical slot
    (from a ViBE/EPLB/contiguous/ViBE-R placement; repeated entries are
    replicas); None = contiguous default. ``n_slots`` overrides the
    arch-derived slot count when the caller runs an expanded ViBE-R slot
    budget (extra replica slots beyond one-per-expert).

    ``share``: (n_moe_layers, n_slots) per-slot traffic fractions (a
    ``ReplicatedPlacement.share``) — folded into the cumulative-share table
    the dispatch uses for inverse-CDF replica selection; None = uniform
    split over copies. ``r_max`` pins the copy-axis width so placements
    with different replication degrees keep identical table shapes (the
    no-recompile discipline — tables are jit *inputs*, never statics).

    Returns arrays shaped (n_blocks, moe_per_block, E, r) / (…, E) /
    (…, E, r), or None for non-MoE archs.
    """
    if not cfg.is_moe:
        return None
    nb, specs = block_layout(cfg)
    m = sum(1 for s in specs if s.ffn == "moe")
    n_moe, default_slots = moe_perm_shape(cfg, rules, phase)
    n_slots = default_slots if n_slots is None else int(n_slots)
    if perm is None:
        if rules is not None and rules.mesh is not None and phase == "decode":
            fleet = (rules.ep_size if rules.decode_expert_tp
                     else rules.ep_all_size)
            perm = default_perm_replicated(n_moe, cfg.n_experts, fleet)
        else:
            ep = rules.ep_size if (rules and rules.mesh is not None) else 1
            perm = default_perm_a2a(n_moe, cfg.n_experts, ep)
    perm = np.atleast_2d(perm)
    if perm.shape != (n_moe, n_slots):
        raise ValueError(f"perm shape {perm.shape} != {(n_moe, n_slots)}")
    slots_of, n_copies = build_slots_of(perm, cfg.n_experts, n_slots,
                                        r_max=r_max)
    r = slots_of.shape[-1]
    copy_cdf = build_copy_cdf(perm, cfg.n_experts, n_slots, share=share,
                              r_max=r)
    return (jnp.asarray(slots_of.reshape(nb, m, cfg.n_experts, r)),
            jnp.asarray(n_copies.reshape(nb, m, cfg.n_experts)),
            jnp.asarray(copy_cdf.reshape(nb, m, cfg.n_experts, r)))


def refresh_moe_share_tables(cfg: ArchConfig, moe_tables,
                             perm: np.ndarray, share: np.ndarray):
    """Rebuild only the ``copy_cdf`` entry of ``moe_tables`` for new shares.

    The fast path for dispatch-time share updates (work stealing,
    :mod:`repro.core.steal`): the slot table is unchanged, so ``slots_of``
    and ``n_copies`` — the expensive per-slot enumeration in
    :func:`~repro.models.sharding.build_slots_of` — are reused as-is, and
    only the cumulative-share table is recomputed. The returned tuple has
    identical shapes/dtypes to the input (copy-axis width taken from the
    existing ``slots_of``), so swapping it into a jitted step function
    never recompiles.
    """
    if moe_tables is None:
        return None
    slots_of, n_copies, old_cdf = moe_tables
    nb, m, E, r = old_cdf.shape
    perm = np.atleast_2d(perm)
    copy_cdf = build_copy_cdf(perm, cfg.n_experts, perm.shape[1],
                              share=share, r_max=r)
    return (slots_of, n_copies,
            jnp.asarray(copy_cdf.reshape(nb, m, E, r)))


# ---------------------------------------------------------------------------
# block body
# ---------------------------------------------------------------------------

def _attn_specs(cfg, rules: ShardingRules):
    """(q_spec, kv_spec) activation constraints for the chosen TP mode."""
    if rules is None:
        return None, None
    if rules.attn_mode == "heads" and cfg.n_heads % max(rules.axis_size(rules.tp), 1) == 0 \
            and cfg.n_kv_heads % max(rules.axis_size(rules.tp), 1) == 0:
        return (P(rules.dp, None, rules.tp, None),
                P(rules.dp, None, rules.tp, None))
    # context mode: sequence-sharded q, replicated kv (flash gathers chunks)
    return (P(rules.dp, rules.tp, None, None),
            P(rules.dp, None, None, None))


def _run_attention(p, x, cfg, rules, window, positions, cache=None,
                   pos=None, blk=None):
    """Returns (out, (k, v)) for prefill/train or (out, new_cache) decode.

    K and V come out, and the decode cache is held, as (B, KV, S, hd) per
    layer: the row write and the attention read agree on one layout. In
    decode ``cache`` is every block's stacked (nb, B, KV, S_max, hd) K and
    V and ``blk`` this block's index; one row per lane is written in place.
    """
    B, S, D = x.shape
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, KV, G, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, KV, hd)
    if cache is None:
        cos, sin = rope_tables(positions[None, :], hd, cfg.rope_theta)
        q = apply_rope(q.reshape(B, S, KV * G, hd), cos, sin) \
            .reshape(B, S, KV, G, hd)
        k = apply_rope(k, cos, sin)
        tp_size = 1 if rules is None else rules.axis_size(rules.tp)
        use_cp = (rules is not None and rules.mesh is not None
                  and rules.attn_mode == "context" and S % tp_size == 0
                  and tp_size > 1)
        if use_cp:
            # context-parallel flash (§Perf): each TP rank holds a q
            # sequence shard and the (small, GQA) kv replicated — fully
            # local attention. Constraining alone does NOT survive the
            # chunking reshapes (XLA re-replicates q → S² score traffic).
            dp_sz = max(rules.axis_size(rules.dp), 1)
            b_ax = rules.dp if B % dp_sz == 0 else None
            qspec = rules.spec(b_ax, rules.tp, None, None, None)
            kvspec = rules.spec(b_ax, None, None, None)
            win = window if window is not None else jnp.int32(0)

            def body(q, k, v, qpos, kpos, win):
                return flash_attention(q, k, v, causal=cfg.causal,
                                       window=win, q_positions=qpos,
                                       kv_positions=kpos)

            out = jax.shard_map(
                body, mesh=rules.mesh,
                in_specs=(qspec, kvspec, kvspec, rules.spec(rules.tp),
                          P(), P()),
                out_specs=qspec, check_vma=False,
            )(q, k, v, positions, positions, win)
        else:
            if rules is not None:
                qs, kvs = _attn_specs(cfg, rules)
                if qs is not None:
                    q = rules.constrain(q.reshape(B, S, H, hd), *qs)\
                        .reshape(B, S, KV, G, hd)
                    k = rules.constrain(k, *kvs)
                    v = rules.constrain(v, *kvs)
            out = flash_attention(q, k, v, causal=cfg.causal, window=window,
                                  q_positions=positions,
                                  kv_positions=positions)
        out = out.reshape(B, S, H * hd)
        return (jnp.einsum("bsh,hd->bsd", out, p["wo"]),
                (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)))
    # decode: single token per sequence at per-sequence positions (B,)
    k_stack, v_stack = cache
    S_max = k_stack.shape[3]
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,))
    cos, sin = rope_tables(pos[:, None], hd, cfg.rope_theta)    # (B,1,hd/2)
    q = apply_rope(q.reshape(B, S, KV * G, hd), cos, sin) \
        .reshape(B, KV, G, hd)
    k = apply_rope(k, cos, sin)[:, 0].astype(k_stack.dtype)     # (B,KV,hd)
    v = v[:, 0].astype(v_stack.dtype)
    tp_size = 1 if rules is None else rules.axis_size(rules.tp)
    use_cp = (rules is not None and rules.mesh is not None
              and rules.attn_mode == "context" and tp_size > 1
              and S_max % tp_size == 0)
    if use_cp:
        # context-parallel flash-decode (§Perf): the cache stays
        # sequence-sharded; each TP rank updates/attends its shard and a
        # psum merges the online-softmax stats — no cache gather/halo.
        dp_sz = max(rules.axis_size(rules.dp), 1)
        b_ax = rules.dp if B % dp_sz == 0 else None
        cspec = rules.spec(None, b_ax, None, rules.tp, None)
        qspec = rules.spec(b_ax, None, None, None)
        rowspec = rules.spec(b_ax, None, None)
        s_loc = S_max // tp_size

        def body(q, k1, v1, kc, vc, pos, blk):
            rank = jax.lax.axis_index(rules.tp)
            off = rank * s_loc
            upd = pos - off
            owned = (upd >= 0) & (upd < s_loc)
            safe = jnp.clip(upd, 0, s_loc - 1)[:, None]
            at = (blk, jnp.arange(q.shape[0])[:, None],
                  jnp.arange(KV)[None, :], safe)
            kc = kc.at[at].set(
                jnp.where(owned[:, None, None], k1, kc[at]))
            vc = vc.at[at].set(
                jnp.where(owned[:, None, None], v1, vc[at]))
            acc, m, l = flash_decode(q, kc[blk], vc[blk], pos, window=window,
                                     kpos_offset=off, return_stats=True)
            m_g = jax.lax.pmax(m, rules.tp)
            scale = jnp.exp(m - m_g)
            num = jax.lax.psum(acc * scale[..., None], rules.tp)
            den = jax.lax.psum(l * scale, rules.tp)
            out = (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
            return out, kc, vc

        out, k_stack, v_stack = jax.shard_map(
            body, mesh=rules.mesh,
            in_specs=(qspec, rowspec, rowspec, cspec, cspec,
                      rules.spec(b_ax), P()),
            out_specs=(qspec, cspec, cspec), check_vma=False,
        )(q, k, v, k_stack, v_stack, pos, blk)
    else:
        # one head_dim row per (lane, kv head) at the lane's position:
        # with the heads indexed too, the scatter's window is head_dim
        # alone and it updates the positions-minor stack in place
        bi, ki = jnp.arange(B)[:, None], jnp.arange(KV)[None, :]
        k_stack = k_stack.at[blk, bi, ki, pos[:, None]].set(k)
        v_stack = v_stack.at[blk, bi, ki, pos[:, None]].set(v)
        if rules is not None:
            cspec = P(None, rules.dp, rules.tp, None, None)
            k_stack = rules.constrain(k_stack, *cspec)
            v_stack = rules.constrain(v_stack, *cspec)
        out = flash_decode(q, k_stack[blk], v_stack[blk], pos, window=window)
    out = out.reshape(B, 1, H * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), (k_stack, v_stack)


def _run_attention_chunk(p, x, cfg, window, cache, blk, positions, lane,
                         offset, n_valid, row_valid):
    """Chunked-prefill attention: one prompt chunk of one sequence against
    its lane in the full (batch, S_max) cache.

    ``cache`` is every block's stacked (nb, B, KV, S_max, hd) K and V and
    ``blk`` this block's index. ``row_valid`` masks the tail chunk's
    padding: padded rows never reach the cache (masked write) and
    unwritten cache rows never reach the scores (``kv_valid``), so a
    chunked prefill accumulates exactly the rows a whole-prompt prefill
    would.
    """
    B, C, D = x.shape                    # B == 1: one sequence's chunk
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    k_stack, v_stack = cache
    S_max = k_stack.shape[3]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, C, KV, G, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, C, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, C, KV, hd)
    cos, sin = rope_tables(positions[None, :], hd, cfg.rope_theta)
    q = apply_rope(q.reshape(B, C, KV * G, hd), cos, sin) \
        .reshape(B, C, KV, G, hd)
    k = apply_rope(k, cos, sin)
    lane = jnp.asarray(lane, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)

    def write(stack, new):
        # masked in-place write of the chunk's rows at (blk, lane, :,
        # offset): padded rows keep the old cache contents (offset + C <=
        # S_max by EngineConfig validation, so dynamic_slice never
        # clamps/shifts the window)
        at = (blk, lane, 0, offset, 0)
        old = jax.lax.dynamic_slice(stack, at, (1, 1, KV, C, hd))
        new = new.transpose(0, 2, 1, 3)[None].astype(stack.dtype)
        upd = jnp.where(row_valid[:, None], new, old)
        return jax.lax.dynamic_update_slice(stack, upd, at)

    def read(stack):
        # the lane as flash_attention takes it, (1, S_max, KV, hd)
        at = (blk, lane, 0, 0, 0)
        lane_kv = jax.lax.dynamic_slice(stack, at, (1, 1, KV, S_max, hd))
        return lane_kv[0].transpose(0, 2, 1, 3)

    k_stack = write(k_stack, k)
    v_stack = write(v_stack, v)
    kv_valid = jnp.arange(S_max) < offset + n_valid
    out = flash_attention(q, read(k_stack), read(v_stack), causal=cfg.causal,
                          window=window, q_positions=positions,
                          kv_positions=jnp.arange(S_max), kv_valid=kv_valid)
    out = out.reshape(B, C, H * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), (k_stack, v_stack)


def _block_body(cfg, rules, specs, bp, x, *, windows_blk, moe_tables_blk,
                positions, phase, cache=None, blk=None, pos=None,
                chunk_ctx=None):
    """One super-block forward. Returns (x, tallies, aux, new_cache).

    Without ``cache`` (train, whole-prompt prefill) ``new_cache`` is this
    block's per-position states. With it (decode, chunk) ``cache`` is every
    block's stacked states and ``blk`` this block's index: the block reads
    its entry and writes back only what it changed, and ``new_cache`` is
    the whole updated stack.

    ``chunk_ctx`` — (lane, offset, n_valid, row_valid) for the chunked-
    prefill phase: attention routes through :func:`_run_attention_chunk`
    and MoE layers get the padding mask so telemetry stays honest.
    """
    tallies, aux_total = [], jnp.float32(0.0)
    new_cache = []
    moe_i = 0
    for i, spec in enumerate(specs):
        sub = bp[i]
        h = rms_norm(x, sub["ln1"], cfg.norm_eps)
        if spec.mixer == "attn":
            window = None
            if windows_blk is not None:
                window = windows_blk[i]
            st_in = None if cache is None else cache[i]
            with jax.named_scope("attention"):
                if phase == "chunk":
                    lane, offset, n_valid, row_valid = chunk_ctx
                    h, st = _run_attention_chunk(
                        sub["mixer"], h, cfg, window, st_in, blk, positions,
                        lane, offset, n_valid, row_valid)
                else:
                    h, st = _run_attention(sub["mixer"], h, cfg, rules,
                                           window, positions, cache=st_in,
                                           pos=pos, blk=blk)
            new_cache.append(st)
        else:
            st_in = None if cache is None else jax.tree.map(
                lambda a: a[blk], cache[i])
            fn = {"mamba": ssm.mamba_seq, "mlstm": ssm.mlstm_seq,
                  "slstm": ssm.slstm_seq}[spec.mixer]
            if phase == "decode":
                fn = {"mamba": ssm.mamba_step, "mlstm": ssm.mlstm_step,
                      "slstm": ssm.slstm_step}[spec.mixer]
            h, st = fn(sub["mixer"], h, st_in)
            if cache is not None:
                st = jax.tree.map(
                    lambda a, s: jax.lax.dynamic_update_index_in_dim(
                        a, s.astype(a.dtype), blk, 0), cache[i], st)
            new_cache.append(st)
        x = x + h
        if spec.ffn != "none":
            h2 = rms_norm(x, sub["ln2"], cfg.norm_eps)
            if spec.ffn == "dense":
                tp = None if rules is None else P(rules.dp, None, rules.tp)
                h2 = mlp(sub["ffn"], h2, cfg.mlp_gated, tp_spec=tp)
            else:
                so = nc = cdf = None
                if moe_tables_blk is not None:
                    so = moe_tables_blk[0][moe_i]
                    nc = moe_tables_blk[1][moe_i]
                    if len(moe_tables_blk) > 2:     # pre-share-table callers
                        cdf = moe_tables_blk[2][moe_i]
                # position-derived salt: decode positions advance every
                # step, so tiny batches re-draw their replica-selection
                # uniforms instead of replaying one fixed set forever
                seed = jnp.sum(positions).astype(jnp.int32)
                rv = None
                if chunk_ctx is not None:
                    rv = jnp.broadcast_to(chunk_ctx[3][None, :],
                                          h2.shape[:2]).reshape(-1)
                with jax.named_scope("moe"):
                    y, tally, aux = moe_layer(
                        sub["ffn"], h2, top_k=cfg.top_k,
                        n_experts=cfg.n_experts, rules=rules,
                        slots_of=so, n_copies=nc, copy_cdf=cdf,
                        route_seed=seed, phase=phase, row_valid=rv)
                    if cfg.n_shared_experts:
                        tp = None if rules is None \
                            else P(rules.dp, None, rules.tp)
                        y = y + mlp(sub["shared"], h2, cfg.mlp_gated,
                                    tp_spec=tp)
                tallies.append(tally)
                aux_total = aux_total + aux
                moe_i += 1
                h2 = y
            x = x + h2
    tall = (jnp.stack(tallies) if tallies
            else jnp.zeros((0, cfg.n_experts + 1 if cfg.is_moe else 1),
                           jnp.float32))
    return x, tall, aux_total, new_cache


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed(cfg, params, batch, rules):
    """Token/feature embedding → (x (B,S,D), labels_offset)."""
    with jax.named_scope("embed"):
        return _embed_inputs(cfg, params, batch, rules)


def _embed_inputs(cfg, params, batch, rules):
    if cfg.frontend == "audio":
        x = jnp.einsum("bsf,fd->bsd", batch["feats"],
                       params["frontend"])
        return x, 0
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "vision" and "patches" in batch:    # decode: text only
        patches = jnp.einsum("bpf,fd->bpd", batch["patches"],
                             params["frontend"])
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        off = cfg.n_patches
    else:
        off = 0
    if rules is not None:
        x = rules.constrain(x, rules.dp, None, None)
    return x, off


def _unembed_w(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def _unembed_f32(cfg, params, x, spec: str):
    """Logits of ``x`` against the head, both in float32."""
    with jax.named_scope("unembed"):
        return jnp.einsum(spec, x.astype(jnp.float32),
                          _unembed_w(cfg, params).astype(jnp.float32))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _scan_blocks(cfg, rules, params, x, *, phase, moe_tables, positions,
                 cache=None, pos=None, chunk_ctx=None):
    nb, specs = block_layout(cfg)
    win = _windows(cfg)
    win = None if win is None else jnp.asarray(win)

    # sequence parallelism: the residual stream (and the remat-saved block
    # inputs) live sequence-sharded over the TP axis; attention/MLP gather
    # internally (Megatron-SP). Decode has S=1 — skip.
    seq_ok = (rules is not None and phase != "decode"
              and x.shape[1] % max(rules.axis_size(rules.tp), 1) == 0)

    def block(x, bp, wb, mt, cache=None, blk=None):
        if seq_ok:
            x = rules.constrain(x, rules.dp, rules.tp, None)
        fn = lambda x_: _block_body(cfg, rules, specs, bp, x_,
                                    windows_blk=wb, moe_tables_blk=mt,
                                    positions=positions, phase=phase,
                                    cache=cache, blk=blk, pos=pos,
                                    chunk_ctx=chunk_ctx)
        if rules is not None and rules.remat and phase == "train":
            x, tall, aux, nc = jax.checkpoint(fn)(x)
        else:
            x, tall, aux, nc = fn(x)
        if seq_ok:
            x = rules.constrain(x, rules.dp, rules.tp, None)
        return x, tall, aux, nc

    xs = (params["blocks"], win, moe_tables)
    if cache is None:
        def body(x, xs):
            x, tall, aux, nc = block(x, *xs)
            if phase == "train":
                nc = []    # don't materialize stacked states during training
            return x, (tall, aux, nc)

        x, (tallies, aux, new_cache) = jax.lax.scan(body, x, xs)
    else:
        # the stacked cache rides in the carry, so each block updates its
        # rows in place (with the cache donated, in the caller's buffer)
        def body(carry, xs):
            x, cache = carry
            blk, xs = xs
            x, tall, aux, cache = block(x, *xs, cache=cache, blk=blk)
            return (x, cache), (tall, aux)

        (x, new_cache), (tallies, aux) = jax.lax.scan(
            body, (x, cache), (jnp.arange(nb, dtype=jnp.int32), xs))
    # tallies (nb, m, E+1) → (n_moe_layers, E+1): per-layer logical-expert
    # routing counts plus a final capacity-dropped-assignment column
    # (see moe_layer); aux summed
    tallies = tallies.reshape(-1, tallies.shape[-1])
    return x, tallies, aux.sum(), new_cache


def loss_fn(cfg: ArchConfig, rules: Optional[ShardingRules] = None,
            aux_weight: float = 0.01):
    """Training loss: mean token xent + MoE load-balance aux."""

    def fn(params, batch, moe_tables=None):
        x, off = _embed(cfg, params, batch, rules)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, tallies, aux, _ = _scan_blocks(
            cfg, rules, params, x, phase="train", moe_tables=moe_tables,
            positions=positions)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if off:
            x = x[:, off:]
        logits_spec = None
        if rules is not None and cfg.vocab % max(
                rules.axis_size(rules.tp), 1) == 0:
            logits_spec = P(rules.dp, None, rules.tp)
        loss = softmax_xent_chunked(x, _unembed_w(cfg, params),
                                    batch["labels"], logits_spec=logits_spec)
        return loss + aux_weight * aux, (tallies, aux)

    return fn


def prefill_fn(cfg: ArchConfig, rules: Optional[ShardingRules] = None):
    """(params, batch) → (last-position logits, cache, tallies)."""

    def prefill(params, batch, moe_tables=None):
        x, off = _embed(cfg, params, batch, rules)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, tallies, _, cache = _scan_blocks(
            cfg, rules, params, x, phase="prefill", moe_tables=moe_tables,
            positions=positions)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _unembed_f32(cfg, params, x[:, -1], "bd,dv->bv")
        return logits, cache, tallies

    return prefill


def prefill_chunk_fn(cfg: ArchConfig, rules: Optional[ShardingRules] = None):
    """Chunked prefill: one fixed-width prompt chunk into one cache lane.

    ``(params, tokens (1, C), cache, lane, offset, n_valid)`` →
    ``(logits (1, V) at the chunk's last valid row, new cache, tallies)``.

    ``lane``/``offset``/``n_valid`` are traced scalars, so one compilation
    serves every lane, every chunk index and every tail length — the
    engine pays one compile per chunk width, not per request. The caller
    guarantees ``offset + C <= max_seq`` (``EngineConfig`` validates
    ``max_seq % prefill_chunk == 0``); padded tail rows are masked out of
    the cache write, the attention scores and the MoE tallies, so the
    final chunk's logits and cache state match a whole-prompt prefill.
    Logits are only meaningful on the chunk that completes the prompt.
    Only the chunk's rows of ``lane`` change, so a caller that donates
    ``cache`` gets it updated in place.
    """
    _, specs = block_layout(cfg)
    if any(s.mixer != "attn" for s in specs):
        raise NotImplementedError(
            f"{cfg.name}: chunked prefill needs a resumable per-position "
            "cache; SSM/hybrid mixers carry recurrent state and are not "
            "supported")
    if rules is not None and rules.mesh is not None:
        raise NotImplementedError(
            "chunked prefill is single-device (the serving engine's "
            "configuration); mesh sharding is not supported")

    def prefill_chunk(params, tokens, cache, lane, offset, n_valid,
                      moe_tables=None):
        x, _ = _embed(cfg, params, {"tokens": tokens}, rules)
        C = x.shape[1]
        positions = offset + jnp.arange(C)
        row_valid = jnp.arange(C) < n_valid
        x, tallies, _, new_cache = _scan_blocks(
            cfg, rules, params, x, phase="chunk", moe_tables=moe_tables,
            positions=positions, cache=cache,
            chunk_ctx=(lane, offset, n_valid, row_valid))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take(x[0], jnp.maximum(n_valid - 1, 0), axis=0)
        logits = _unembed_f32(cfg, params, last, "d,dv->v")
        return logits[None], new_cache, tallies

    return prefill_chunk


def decode_fn(cfg: ArchConfig, rules: Optional[ShardingRules] = None):
    """(params, token (B,1), cache, pos) → (logits, new cache, tallies).

    The new cache differs from ``cache`` in one row per lane and layer, so
    a caller that donates ``cache`` gets it updated in place.
    """

    def decode_step(params, token, cache, pos, moe_tables=None):
        """``pos``: (B,) per-sequence positions (continuous batching)."""
        x, _ = _embed(cfg, params, {"tokens": token}, rules)
        pos = jnp.broadcast_to(jnp.asarray(pos), (token.shape[0],))
        x, tallies, _, new_cache = _scan_blocks(
            cfg, rules, params, x, phase="decode", moe_tables=moe_tables,
            positions=pos, cache=cache, pos=pos)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _unembed_f32(cfg, params, x[:, -1], "bd,dv->bv")
        return logits, new_cache, tallies

    return decode_step


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               rules: Optional[ShardingRules] = None, dtype=jnp.bfloat16):
    """Stacked per-block cache pytree matching the scan layout: a list over
    the block's positions; an attention position holds K and V as
    (n_blocks, batch, kv_heads, max_seq, head_dim)."""
    nb, specs = block_layout(cfg)
    per_pos = []
    for spec in specs:
        if spec.mixer == "attn":
            shape = (nb, batch, cfg.n_kv_heads, max_seq, cfg.hd)
            per_pos.append((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)))
        elif spec.mixer == "mamba":
            st = ssm.mamba_state_init(batch, cfg.d_model,
                                      expand=cfg.ssm_expand,
                                      d_state=cfg.ssm_d_state,
                                      d_conv=cfg.ssm_conv, dtype=dtype)
            per_pos.append(jax.tree.map(
                lambda a: jnp.zeros((nb,) + a.shape, a.dtype), st))
        elif spec.mixer == "mlstm":
            st = ssm.mlstm_state_init(batch, cfg.d_model,
                                      n_heads=cfg.n_heads,
                                      expand=cfg.ssm_expand)
            per_pos.append(jax.tree.map(
                lambda a: jnp.zeros((nb,) + a.shape, a.dtype), st))
        else:
            st = ssm.slstm_state_init(batch, cfg.d_model,
                                      n_heads=cfg.n_heads,
                                      expand=cfg.ssm_expand)
            per_pos.append(jax.tree.map(
                lambda a: jnp.zeros((nb,) + a.shape, a.dtype), st))
    return per_pos
