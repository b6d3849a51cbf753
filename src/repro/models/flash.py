"""Chunked (flash-style) attention in pure JAX.

The quadratic reference in ``attention.py`` materializes (B, S, S) scores —
fine as an oracle, impossible at prefill_32k / train_4k full configs. This
module implements the online-softmax algorithm with both query and key/value
chunking via ``lax.scan`` so peak memory is O(Cq · Ckv) per (batch, head)
instead of O(S²), while producing bit-comparable results (fp32 accumulation).

GQA layout: q (B, Sq, KV, G, hd), k/v (B, Skv, KV, hd) where G = H / KV;
the decode cache is (B, KV, S_max, hd), the layout its row writes use.

Sliding-window and causal masking are data (position arrays + scalar window),
not structure, so local/global gemma3 layers share one compiled body.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "flash_decode"]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

# §Perf knobs (read at import; see EXPERIMENTS.md §Perf):
#  REPRO_FLASH_BF16=1  — store softmax probabilities in bf16 (the dominant
#    HBM tensor at 32k context is the (Cq, Ckv) score/prob block; flash
#    kernels feed the MXU bf16 p anyway). Max/sum statistics stay fp32.
#  REPRO_FLASH_KV_CHUNK — kv chunk length (default 1024); accumulator
#    rewrite traffic scales with S/kv_chunk.
_P_BF16 = os.environ.get("REPRO_FLASH_BF16", "") == "1"
_KV_CHUNK = int(os.environ.get("REPRO_FLASH_KV_CHUNK", "1024"))


def _chunk(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    """Split ``axis`` into (n_chunks, size) and move n_chunks to the front."""
    n = x.shape[axis] // size
    shape = x.shape[:axis] + (n, size) + x.shape[axis + 1:]
    x = x.reshape(shape)
    return jnp.moveaxis(x, axis, 0)


def flash_attention(
    q: jnp.ndarray,                   # (B, Sq, KV, G, hd)
    k: jnp.ndarray,                   # (B, Skv, KV, hd)
    v: jnp.ndarray,                   # (B, Skv, KV, hd)
    *,
    causal: bool = True,
    window: Optional[jnp.ndarray] = None,    # scalar; 0/None = full
    q_positions: Optional[jnp.ndarray] = None,   # (Sq,)
    kv_positions: Optional[jnp.ndarray] = None,  # (Skv,)
    kv_valid: Optional[jnp.ndarray] = None,      # (Skv,) bool — cache fill mask
    q_chunk: int = 512,
    kv_chunk: int = _KV_CHUNK,
) -> jnp.ndarray:
    """Online-softmax attention, O(Cq·Ckv) live scores. Returns (B,Sq,KV,G,hd)."""
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad to chunk multiples (padded kv masked out; padded q discarded)
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)
    if kv_valid is None:
        kv_valid = jnp.ones((Skv,), bool)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=q_positions[-1])
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pk))
        kv_valid = jnp.pad(kv_valid, (0, pk), constant_values=False)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qc = _chunk(q, 1, q_chunk)                     # (Nq, B, Cq, KV, G, hd)
    kc = _chunk(k, 1, kv_chunk)                    # (Nk, B, Ck, KV, hd)
    vc = _chunk(v, 1, kv_chunk)
    qpos_c = _chunk(q_positions, 0, q_chunk)       # (Nq, Cq)
    kpos_c = _chunk(kv_positions, 0, kv_chunk)     # (Nk, Ck)
    kval_c = _chunk(kv_valid, 0, kv_chunk)

    def one_q_chunk(_, q_in):
        qi, qpos = q_in                            # (B,Cq,KV,G,hd), (Cq,)

        # flash backward: recompute scores per chunk pair instead of letting
        # the scan VJP store a (B,KV,G,Cq,Ckv) residual for every pair
        @jax.checkpoint
        def one_kv_chunk(carry, kv_in):
            m, l, acc = carry
            kj, vj, kpos, kval = kv_in
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            mask = kval[None, :]
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                w = jnp.asarray(window)
                in_win = (qpos[:, None] - kpos[None, :]) < w
                mask = mask & jnp.where(w > 0, in_win, True)
            s = jnp.where(mask[None, None, None, :, :], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            if _P_BF16:
                p = p.astype(jnp.bfloat16)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vj.dtype), vj)
            acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), _NEG, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(one_kv_chunk, (m0, l0, a0),
                                      (kc, vc, kpos_c, kval_c))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)           # (B,KV,G,Cq,hd)

    _, out = jax.lax.scan(one_q_chunk, None, (qc, qpos_c))
    # (Nq, B, KV, G, Cq, hd) → (B, Sq_pad, KV, G, hd)
    out = jnp.moveaxis(out, 0, 3).reshape(B, KV, G, Sq + pq, hd)
    out = jnp.moveaxis(out, 3, 1)
    return out[:, :Sq] if pq else out


def flash_decode(
    q: jnp.ndarray,                   # (B, KV, G, hd) — one new token
    k_cache: jnp.ndarray,             # (B, KV, S_max, hd)
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,                 # (B,) per-sequence positions
    *,
    window: Optional[jnp.ndarray] = None,
    kv_chunk: int = 2048,
    kpos_offset=0,                    # global position of cache row 0
    return_stats: bool = False,       # (acc, m, l) for cross-shard merge
):
    """Single-token decode against a long cache, chunked over the cache.

    Equivalent to flash_attention with Sq=1 but avoids the q-chunk padding
    and keeps the (B, S_max) score row in chunks. ``pos`` is per-sequence —
    continuous batching serves sequences at different positions in one step.
    """
    B, KV, S_max, hd = k_cache.shape
    G = q.shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,))
    kv_chunk = min(kv_chunk, S_max)
    while S_max % kv_chunk:            # keep the cache unpadded/uncopied
        kv_chunk //= 2
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    n = S_max // kv_chunk

    # §Perf iteration: scan over the chunk *index* and dynamic-slice the
    # cache in place — the previous reshape/moveaxis pre-chunking
    # materialized a transposed copy of the entire cache every decode step.
    def one_chunk(carry, j):
        m, l, acc = carry
        start = j * kv_chunk
        kj = jax.lax.dynamic_slice_in_dim(k_cache, start, kv_chunk, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v_cache, start, kv_chunk, axis=2)
        kp = kpos_offset + start + jnp.arange(kv_chunk)
        s = jnp.einsum("bkgh,bksh->bkgs", q, kj,
                       preferred_element_type=jnp.float32) * scale
        valid = kp[None, :] <= pos[:, None]                  # (B, Ck)
        if window is not None:
            w = jnp.asarray(window)
            valid = valid & jnp.where(w > 0,
                                      (pos[:, None] - kp[None, :]) < w, True)
        s = jnp.where(valid[:, None, None, :], s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgs,bksh->bkgh", p.astype(vj.dtype), vj)
        acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    a0 = jnp.zeros((B, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(one_chunk, (m0, l0, a0),
                                  jnp.arange(n, dtype=jnp.int32))
    if return_stats:
        return acc, m, l
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
