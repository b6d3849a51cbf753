"""Mixture-of-Experts layer with expert-parallel dispatch (paper Fig 2b).

Three dispatch paths, all semantically identical (modulo capacity drops):

* ``dense``      — reference: every expert computed on every token, masked
                   combine. Differentiable oracle for tests; used when no
                   mesh is active (CPU smoke).
* ``a2a``        — production train/prefill path: tokens sharded over the EP
                   axis, capacity-bucketed per physical expert slot, two
                   ``lax.all_to_all`` exchanges around the grouped expert FFN
                   inside ``jax.shard_map`` — the paper's synchronized EP
                   execution, layer latency = slowest rank (§2).
* ``replicated`` — production decode path: with one token per sequence the
                   token tensor is tiny, so tokens are replicated across the
                   *full* device fleet, each device computes only the tokens
                   routed to its local expert slot(s), and a single ``psum``
                   combines. Experts are *replicated* across slots when the
                   fleet is larger than E (the paper's §5.5 "selective expert
                   duplication" future work, realized here as uniform
                   round-robin duplication).

Each path additionally comes in two **implementations**
(``ShardingRules.moe_impl``): ``capacity`` — the legacy fixed per-slot
buckets (cf-bounded buffers, overflow assignments dropped and surfaced in
``tally[E]``, grouped-FFN cost ``E_loc × capacity`` regardless of skew) —
and ``ragged`` (the ``auto`` default) — sort-based dropless dispatch:
assignments are stable-argsorted by physical slot (``_sort_by_slot``,
O(A log A) vs the old one-hot/cumsum O(A × n_slots)), packed into a flat
expert-sorted buffer whose per-slot segments are tile-aligned
(``_ragged_plan``), and the grouped FFN (``kernels.ragged_moe_ffn``)
executes only occupied (bm, D) tiles — compute tracks *realized* routed
tokens, hot experts never drop, cold experts burn nothing.

**Placement is positional** (DESIGN.md §3): the stacked expert weights live
in *physical slot* order; the router produces *logical* expert ids; the
``slots_of`` lookup (built from a ViBE/EPLB/contiguous ``Placement``) maps
logical → physical at runtime. Replicated experts additionally carry a
``copy_cdf`` cumulative-share table (ViBE-R solver phase 3): each
assignment picks among an expert's copies by inverse CDF over a
deterministic per-assignment uniform, so realized per-copy traffic matches
the solver's speed-proportional shares (see ``_select_slots``). Because
``slots_of``/``copy_cdf`` are plain array inputs, recalibration changes
placement *and* traffic shares *without recompilation* — only the weight
migration gather (:func:`apply_placement`) touches the expert tensors.

Phantom padding: when E does not divide the EP degree (granite: 40 experts,
16 ranks) the slot count is padded to the next multiple (48); phantom slots
never receive tokens. This keeps the full ViBE placement freedom at any mesh
instead of degrading to expert-TP.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .common import dense_init
from .sharding import ShardingRules

__all__ = [
    "moe_init", "moe_layer", "route", "expert_ffn_ref",
    "default_perm_a2a", "default_perm_replicated", "n_slots_a2a",
    "apply_placement", "placement_gather_indices", "expand_experts",
]


# ---------------------------------------------------------------------------
# init / slot layout helpers
# ---------------------------------------------------------------------------

def n_slots_a2a(n_experts: int, ep_size: int) -> int:
    """Physical slot count for a2a dispatch: E padded to a multiple of EP."""
    return ((n_experts + ep_size - 1) // ep_size) * ep_size


def default_perm_a2a(n_layers: int, n_experts: int, ep_size: int) -> np.ndarray:
    """Identity (contiguous) slot permutation; phantoms at the tail."""
    ns = n_slots_a2a(n_experts, ep_size)
    return np.tile(np.arange(ns, dtype=np.int32), (n_layers, 1))


def default_perm_replicated(n_layers: int, n_experts: int,
                            fleet: int) -> np.ndarray:
    """Round-robin replication: slot p holds logical expert p % E."""
    e_loc = max(1, -(-n_experts // max(fleet, 1)))
    ns = e_loc * max(fleet, 1)
    return np.tile(np.arange(ns, dtype=np.int32) % n_experts, (n_layers, 1))


def moe_init(key, *, d: int, f: int, n_experts: int, n_slots: int,
             dtype=jnp.bfloat16):
    """Router (logical order) + stacked expert weights (physical slot order)."""
    ks = jax.random.split(key, 4)
    shape = lambda a, b: (n_slots, a, b)
    init = lambda k, a, b: (jax.random.normal(k, shape(a, b), jnp.float32)
                            / np.sqrt(a)).astype(dtype)
    return {
        "router": dense_init(ks[0], d, n_experts, jnp.float32),
        "w1": init(ks[1], d, f),
        "w3": init(ks[2], d, f),
        "w2": init(ks[3], f, d),
    }


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route(router_w: jnp.ndarray, xf: jnp.ndarray, top_k: int):
    """Softmax-then-top-k routing (Mixtral/Qwen convention).

    Returns gate weights (t, K) f32 renormalized over the selected experts,
    indices (t, K) i32 (logical), and mean full-softmax probs (E,) f32 for
    the load-balance aux loss.
    """
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, top_k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
        return weights, idx.astype(jnp.int32), probs.mean(axis=0)


def expert_ffn_ref(w1, w3, w2, toks):
    """Grouped SwiGLU FFN: toks (E_loc, C, D) → (E_loc, C, D). Pure jnp."""
    h = jnp.einsum("ecd,edf->ecf", toks, w1)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", toks, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _get_ffn(rules: Optional[ShardingRules]) -> Callable:
    if rules is not None and rules.use_kernel:
        from repro.kernels import ops
        return ops.fused_moe_ffn
    return expert_ffn_ref


def _get_ragged_ffn(rules: Optional[ShardingRules]) -> Callable:
    """Grouped FFN over a flat expert-sorted buffer + per-tile expert ids."""
    if rules is not None and rules.use_kernel:
        from repro.kernels import ops
        return ops.ragged_moe_ffn
    from repro.kernels.ref import ragged_moe_ffn_ref
    return ragged_moe_ffn_ref


def _sort_by_slot(slot_flat: jnp.ndarray, n_slots: int,
                  active: Optional[jnp.ndarray] = None):
    """Sort-based bucketing core shared by every dispatch path.

    Stable-argsorts the (A,) assignment→slot map (inactive assignments get
    the sentinel key ``n_slots`` so they sort past every real slot) and
    finds each slot's segment boundaries with ``searchsorted`` — O(A log A)
    instead of the old one-hot/cumsum O(A × n_slots).

    Returns ``(order, sorted_key, starts, pos_sorted)``:

    * ``order`` (A,) — assignment index in slot-sorted order (stable, so
      within a slot the original arrival order is preserved);
    * ``sorted_key`` (A,) — slot id per sorted assignment (``n_slots`` =
      inactive);
    * ``starts`` (n_slots + 1,) — segment start per slot;
      ``starts[n_slots]`` is where the inactive tail begins;
    * ``pos_sorted`` (A,) — arrival position within the slot's segment.
    """
    key = slot_flat.astype(jnp.int32)
    if active is not None:
        key = jnp.where(active, key, n_slots)
    order = jnp.argsort(key)
    sorted_key = key[order]
    starts = jnp.searchsorted(
        sorted_key, jnp.arange(n_slots + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)
    pos_sorted = (jnp.arange(slot_flat.shape[0], dtype=jnp.int32)
                  - starts[sorted_key])
    return order, sorted_key, starts, pos_sorted


def _bucket_positions(slot_flat: jnp.ndarray, n_slots: int,
                      active: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Arrival position of each assignment within its slot's bucket.

    ``slot_flat``: (A,) slot id per assignment; ``active``: (A,) bool mask —
    inactive assignments consume no capacity. Sort-based (``_sort_by_slot``);
    the stable sort preserves arrival order, so positions are bit-identical
    to the old one-hot/cumsum build at O(A log A) instead of O(A × n_slots).
    Positions of inactive assignments are meaningless (callers mask them).
    """
    order, _, _, pos_sorted = _sort_by_slot(slot_flat, n_slots, active)
    return jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)


def _ragged_plan(slot_flat: jnp.ndarray, n_slots: int, bm: int,
                 active: Optional[jnp.ndarray] = None):
    """Sort-based dropless dispatch plan (the ragged hot path's metadata).

    Lays every assignment into a flat expert-sorted buffer whose per-slot
    segments are padded to multiples of the row tile ``bm`` (group-aligned:
    each (bm, D) tile belongs to exactly one slot; empty slots own zero
    tiles). All shapes are static worst-case bounds; the data-dependent part
    is *values only*, so the plan jits.

    Returns ``(order, rows, tile_group, n_rows)``:

    * ``order`` (A,) — assignment index in slot-sorted order;
    * ``rows`` (A,) — buffer row per *sorted* assignment; inactive
      assignments get ``n_rows`` (out of bounds → scatters drop them,
      gathers clamp and callers mask them);
    * ``tile_group`` (n_tiles,) — owning slot per tile, sentinel
      ``n_slots`` for unoccupied tiles (the grouped FFN skips those);
    * ``n_rows`` — static buffer row count (``ragged_n_tiles(A) × bm``).
    """
    from repro.kernels.ragged_moe_ffn import (ragged_n_tiles,
                                              ragged_tile_metadata)
    A = slot_flat.shape[0]
    order, sorted_key, starts, pos_sorted = _sort_by_slot(
        slot_flat, n_slots, active)
    sizes = jnp.diff(starts)                         # (n_slots,)
    n_tiles = ragged_n_tiles(A, n_slots, bm)
    n_rows = n_tiles * bm
    row_off, tile_group = ragged_tile_metadata(sizes, bm, n_tiles)
    rows = jnp.where(
        sorted_key < n_slots,
        row_off[jnp.minimum(sorted_key, n_slots - 1)] + pos_sorted,
        n_rows)
    return order, rows, tile_group, n_rows


#: Knuth multiplicative-hash constant: odd, so ``i * KNUTH mod 2^32`` is an
#: equidistributed (Weyl) sequence over uint32 — successive assignment
#: positions cover [0, 1) with low discrepancy, decorrelated from position.
_HASH_MULT = np.uint32(2654435761)
#: odd stride for the per-step salt: for a fixed assignment index, varying
#: the seed walks its own Weyl sequence, so traffic aggregated *across*
#: steps converges too (a decode batch has only t·K ≈ tens of assignments
#: per step — without the salt those few uniforms would repeat forever and
#: quantize the realized shares).
_SEED_MULT = np.uint32(2246822519)


def _assignment_uniforms(t: int, K: int, seed=None) -> jnp.ndarray:
    """Deterministic per-assignment uniforms u ∈ [0, 1) → (t, K) f32.

    Top 24 bits of a multiplicative hash of the flat assignment index
    (offset by ``seed``, an int32 scalar that callers vary per step), so
    every value is exactly representable in float32 and strictly < 1.
    """
    i = jnp.arange(t * K, dtype=jnp.uint32)
    if seed is not None:
        i = i + jnp.asarray(seed).astype(jnp.uint32) * _SEED_MULT
    h = i * _HASH_MULT
    u = (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return u.reshape(t, K)


def _select_slots(idx: jnp.ndarray, slots_of: jnp.ndarray,
                  n_copies: jnp.ndarray,
                  copy_cdf: Optional[jnp.ndarray] = None,
                  route_seed=None) -> jnp.ndarray:
    """Map logical ids (t, K) to physical slots across replicas.

    With ``copy_cdf`` (E, r_max) — the cumulative per-copy traffic shares
    from the placement solver — each assignment draws a deterministic,
    position-decorrelated uniform and picks its copy by inverse CDF, so
    realized per-copy traffic converges to the solver's shares (ViBE-R
    phase 3 honored by the actual dispatch, not just the objective).
    ``route_seed`` (int32 scalar) salts the hash; the model threads a
    step-varying value through so tiny decode batches converge across
    steps rather than replaying one fixed set of uniforms.
    ``copy_cdf=None`` keeps the legacy uniform ``% n_copies`` hash (the
    share-oblivious path the parity suite uses as its regression tripwire).
    """
    t, K = idx.shape
    r_max = slots_of.shape[-1]
    if r_max == 1:
        return slots_of[:, 0][idx]
    if copy_cdf is None:
        copy = (jnp.arange(t * K, dtype=jnp.int32).reshape(t, K)) \
            % n_copies[idx]
    else:
        u = _assignment_uniforms(t, K, route_seed)
        # smallest r with u < cdf[r]; trailing entries are 1.0 > u, and the
        # min() guards f32 round-up of a copy's cumulative share past u
        copy = jnp.sum(u[:, :, None] >= copy_cdf[idx], axis=-1,
                       dtype=jnp.int32)
        copy = jnp.minimum(copy, n_copies[idx] - 1)
    return slots_of[idx, copy]


# ---------------------------------------------------------------------------
# dense (reference) dispatch
# ---------------------------------------------------------------------------

def _dense_dispatch(p, xf, route_seed, *, top_k, n_experts, slots_of,
                    n_copies, copy_cdf, row_valid=None):
    weights, idx, mean_prob = route(p["router"], xf, top_k)
    n_slots = p["w1"].shape[0]
    with jax.named_scope("dispatch"):
        if row_valid is not None:
            # padded rows (chunked prefill): no gate weight, no tally —
            # they must be invisible to both the output and the routing
            # telemetry
            weights = weights * row_valid[:, None].astype(weights.dtype)
        slots = _select_slots(idx, slots_of, n_copies, copy_cdf,
                              route_seed)               # (t, K) physical
        # scatter gate weights into a (t, n_slots) combine matrix
        comb = jnp.zeros((xf.shape[0], n_slots), jnp.float32).at[
            jnp.arange(xf.shape[0])[:, None], slots].add(weights)
    with jax.named_scope("ffn"):
        y = expert_ffn_ref(p["w1"], p["w3"], p["w2"],
                           jnp.broadcast_to(xf, (n_slots,) + xf.shape))
    with jax.named_scope("combine"):
        out = jnp.einsum("te,etd->td", comb, y.astype(jnp.float32))
    tally = _masked_tally(idx, n_experts, row_valid)
    aux = _aux_loss(tally, mean_prob, n_experts)
    # dense computes every expert on every token: nothing can be dropped
    tally = jnp.concatenate([tally, jnp.zeros((1,), jnp.float32)])
    return out.astype(xf.dtype), tally, aux


def _masked_tally(idx, n_experts, row_valid=None):
    oh = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
    if row_valid is not None:
        oh = oh * row_valid[:, None, None].astype(jnp.float32)
    return oh.sum((0, 1))


def _aux_loss(tally, mean_prob, n_experts):
    frac = tally / jnp.maximum(tally.sum(), 1.0)
    return n_experts * jnp.dot(frac, mean_prob)


# ---------------------------------------------------------------------------
# ragged (dropless) dispatch
# ---------------------------------------------------------------------------

def _combine_top_k(y_rows, weights):
    """Gate-weighted sum of each token's top-k results: ``y_rows`` (t·K, D)
    in assignment order (token-major), ``weights`` (t, K) → (t, D) f32.

    A reshape-and-sum, not a scatter-add at slot-sorted token ids: on a TPU
    that scatter-add, fed by the slot sort, lost most contributions (XLA
    TPU backend, JAX 0.9.0), while this form matched the oracle."""
    t, K = weights.shape
    y = y_rows.astype(jnp.float32).reshape(t, K, -1)
    return (y * weights.astype(jnp.float32)[:, :, None]).sum(1)


def _ragged_local_ffn(xf, weights, slot_flat, active, n_groups, bm, ffn,
                      w1, w3, w2):
    """Sorted-buffer grouped FFN + weighted combine for local assignments.

    ``weights`` (t, K) are the gate weights, ``slot_flat`` (t·K,) the
    assignments' slots in token-major order. Builds the ragged plan,
    scatters each (active) assignment's token row into the flat
    expert-sorted buffer, runs the grouped FFN over occupied tiles, and
    gathers each assignment's result back in assignment order for the
    weighted combine. Inactive assignments land out of bounds (their
    scatters drop, their gathers clamp and are zero-weighted). Returns the
    (t, D) f32 partial output — dropless by construction.
    """
    t, K = weights.shape
    with jax.named_scope("dispatch"):
        order, rows, tile_group, n_rows = _ragged_plan(slot_flat, n_groups,
                                                       bm, active)
        buf = jnp.zeros((n_rows, xf.shape[1]), xf.dtype).at[rows].set(
            xf[order // K], mode="drop")
    with jax.named_scope("ffn"):
        y_buf = ffn(w1, w3, w2, buf, tile_group)
    with jax.named_scope("combine"):
        row_of = jnp.zeros_like(rows).at[order].set(rows)  # assignment order
        if active is not None:
            weights = weights * active.reshape(t, K).astype(weights.dtype)
        return _combine_top_k(y_buf[jnp.minimum(row_of, n_rows - 1)],
                              weights)


def _dense_dispatch_ragged(p, xf, route_seed, *, top_k, n_experts, slots_of,
                           n_copies, copy_cdf, bm, ffn, row_valid=None):
    """Single-device ragged dispatch: compute each assignment exactly once
    (A = t·top_k rows) instead of the dense oracle's every-expert-on-every-
    token broadcast. Same return contract as ``_dense_dispatch``."""
    weights, idx, mean_prob = route(p["router"], xf, top_k)
    with jax.named_scope("dispatch"):
        if row_valid is not None:
            weights = weights * row_valid[:, None].astype(weights.dtype)
        slots = _select_slots(idx, slots_of, n_copies, copy_cdf, route_seed)
    n_slots = p["w1"].shape[0]
    out = _ragged_local_ffn(xf, weights, slots.reshape(-1), None, n_slots,
                            bm, ffn, p["w1"], p["w3"], p["w2"])
    tally = _masked_tally(idx, n_experts, row_valid)
    aux = _aux_loss(tally, mean_prob, n_experts)
    tally = jnp.concatenate([tally, jnp.zeros((1,), jnp.float32)])
    return out.astype(xf.dtype), tally, aux


def _a2a_body_ragged(xb, router_w, w1, w3, w2, slots_of, n_copies, copy_cdf,
                     route_seed, *, top_k, n_experts, n_slots, bm, ep,
                     ep_axes, dp_axes, fsdp_axes, ffn):
    """Dropless a2a dispatch: sorted per-destination frames + ragged FFN.

    The exchange cannot be ragged itself (``lax.all_to_all`` needs equal
    splits), so instead of per-*slot* capacity buckets the send buffer holds
    one fixed frame of A = t_loc·top_k rows per destination rank — the
    worst case (every local assignment routed to one rank), so nothing can
    ever overflow. Assignments are slot-sorted (slots are rank-major, so
    one sort orders by destination rank *and* groups by slot), packed into
    their destination frame, and their local-slot ids ride along in a
    parallel int frame. The receiver re-sorts the ep·A incoming rows by
    local slot and runs the grouped FFN over occupied tiles only; results
    return through the mirror-image exchange. Memory trades against the
    capacity path: frames total ep·A rows vs ``n_slots·capacity ≈ A·cf``
    on the send side, but the FFN computes only realized tokens and the
    tally's drop column is structurally zero.
    """
    Bl, Sl, D = xb.shape
    e_loc = n_slots // ep
    if fsdp_axes:
        w1 = jax.lax.all_gather(w1, fsdp_axes, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axes, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=1, tiled=True)

    xf = xb.reshape(Bl * Sl, D)
    t = xf.shape[0]
    weights, idx, mean_prob = route(router_w, xf, top_k)
    slots = _select_slots(idx, slots_of, n_copies, copy_cdf, route_seed)
    slot_flat = slots.reshape(-1)
    A = t * top_k

    # sorted send: slot-major order == (dest rank, local slot) order, so
    # one shared sort plan yields the rank segments too (slots are
    # rank-major: rank r's segment starts where slot r·e_loc does)
    order, ss, starts, _ = _sort_by_slot(slot_flat, n_slots)
    rank_sorted = ss // e_loc
    rank_starts = starts[jnp.arange(ep + 1, dtype=jnp.int32) * e_loc]
    pos_in_rank = jnp.arange(A, dtype=jnp.int32) - rank_starts[rank_sorted]
    send_row = rank_sorted * A + pos_in_rank
    send = jnp.zeros((ep * A, D), xf.dtype).at[send_row].set(
        xf[order // top_k])
    # local-slot ids per frame row; e_loc = padding sentinel
    loc_ids = jnp.full((ep * A,), e_loc, jnp.int32).at[send_row].set(
        ss % e_loc)

    a2a_axes = ep_axes[0] if len(ep_axes) == 1 else ep_axes
    recv = jax.lax.all_to_all(send.reshape(ep, A, D), a2a_axes,
                              split_axis=0, concat_axis=0)
    rloc = jax.lax.all_to_all(loc_ids.reshape(ep, A), a2a_axes,
                              split_axis=0, concat_axis=0).reshape(-1)

    # receiver: compact ep·A frame rows into the slot-sorted ragged buffer
    R = ep * A
    order2, rows2, tile_group, n_rows = _ragged_plan(
        rloc, e_loc, bm, active=rloc < e_loc)
    buf = jnp.zeros((n_rows, D), xf.dtype).at[rows2].set(
        recv.reshape(R, D)[order2], mode="drop")
    y_buf = ffn(w1, w3, w2, buf, tile_group)
    # un-sort back into frame layout (padding rows stay zero) and return
    row_of_recv = jnp.full((R,), n_rows, jnp.int32).at[order2].set(rows2)
    y_recv = (y_buf[jnp.minimum(row_of_recv, n_rows - 1)]
              * (rloc < e_loc)[:, None].astype(y_buf.dtype))
    back = jax.lax.all_to_all(y_recv.reshape(ep, A, D), a2a_axes,
                              split_axis=0, concat_axis=0).reshape(R, D)

    send_row_of = jnp.zeros_like(send_row).at[order].set(send_row)
    out = _combine_top_k(back[send_row_of], weights)

    tally = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum((0, 1))
    tally = jnp.concatenate([tally, jnp.zeros((1,))])   # dropless: tally[E]=0
    tally = jax.lax.psum(tally, ep_axes + dp_axes)
    mean_prob = jax.lax.pmean(mean_prob, ep_axes + dp_axes)
    aux = _aux_loss(tally[:n_experts], mean_prob, n_experts)
    return out.astype(xb.dtype).reshape(Bl, Sl, D), tally, aux


def _replicated_body_ragged(xb, router_w, w1, w3, w2, slots_of, n_copies,
                            copy_cdf, route_seed, *, top_k, n_experts,
                            n_slots, bm, ep_axes, ep_sizes, ffn,
                            psum_axes=None):
    """Dropless decode path: each device ragged-computes its own slots.

    Same replication scheme as ``_replicated_body`` (tokens fleet-wide,
    psum combine), but local assignments go through the sorted ragged
    buffer instead of fixed capacity buckets — the buffer's static bound
    covers *all* A assignments landing on one device, so nothing drops.
    """
    B, S, D = xb.shape
    e_loc = w1.shape[0]
    psum_axes = psum_axes or ep_axes
    my_rank = jnp.int32(0)
    for a, sz in zip(ep_axes, ep_sizes):
        my_rank = my_rank * sz + jax.lax.axis_index(a)

    xf = xb.reshape(B * S, D)
    weights, idx, mean_prob = route(router_w, xf, top_k)
    slots = _select_slots(idx, slots_of, n_copies, copy_cdf, route_seed)
    slot_flat = slots.reshape(-1)

    mine =(slot_flat // e_loc) == my_rank
    out = _ragged_local_ffn(xf, weights, slot_flat % e_loc, mine, e_loc, bm,
                            ffn, w1, w3, w2)
    out = jax.lax.psum(out, psum_axes)

    tally = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum((0, 1))
    aux = _aux_loss(tally, mean_prob, n_experts)
    tally = jnp.concatenate([tally, jnp.zeros((1,))])   # dropless: tally[E]=0
    return out.astype(xb.dtype).reshape(B, S, D), tally, aux


# ---------------------------------------------------------------------------
# a2a dispatch (train / prefill)
# ---------------------------------------------------------------------------

def _a2a_body(xb, router_w, w1, w3, w2, slots_of, n_copies, copy_cdf,
              route_seed, *, top_k, n_experts, n_slots, capacity, ep,
              ep_axes, dp_axes, fsdp_axes, ffn):
    """Per-device block of the a2a EP MoE layer.

    xb: (B_loc, S_loc, D). Expert weights arrive sharded (E_loc, D/f, F)
    with axis 1 FSDP-sharded; gathered here (ZeRO-3, transposes to
    reduce-scatter in the backward). ``ep`` is the static EP group size
    (the mesh shape is known at trace time).
    """
    Bl, Sl, D = xb.shape
    e_loc = n_slots // ep
    if fsdp_axes:
        w1 = jax.lax.all_gather(w1, fsdp_axes, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axes, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=1, tiled=True)

    xf = xb.reshape(Bl * Sl, D)
    t = xf.shape[0]
    weights, idx, mean_prob = route(router_w, xf, top_k)
    slots = _select_slots(idx, slots_of, n_copies, copy_cdf,
                          route_seed)                   # (t, K)
    slot_flat = slots.reshape(-1)
    wgt_flat = weights.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)

    pos = _bucket_positions(slot_flat, n_slots)
    keep = pos < capacity
    dest = slot_flat * capacity + jnp.where(keep, pos, 0)
    send = jnp.zeros((n_slots * capacity, D), xf.dtype)
    send = send.at[dest].add(xf[tok_flat] * keep[:, None].astype(xf.dtype))

    # dispatch: (ep, E_loc, C, D) — chunk i goes to EP rank i
    send = send.reshape(ep, e_loc, capacity, D)
    a2a_axes = ep_axes[0] if len(ep_axes) == 1 else ep_axes
    recv = jax.lax.all_to_all(send, a2a_axes, split_axis=0, concat_axis=0)
    # recv[j] = tokens from source rank j for my local experts
    toks = jnp.moveaxis(recv, 0, 1).reshape(e_loc, ep * capacity, D)
    y = ffn(w1, w3, w2, toks)                                # (E_loc, ep·C, D)
    y = jnp.moveaxis(y.reshape(e_loc, ep, capacity, D), 1, 0)
    back = jax.lax.all_to_all(y, a2a_axes, split_axis=0, concat_axis=0)
    back = back.reshape(n_slots * capacity, D)               # my sends, processed

    contrib = (back[dest].astype(jnp.float32)
               * (wgt_flat * keep)[:, None])
    out = jnp.zeros((t, D), jnp.float32).at[tok_flat].add(contrib)

    tally = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum((0, 1))
    # capacity-overflow accounting: assignments past a slot's bucket are
    # zeroed above; surface the count instead of dropping them silently
    dropped = jnp.sum(1.0 - keep.astype(jnp.float32))[None]
    tally = jnp.concatenate([tally, dropped])
    tally = jax.lax.psum(tally, ep_axes + dp_axes)
    mean_prob = jax.lax.pmean(mean_prob, ep_axes + dp_axes)
    aux = _aux_loss(tally[:n_experts], mean_prob, n_experts)
    return out.astype(xb.dtype).reshape(Bl, Sl, D), tally, aux


# ---------------------------------------------------------------------------
# replicated dispatch (decode)
# ---------------------------------------------------------------------------

def _replicated_body(xb, router_w, w1, w3, w2, slots_of, n_copies, copy_cdf,
                     route_seed, *, top_k, n_experts, n_slots, capacity,
                     ep_axes, ep_sizes, ffn, psum_axes=None):
    """Tokens replicated fleet-wide; each device computes its slots only.

    With expert-TP (big experts) the local w1/w3 carry an F-slice and w2 the
    matching rows: y is a partial sum over F, folded in by the wider psum.
    ``ep_sizes`` are the static mesh sizes of ``ep_axes`` (same order).
    """
    B, S, D = xb.shape
    e_loc = w1.shape[0]
    psum_axes = psum_axes or ep_axes
    my_rank = jnp.int32(0)
    for a, sz in zip(ep_axes, ep_sizes):
        my_rank = my_rank * sz + jax.lax.axis_index(a)

    xf = xb.reshape(B * S, D)
    t = xf.shape[0]
    weights, idx, mean_prob = route(router_w, xf, top_k)
    slots = _select_slots(idx, slots_of, n_copies, copy_cdf, route_seed)
    slot_flat = slots.reshape(-1)
    wgt_flat = weights.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)

    mine = (slot_flat // e_loc) == my_rank
    loc = slot_flat % e_loc
    pos = _bucket_positions(loc, e_loc, active=mine)
    keep = mine & (pos >= 0) & (pos < capacity)
    dest = loc * capacity + jnp.where(keep, pos, 0)
    buckets = jnp.zeros((e_loc * capacity, D), xf.dtype)
    buckets = buckets.at[dest].add(xf[tok_flat] * keep[:, None].astype(xf.dtype))

    y = ffn(w1, w3, w2, buckets.reshape(e_loc, capacity, D))
    y = y.reshape(e_loc * capacity, D)
    contrib = y[dest].astype(jnp.float32) * (wgt_flat * keep)[:, None]
    out = jnp.zeros((t, D), jnp.float32).at[tok_flat].add(contrib)
    out = jax.lax.psum(out, psum_axes)

    tally = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum((0, 1))
    aux = _aux_loss(tally, mean_prob, n_experts)
    # local capacity overflow (each device drops its own bucket excess);
    # psum over the slot axes only — expert-TP ranks see duplicate drops
    dropped = jnp.sum((mine & (pos >= capacity)).astype(jnp.float32))[None]
    dropped = jax.lax.psum(dropped, ep_axes)
    tally = jnp.concatenate([tally, dropped])
    return out.astype(xb.dtype).reshape(B, S, D), tally, aux


# ---------------------------------------------------------------------------
# public layer
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_layer(
    p,
    x: jnp.ndarray,                    # (B, S, D)
    *,
    top_k: int,
    n_experts: int,
    rules: Optional[ShardingRules] = None,
    slots_of: Optional[jnp.ndarray] = None,     # (E, r_max) physical lookup
    n_copies: Optional[jnp.ndarray] = None,     # (E,)
    copy_cdf: Optional[jnp.ndarray] = None,     # (E, r_max) cumulative shares
    route_seed=None,                   # int32 scalar salt (varies per step)
    phase: str = "train",              # "train" | "prefill" | "decode"
    row_valid: Optional[jnp.ndarray] = None,    # (B·S,) bool — chunk padding
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,D), tally (E+1,), aux_loss).

    ``row_valid`` masks padded token rows (a chunked-prefill tail chunk):
    masked rows get zero gate weight and contribute nothing to the tally,
    so the routing telemetry the virtual clock prices stays honest.
    Supported on the single-device dense paths only (the serving engine's
    configuration); mesh dispatch with a row mask is not implemented.

    ``tally[:E]`` — logical-expert routing counts (pre-capacity, so each
    token contributes exactly top_k); ``tally[E]`` — assignments dropped by
    the capacity buckets this pass (0 on the dense path).

    ``copy_cdf`` carries the placement solver's per-copy traffic shares
    (cumulative, from ``make_moe_tables``/``build_copy_cdf``); replicas are
    then traffic-weighted by inverse-CDF selection. None = uniform split
    over copies — correct for round-robin duplication, share-oblivious for
    ViBE-R placements. ``route_seed`` decorrelates the selection across
    steps (the model passes a position-derived salt) so small decode
    batches don't replay one fixed uniform set forever.
    """
    B, S, D = x.shape
    n_slots = p["w1"].shape[0]
    if slots_of is None:
        slots_of = jnp.arange(n_experts, dtype=jnp.int32)[:, None]
    if n_copies is None:
        n_copies = jnp.ones((n_experts,), jnp.int32)
    if copy_cdf is None:
        # uniform fallback: copy r of expert e covers ((r+1)/n_copies[e])
        r_pad = slots_of.shape[-1]
        copy_cdf = jnp.minimum(
            jnp.arange(1, r_pad + 1, dtype=jnp.float32)[None, :]
            / jnp.maximum(n_copies[:, None].astype(jnp.float32), 1.0), 1.0)
    if route_seed is None:
        route_seed = jnp.int32(0)
    route_seed = jnp.asarray(route_seed).astype(jnp.int32)

    mode = "dense"
    impl = "capacity" if rules is None else rules.moe_impl_resolved
    if rules is not None and rules.mesh is not None:
        if rules.moe_dispatch in ("a2a", "replicated", "dense"):
            mode = rules.moe_dispatch
        elif phase == "decode":
            mode = "replicated"
        else:
            mode = "a2a"
        if mode == "a2a" and S % max(rules.ep_size, 1) != 0:
            mode = "replicated"

    if mode == "dense":
        if rules is not None and impl == "ragged":
            out, tally, aux = _dense_dispatch_ragged(
                p, x.reshape(B * S, D), route_seed, top_k=top_k,
                n_experts=n_experts, slots_of=slots_of, n_copies=n_copies,
                copy_cdf=copy_cdf, bm=rules.moe_block_m,
                ffn=_get_ragged_ffn(rules), row_valid=row_valid)
        else:
            out, tally, aux = _dense_dispatch(
                p, x.reshape(B * S, D), route_seed, top_k=top_k,
                n_experts=n_experts, slots_of=slots_of, n_copies=n_copies,
                copy_cdf=copy_cdf, row_valid=row_valid)
        return out.reshape(B, S, D), tally, aux

    if row_valid is not None:
        raise NotImplementedError(
            "row_valid (chunked-prefill padding mask) is only supported on "
            "the single-device dense dispatch paths")

    cf = rules.capacity_factor
    bm = rules.moe_block_m
    ffn = _get_ragged_ffn(rules) if impl == "ragged" else _get_ffn(rules)
    mesh = rules.mesh
    if mode == "a2a":
        ep_axes, dp_axes = rules.ep_axes, rules.dp_axes
        fsdp_axes = tuple(a for a in ((rules.fsdp,) if isinstance(rules.fsdp, str)
                                      else (rules.fsdp or ()))
                          if a in mesh.axis_names)
        ep = rules.ep_size
        t_loc = (B // max(rules.axis_size(dp_axes), 1)) * (S // ep)
        capacity = _round_up(max(int(np.ceil(t_loc * top_k / n_slots * cf)), 1), 4)
        x = rules.constrain(x, rules.dp, rules.ep[0] if len(rules.ep) == 1 else rules.ep, None)
        if impl == "ragged":
            body = functools.partial(
                _a2a_body_ragged, top_k=top_k, n_experts=n_experts,
                n_slots=n_slots, bm=bm, ep=ep, ep_axes=ep_axes,
                dp_axes=dp_axes, fsdp_axes=fsdp_axes, ffn=ffn)
        else:
            body = functools.partial(
                _a2a_body, top_k=top_k, n_experts=n_experts, n_slots=n_slots,
                capacity=capacity, ep=ep, ep_axes=ep_axes, dp_axes=dp_axes,
                fsdp_axes=fsdp_axes, ffn=ffn)
        ep_spec = ep_axes[0] if len(ep_axes) == 1 else ep_axes
        w_spec = P(ep_spec, fsdp_axes if fsdp_axes else None, None)
        out, tally, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(dp_axes if dp_axes else None, ep_spec, None),
                      P(None, None), w_spec, w_spec,
                      P(ep_spec, fsdp_axes if fsdp_axes else None, None),
                      P(None, None), P(None), P(None, None), P()),
            out_specs=(P(dp_axes if dp_axes else None, ep_spec, None),
                       P(None), P()),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"], slots_of, n_copies,
          copy_cdf, route_seed)
        return out, tally, aux

    # replicated decode: one-or-few slots per device across the whole fleet
    # (expert-TP variant: slots over `ep` only, F sliced over the dp axes)
    if rules.decode_expert_tp:
        ep_axes = rules.ep_axes
        ftp_axes = tuple(a for a in rules.ep_all_axes if a not in ep_axes)
    else:
        ep_axes = rules.ep_all_axes
        ftp_axes = ()
    fleet = rules.axis_size(ep_axes)
    t = B * S
    capacity = _round_up(
        max(int(np.ceil(t * top_k / n_slots * max(cf, 2.0))), 4), 4)
    ep_spec = ep_axes if len(ep_axes) > 1 else (ep_axes[0] if ep_axes else None)
    ftp_spec = (ftp_axes if len(ftp_axes) > 1 else
                (ftp_axes[0] if ftp_axes else None))
    if impl == "ragged":
        body = functools.partial(
            _replicated_body_ragged, top_k=top_k, n_experts=n_experts,
            n_slots=n_slots, bm=bm, ep_axes=ep_axes,
            ep_sizes=tuple(rules.axis_size(a) for a in ep_axes), ffn=ffn,
            psum_axes=ep_axes + ftp_axes)
    else:
        body = functools.partial(
            _replicated_body, top_k=top_k, n_experts=n_experts,
            n_slots=n_slots, capacity=capacity, ep_axes=ep_axes,
            ep_sizes=tuple(rules.axis_size(a) for a in ep_axes), ffn=ffn,
            psum_axes=ep_axes + ftp_axes)
    out, tally, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None),
                  P(ep_spec, None, ftp_spec), P(ep_spec, None, ftp_spec),
                  P(ep_spec, ftp_spec, None), P(None, None), P(None),
                  P(None, None), P()),
        out_specs=(P(None, None, None), P(None), P()),
        check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"], slots_of, n_copies,
      copy_cdf, route_seed)
    return out, tally, aux


# ---------------------------------------------------------------------------
# placement application (weight migration)
# ---------------------------------------------------------------------------

def _first_slot_of(perm: np.ndarray, n_ids: int) -> np.ndarray:
    """inv[l, e] = first (lowest) slot in ``perm[l]`` holding id e, -1 if
    absent. Vectorized first-occurrence build: numpy fancy assignment lets
    the *last* write win, so feeding slots in descending order makes slot 0
    the survivor — identical to the old per-slot Python scan."""
    L, NS = perm.shape
    inv = np.full((L, n_ids), -1, dtype=np.int32)
    desc = np.arange(NS - 1, -1, -1, dtype=np.int32)
    inv[np.arange(L)[:, None], perm[:, ::-1]] = desc[None, :]
    return inv


def placement_gather_indices(old_perm: np.ndarray,
                             new_perm: np.ndarray) -> np.ndarray:
    """gather_idx[l, p] = old slot whose weights must land in new slot p.

    Fully vectorized (scatter-build of the expert→first-slot inverse plus
    one gather); runs on every engine recalibration, so no Python O(L·NS)
    loops. Bit-identical to the historical loop build (tests pin this).
    """
    old_perm = np.atleast_2d(old_perm)
    new_perm = np.atleast_2d(new_perm)
    L, NS = old_perm.shape
    n_ids = int(max(old_perm.max(), new_perm.max())) + 1
    inv = _first_slot_of(old_perm, n_ids)
    src = inv[np.arange(L)[:, None], new_perm]                  # (L, NS)
    return np.where(src >= 0, src,
                    np.arange(NS, dtype=np.int32)[None, :]).astype(np.int32)


@functools.partial(jax.jit, donate_argnums=0)
def _gather_experts(leaf: jnp.ndarray, gather_idx: jnp.ndarray) -> jnp.ndarray:
    # leaf (L, n_slots, ...) ← leaf[l, gather_idx[l]]
    return jnp.take_along_axis(
        leaf, gather_idx.reshape(gather_idx.shape + (1,) * (leaf.ndim - 2)),
        axis=1)


def apply_placement(expert_params: dict, old_perm: np.ndarray,
                    new_perm: np.ndarray) -> Tuple[dict, int]:
    """Migrate stacked expert weights from one slot permutation to another.

    Returns (new params, number of (layer, slot) tensors that moved) — the
    paper's weight-transfer volume; the incremental solver's swap list makes
    this O(#swaps) instead of O(L·E).
    """
    gi = placement_gather_indices(old_perm, new_perm)
    moved = int((gi != np.arange(gi.shape[1])[None, :]).sum())
    out = dict(expert_params)
    for k in ("w1", "w2", "w3"):
        if k in out:
            out[k] = _gather_experts(out[k], jnp.asarray(gi))
    return out, moved


def expand_experts(expert_params: dict, perm_a2a: np.ndarray,
                   perm_dec: np.ndarray) -> dict:
    """Build decode-fleet expert tensors (replicated slots) from the a2a
    layout: decode slot p holds logical expert perm_dec[l, p], fetched from
    the a2a slot holding that expert. Vectorized like
    :func:`placement_gather_indices` (the old dict build also kept the
    first a2a slot per expert); a decode expert absent from the a2a layout
    is an error, as before."""
    perm_dec = np.atleast_2d(perm_dec)
    perm_a2a = np.atleast_2d(perm_a2a)
    L, ns_dec = perm_dec.shape
    n_ids = int(max(perm_a2a.max(), perm_dec.max())) + 1
    inv = _first_slot_of(perm_a2a, n_ids)
    gi = inv[np.arange(L)[:, None], perm_dec]
    if (gi < 0).any():
        missing = sorted(set(perm_dec[gi < 0].tolist()))
        raise KeyError(f"decode experts absent from a2a layout: {missing}")
    gi = gi.astype(np.int32)
    out = dict(expert_params)
    for k in ("w1", "w2", "w3"):
        if k in out:
            out[k] = jnp.take_along_axis(
                out[k], jnp.asarray(gi).reshape(gi.shape + (1,) * (out[k].ndim - 2)),
                axis=1)
    return out
