"""Ragged (dropless) grouped MoE expert FFN — MegaBlocks-style on TPU.

The capacity-bucket kernel (:mod:`.moe_ffn`) pads every expert to a fixed
``capacity``: hot experts overflow (dropped assignments), cold experts burn
MXU cycles on all-zero rows, and the grouped-FFN cost is ``E_loc × capacity``
no matter how skewed the realized routing is. This kernel consumes the
*ragged* layout instead:

* tokens arrive as one flat buffer ``(T, D)``, sorted by expert, each
  expert's segment zero-padded up to the next multiple of the row-tile
  ``bm`` (so every (bm, D) tile belongs to exactly one expert);
* a per-tile expert id array ``tile_group`` (``n_tiles = T // bm``) is
  passed as a **scalar-prefetch** operand (`pltpu.PrefetchScalarGridSpec`):
  the block index maps read it to DMA the right expert's weight blocks, the
  MegaBlocks grouped-GEMM trick;
* tiles past the occupied prefix carry the sentinel id ``E`` — the kernel
  skips their GEMMs entirely (``pl.when``) and writes zeros, and an expert
  with zero routed tokens owns zero tiles, so compute scales with the
  *realized* token count, not with ``E_loc × max_e load_e``.

``ragged_tile_metadata`` builds the layout from per-expert segment sizes
with pure ``jnp`` ops (cumsum + searchsorted), so the whole plan is
O(E log E) array work and jit-compatible: sizes are data-dependent *values*
inside static shapes (``n_tiles`` is a static worst-case bound).

Validated on CPU with ``interpret=True`` against ``ref.ragged_moe_ffn_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_tile_metadata", "ragged_n_tiles", "ragged_moe_ffn_pallas"]


def ragged_n_tiles(n_assign: int, n_groups: int, bm: int) -> int:
    """Static worst-case (bm, D)-tile count for ``n_assign`` rows split over
    ``n_groups`` segments, each padded to a multiple of ``bm``:
    sum_g ceil(s_g / bm) <= floor(A / bm) + G."""
    return n_assign // bm + n_groups


def ragged_tile_metadata(sizes: jnp.ndarray, bm: int, n_tiles: int):
    """Group-aligned ragged layout from per-group segment sizes.

    ``sizes``: (G,) int32 routed-token count per group (data-dependent
    values, static shape). Each group's segment is padded to a multiple of
    ``bm`` so tiles never straddle groups. Returns

    * ``row_offsets`` (G + 1,) int32 — row where each group's segment starts
      in the flat buffer (``row_offsets[-1]`` = total occupied rows);
    * ``tile_group`` (n_tiles,) int32 — owning group per (bm, D) tile, with
      the sentinel ``G`` for tiles past the occupied prefix (callers skip
      them). A group with ``sizes[g] == 0`` owns no tiles at all.
    """
    sizes = sizes.astype(jnp.int32)
    padded = ((sizes + bm - 1) // bm) * bm
    row_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded, dtype=jnp.int32)])
    tile_cum = row_offsets[1:] // bm                     # (G,) cumulative tiles
    tile_group = jnp.searchsorted(
        tile_cum, jnp.arange(n_tiles, dtype=jnp.int32), side="right")
    return row_offsets, tile_group.astype(jnp.int32)


def _kernel(g_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref, *,
            n_groups: int):
    """Grid (n_tiles, F/bf); F innermost → acc stays in VMEM across F."""
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(g_ref[i] < n_groups)
    def _compute():
        x = x_ref[...]                                 # (bm, D)
        h = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        g = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h) * g).astype(x.dtype)       # (bm, bf)
        y = jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _init():
            acc_ref[...] = y

        @pl.when(f > 0)
        def _accum():
            acc_ref[...] += y

    @pl.when((g_ref[i] >= n_groups) & (f == 0))
    def _empty():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(f == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bf", "interpret",
                                             "vmem_limit_bytes"))
def ragged_moe_ffn_pallas(w1, w3, w2, toks, tile_group, *, bf: int = 256,
                          interpret: bool = False,
                          vmem_limit_bytes: Optional[int] = None):
    """toks (T, D) group-sorted flat buffer, tile_group (T // bm,) int32,
    w1/w3 (E, D, F), w2 (E, F, D) → (T, D).

    The row tile ``bm`` is implied by ``T // len(tile_group)``; F is padded
    to a multiple of ``bf`` (zero padding is exact for SwiGLU). Tiles whose
    ``tile_group`` is the sentinel ``E`` are skipped (zeros out); occupied
    tiles fetch their expert's weight blocks through the scalar-prefetch
    index maps. ``vmem_limit_bytes`` raises the compiler's scoped-VMEM
    limit for large blocks (``ops.pick_blocks`` sizes them).
    """
    T, D = toks.shape
    n_tiles = tile_group.shape[0]
    bm = T // n_tiles
    E, _, F = w1.shape
    bf = min(bf, F) if F >= 128 else F
    pf = (-F) % bf
    if pf:
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pf)))
        w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, pf)))
        w2 = jnp.pad(w2, ((0, 0), (0, pf), (0, 0)))
    Fp = F + pf

    wid = lambda i, f, g: (jnp.minimum(g[i], E - 1), 0, f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, Fp // bf),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, f, g: (i, 0)),
            pl.BlockSpec((1, D, bf), wid),
            pl.BlockSpec((1, D, bf), wid),
            pl.BlockSpec((1, bf, D),
                         lambda i, f, g: (jnp.minimum(g[i], E - 1), f, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i, f, g: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_groups=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), toks.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(tile_group, toks, w1, w3, w2)
