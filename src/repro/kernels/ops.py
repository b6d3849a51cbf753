"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively. On the CPU backend they run
in ``interpret=True`` mode, which executes the kernel body exactly — so the
same call sites work in CPU tests and on the chip. Any other backend raises:
no kernel falls back silently.

``pick_blocks`` chooses MXU-aligned block shapes whose VMEM footprint fits
``_TILE_BUDGET``. Pallas double-buffers every input and output block, so the
footprint counts x, the output and the w1/w3/w2 blocks twice, plus the f32
accumulator scratch and the f32 (bm, bf) intermediates of the SwiGLU. The
kernels ask the compiler for ``_VMEM_LIMIT`` of scoped VMEM (v5e has 128
MiB; the default scope is 16 MiB), which leaves room for Mosaic's own
scratch above the tile budget.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from .moe_ffn import fused_moe_ffn_pallas
from .ragged_moe_ffn import ragged_moe_ffn_pallas
from .router import router_topk_pallas

__all__ = ["fused_moe_ffn", "ragged_moe_ffn", "router_topk", "pick_blocks",
           "vmem_bytes"]

_VMEM_LIMIT = 64 * 1024 * 1024      # scoped VMEM requested per kernel
_TILE_BUDGET = 48 * 1024 * 1024     # what the blocks + scratch may take


def _interpret() -> bool:
    """Interpret the kernels on the CPU backend, compile them on a TPU."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU (or the CPU "
                       f"interpreter); the default backend is {backend!r}")


def vmem_bytes(bm: int, bf: int, D: int, dtype_bytes: int = 2) -> int:
    """VMEM footprint of one grouped-FFN grid step with (bm, bf) blocks."""
    return (2 * bm * D * dtype_bytes          # x block, double-buffered
            + 2 * bm * D * dtype_bytes        # output block, double-buffered
            + 2 * 3 * D * bf * dtype_bytes    # w1/w3/w2 blocks, double-buffered
            + bm * D * 4                      # f32 accumulator scratch
            + 3 * bm * bf * 4)                # f32 h, g and silu(h)·g


def pick_blocks(D: int, F: int, dtype_bytes: int = 2,
                bm: Optional[int] = None) -> Tuple[int, int]:
    """(bm, bf) fitting the VMEM budget, preferring large MXU-aligned tiles.

    ``bm`` pins the row tile (the ragged kernel's tile is fixed by its
    layout); otherwise it is chosen too. Raises when nothing fits."""
    for m in ((bm,) if bm else (512, 256, 128)):
        for bf in (1024, 512, 256, 128):
            if vmem_bytes(m, bf, D, dtype_bytes) <= _TILE_BUDGET:
                return m, min(bf, F)
    raise ValueError(f"no grouped-FFN block fits {_TILE_BUDGET} B of VMEM "
                     f"at D={D} (bm={bm})")


def fused_moe_ffn(w1, w3, w2, toks):
    """Drop-in replacement for models.moe.expert_ffn_ref (same signature)."""
    E, C, D = toks.shape
    F = w1.shape[-1]
    bm, bf = pick_blocks(D, F)
    return fused_moe_ffn_pallas(w1, w3, w2, toks, bm=bm, bf=bf,
                                interpret=_interpret(),
                                vmem_limit_bytes=_VMEM_LIMIT)


def ragged_moe_ffn(w1, w3, w2, toks, tile_group):
    """Ragged grouped FFN: flat group-sorted (T, D) buffer + per-tile expert
    ids (see kernels.ragged_moe_ffn). Drop-in for the dispatch's ragged
    ffn slot; the row tile bm is implied by T // len(tile_group)."""
    T, D = toks.shape
    F = w1.shape[-1]
    _, bf = pick_blocks(D, F, bm=T // tile_group.shape[0])
    return ragged_moe_ffn_pallas(w1, w3, w2, toks, tile_group, bf=bf,
                                 interpret=_interpret(),
                                 vmem_limit_bytes=_VMEM_LIMIT)


def router_topk(logits, top_k: int):
    return router_topk_pallas(logits, top_k, interpret=_interpret())
