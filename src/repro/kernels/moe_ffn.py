"""Fused grouped MoE expert FFN — the paper's kernel-level hot spot.

The paper's measurements are dominated by the *fused MoE kernel* (AITER on
ROCm): per MoE layer, 49% of prefill time (Fig 3), and it is precisely this
kernel whose per-device latency f_g(n) ViBE profiles and balances. This is
the TPU-native adaptation (DESIGN.md §3):

* GPU version: per-expert grouped GEMM tiles scheduled across CUs, fusing
  gate/up/down projections with the silu epilogue.
* TPU version (here): one ``pl.pallas_call`` over grid (E, C/bm, F/bf) with
  the **output block resident in VMEM across the F sweep** — the F axis is
  innermost, so the (bm, D) fp32 accumulator never round-trips to HBM, and
  the three GEMMs + silu fuse into a single kernel. MXU alignment comes
  from 128-multiple block shapes; VMEM budget drives the block choice
  (see ``ops.pick_blocks``).

Capacity-bucket semantics: unused capacity rows are zero (the EP dispatch
scatters into a zero buffer), and SwiGLU(0) = 0, so no masking is needed.

Validated on CPU with ``interpret=True`` against ``ref.moe_ffn_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_moe_ffn_pallas"]


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref):
    """Grid (E, C/bm, F/bf); F innermost → acc stays in VMEM across F."""
    f = pl.program_id(2)
    x = x_ref[0]                                   # (bm, D)
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

    @pl.when(f == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret",
                                             "vmem_limit_bytes"))
def fused_moe_ffn_pallas(w1, w3, w2, toks, *, bm: int = 128, bf: int = 256,
                         interpret: bool = False,
                         vmem_limit_bytes: Optional[int] = None):
    """toks (E, C, D), w1/w3 (E, D, F), w2 (E, F, D) → (E, C, D).

    C is padded to a multiple of ``bm`` and F to a multiple of ``bf``
    (zero padding is exact for SwiGLU — see module docstring).
    ``vmem_limit_bytes`` raises the compiler's scoped-VMEM limit for large
    blocks (``ops.pick_blocks`` sizes them).
    """
    E, C, D = toks.shape
    F = w1.shape[-1]
    bm = min(bm, C) if C >= 8 else C
    bf = min(bf, F) if F >= 128 else F
    pc = (-C) % bm
    pf = (-F) % bf
    if pc:
        toks = jnp.pad(toks, ((0, 0), (0, pc), (0, 0)))
    if pf:
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pf)))
        w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, pf)))
        w2 = jnp.pad(w2, ((0, 0), (0, pf), (0, 0)))
    Cp, Fp = C + pc, F + pf

    grid = (E, Cp // bm, Fp // bf)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, D), lambda e, i, f: (e, i, 0)),
            pl.BlockSpec((1, D, bf), lambda e, i, f: (e, 0, f)),
            pl.BlockSpec((1, D, bf), lambda e, i, f: (e, 0, f)),
            pl.BlockSpec((1, bf, D), lambda e, i, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, D), lambda e, i, f: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, D), toks.dtype),
        scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(toks, w1, w3, w2)
    return out[:, :C] if pc else out
