"""Model FLOPs from a configuration's shapes, and the chip's peaks.

The count is of the work the model needs, whatever implements it: 2 FLOPs
per multiply-add; the attention and MoE projections of every token; its
scores and values against the context it attends to; the routed top-k
experts only (never the slots a dense implementation also computes); the
unembedding only for rows that yield a token. Norms, softmax, rotary
embedding and the router's top-k are left out.
"""

from __future__ import annotations

import json
from pathlib import Path

from chipbench.reference import Arch

__all__ = ["TokenCost", "token_cost", "from_config", "peak_flops"]

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class TokenCost:
    """FLOPs of one token through all layers: ``fixed + per_ctx * ctx``,
    where ``ctx`` is the number of positions it attends to."""

    def __init__(self, arch: Arch):
        d, hd = arch.d_model, arch.head_dim
        q, kv = arch.n_heads * hd, arch.n_kv_heads * hd
        per_layer = (2 * d * q + 2 * 2 * d * kv + 2 * q * d    # q, k, v, o
                     + 2 * d * arch.n_experts                    # router
                     + arch.top_k * 3 * 2 * d * arch.d_expert)   # experts
        self.fixed = arch.n_layers * per_layer
        self.per_ctx = arch.n_layers * 2 * 2 * q           # q.k and p.v
        self.unembed = 2 * d * arch.vocab

    def tokens(self, n: int, ctx_sum: int) -> float:
        """``n`` tokens whose contexts add up to ``ctx_sum``."""
        return float(n * self.fixed + self.per_ctx * ctx_sum)


def token_cost(arch: Arch) -> TokenCost:
    return TokenCost(arch)


def from_config(config: dict, arch: Arch) -> TokenCost:
    """The module's entry (``chipbench.harness.model_of``): the count of a
    configuration file's model, whose sizes the reference module gave."""
    return token_cost(arch)


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return float(table[device_kind]["bf16_flops_per_s"])
