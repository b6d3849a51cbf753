"""Per-arch sharding rules: param specs, input specs, cache specs.

The mesh is fixed — (16,16) = ("data","model") or (2,16,16) with "pod" —
and each arch maps its logical parallel axes onto it (DESIGN.md §5):

* attention / dense FFN — TP over "model" ("heads" mode when head counts
  divide, else "context": sequence-sharded activations, replicated heads);
* MoE experts — EP over "model" for train/prefill (a2a dispatch), EP over
  *all* axes for decode (replicated dispatch, expert duplication);
* weights — FSDP over ("pod","data") for archs too big to replicate
  (gathered per scanned layer inside the block body);
* batch — DP over ("pod","data").

Param specs are assigned by tree-path pattern over the init_params
structure, so a new arch needs no new sharding code unless it adds a new
leaf kind.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import ShardingRules, block_layout, init_cache, init_params

__all__ = ["make_rules", "param_specs", "batch_specs", "cache_specs",
           "tree_shardings", "FSDP_THRESHOLD"]

#: params above this (count) get FSDP weight sharding over ("pod","data").
#: Below it, weights+optimizer replicate across "data" (pure DP) — cheaper
#: in collectives, and small enough to fit (≤1B ⇒ ≤7 GB fp32 opt state).
FSDP_THRESHOLD = 1e9


def make_rules(cfg: ArchConfig, mesh: Optional[Mesh],
               phase: str = "train",
               moe_impl: str = "auto") -> ShardingRules:
    if mesh is None:
        return ShardingRules(mesh=None, moe_dispatch="dense",
                             moe_impl=moe_impl)
    tp_size = mesh.shape.get("model", 1)
    heads_ok = (cfg.n_heads % tp_size == 0 and cfg.n_kv_heads % tp_size == 0
                and tp_size <= cfg.n_kv_heads * (cfg.n_heads // cfg.n_kv_heads))
    fsdp = (("pod", "data") if cfg.n_params() > FSDP_THRESHOLD else None)
    # big experts (≥256 MB per matrix): decode slots over the model axis
    # with per-expert F sliced over the dp axes (expert-TP decode)
    expert_tp = (cfg.is_moe
                 and cfg.d_model * cfg.moe_d_ff * 2 > 256 * 1024 * 1024)
    return ShardingRules(
        mesh=mesh,
        dp=("pod", "data"),
        tp="model",
        ep=("model",),
        ep_all=("pod", "data", "model"),
        fsdp=fsdp,
        attn_mode="heads" if heads_ok else "context",
        moe_dispatch="auto",
        moe_impl=moe_impl,
        capacity_factor=1.25 if phase == "train" else 1.5,
        remat=(phase == "train"),
        decode_expert_tp=expert_tp,
    )


# ---------------------------------------------------------------------------
# param specs by tree path
# ---------------------------------------------------------------------------

def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(f"[{k.idx}]")
    return tuple(names)


def param_specs(cfg: ArchConfig, rules: ShardingRules,
                phase: str = "train") -> Any:
    """Pytree of PartitionSpec matching init_params(cfg, …, phase)."""
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), rules, phase))
    tp = rules.tp
    f = rules.fsdp if rules.fsdp else None
    ep = rules.ep[0] if len(rules.ep) == 1 else rules.ep
    ep_dec = rules.ep_all
    heads = rules.attn_mode == "heads"
    tp_size = rules.axis_size(tp)

    vocab_ok = cfg.vocab % max(tp_size, 1) == 0

    def spec(path, leaf):
        names = _path_names(path)
        name = names[-1]
        in_moe = "ffn" in names and cfg.is_moe and leaf.ndim == 4
        sp = rules.spec  # filters axes absent from the mesh
        if name == "embed":
            return sp(tp if vocab_ok else None, f)
        if name == "head":
            return sp(f, tp if vocab_ok else None)
        if name in ("final_norm", "ln1", "ln2", "ln_scale", "dt_bias",
                    "D_skip"):
            return sp(*([None] * leaf.ndim))
        if in_moe and name in ("w1", "w3", "w2"):
            if phase == "decode":
                if rules.decode_expert_tp:
                    ftp = tuple(a for a in rules.ep_all
                                if a not in rules.ep)
                    if name == "w2":
                        return sp(None, ep, ftp, None)
                    return sp(None, ep, None, ftp)
                return sp(None, ep_dec, None, None)
            return sp(None, ep, f, None)
        if name == "router":
            return sp(None, None, None)
        if name == "wq":
            return sp(None, f, tp if heads else None)
        if name in ("wk", "wv"):
            return sp(None, f, tp if heads else None)
        if name == "wo":
            return sp(None, tp if heads else None, f)
        if name in ("w1", "w3"):                     # dense MLP (3-D: nb,D,F)
            return sp(None, f, tp)
        if name == "w2":
            return sp(None, tp, f)
        if name == "in_proj":                        # mamba (nb, D, 2di)
            return sp(None, f, tp)
        if name == "conv_w":
            return sp(None, None, tp)
        if name == "x_proj":
            return sp(None, tp, None)
        if name == "dt_proj":
            return sp(None, None, tp)
        if name == "A_log":
            return sp(None, tp, None)
        if name == "out_proj":
            return sp(None, tp, f)
        if name == "up":                             # xlstm (nb, D, k·di)
            return sp(None, f, tp)
        if name in ("wq", "wk", "wv"):
            return sp(None, None, tp)
        if name in ("w_if", "w_gates"):
            return sp(None, tp, None)
        if name == "r_gates":                        # (nb, H, hd, 4hd) small
            return sp(None, tp if cfg.n_heads % max(tp_size, 1) == 0 else None,
                      None, None)
        if name == "down":
            return sp(None, tp, f)
        if name == "frontend":
            return sp(None, tp)
        return sp(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, shapes)


# ---------------------------------------------------------------------------
# inputs / cache
# ---------------------------------------------------------------------------

def batch_specs(cfg: ArchConfig, rules: ShardingRules,
                shape: ShapeSpec) -> Tuple[Any, Any]:
    """(ShapeDtypeStructs, PartitionSpecs) for a train/prefill batch."""
    B, S = shape.global_batch, shape.seq_len
    dp = rules.dp
    dp_ok = B % max(rules.axis_size(dp), 1) == 0
    bspec = rules.spec(dp if dp_ok else None, None)
    if cfg.frontend == "audio":
        structs = {"feats": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim),
                                                 jnp.bfloat16),
                   "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        specs = {"feats": rules.spec(dp if dp_ok else None, None, None),
                 "labels": bspec}
    elif cfg.frontend == "vision":
        st = S - cfg.n_patches
        structs = {"tokens": jax.ShapeDtypeStruct((B, st), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((B, st), jnp.int32),
                   "patches": jax.ShapeDtypeStruct(
                       (B, cfg.n_patches, cfg.frontend_dim), jnp.bfloat16)}
        specs = {"tokens": bspec, "labels": bspec,
                 "patches": rules.spec(dp if dp_ok else None, None, None)}
    else:
        structs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        specs = {"tokens": bspec, "labels": bspec}
    if shape.kind == "prefill":
        structs.pop("labels", None)
        specs.pop("labels", None)
    return structs, specs


def cache_specs(cfg: ArchConfig, rules: ShardingRules, batch: int,
                max_seq: int) -> Tuple[Any, Any]:
    """(cache ShapeDtypeStructs, cache PartitionSpecs) for decode."""
    shapes = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq, rules))
    dp = rules.dp
    tp = rules.tp
    dp_ok = batch % max(rules.axis_size(dp), 1) == 0
    b_ax = dp if dp_ok else None
    heads = rules.attn_mode == "heads"
    tp_size = rules.axis_size(tp)

    def kv_spec(leaf):
        # attention K or V (nb, B, KV, S, hd)
        if heads and cfg.n_kv_heads % max(tp_size, 1) == 0:
            return rules.spec(None, b_ax, tp if dp_ok else None,
                              None if dp_ok else tp, None)
        # context mode: shard the sequence (flash-decode psums)
        seq_ax = tp if dp_ok else (dp + (tp,) if isinstance(dp, tuple)
                                   else (dp, tp))
        return rules.spec(None, b_ax, None, seq_ax, None)

    def state_spec(leaf):
        if leaf.ndim == 5:
            # mlstm C (nb, B, H, hd, hd)
            h_ok = leaf.shape[2] % max(tp_size, 1) == 0
            return rules.spec(None, b_ax, tp if h_ok else None, None, None)
        if leaf.ndim == 4:
            # mamba h (nb, B, di, ds) or conv (nb, B, k-1, di)
            if leaf.shape[-1] > 8 and leaf.shape[2] % max(tp_size, 1) != 0:
                return rules.spec(None, b_ax, None, tp)   # conv: di last
            if leaf.shape[2] % max(tp_size, 1) == 0:
                return rules.spec(None, b_ax, tp, None)
            return rules.spec(None, b_ax, None, None)
        if leaf.ndim == 3:
            # mlstm n / slstm states (nb, B, H, hd) is 4-D; (nb,B,H) 3-D
            return rules.spec(None, b_ax, None)
        return rules.spec(*([None] * leaf.ndim))

    _, layout = block_layout(cfg)
    return shapes, [jax.tree.map(kv_spec if s.mixer == "attn" else state_spec,
                                 entry)
                    for s, entry in zip(layout, shapes)]


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
