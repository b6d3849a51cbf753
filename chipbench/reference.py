"""Plain float32 reference of the served MoE transformer, and its control.

Written from the architecture, in ``jax.numpy``, independent of the
program: it imports nothing of ``repro``. A block is pre-norm GQA attention
with rotary positions (rotate-half form), then a softmax-top-k MoE of
SwiGLU experts, gates renormalized over the chosen experts; a final RMS
norm and the unembedding give the logits. Norm scales are 1.

The weights come from the seed, drawn the way the served model draws them
(``threefry`` keys, split per role and per layer; normal entries scaled by
1/sqrt(fan-in); rounded to bfloat16, the type they are served in; the
router kept in float32). The reference regenerates them layer by layer, so
that only one layer is held at a time, and computes in float32 at
``highest`` matmul precision. Attention runs in blocks of queries.

``prec="fp8"`` is the control: every matmul operand is rounded to
float8 (e4m3, one scale per tensor) before the product, the step down
from the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Arch", "arch_from_config", "Reference"]

_Q_BLOCK = 512
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    top_k: int
    d_expert: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool


def arch_from_config(model: dict) -> Arch:
    """The sizes of a configuration file's ``model`` group, under the
    published (Hugging Face) key names of its model type: the experts'
    count is ``num_local_experts`` (granitemoe) or ``num_experts``
    (qwen3_moe), one expert's width ``moe_intermediate_size`` or, where a
    config has no such key, ``intermediate_size``; without ``head_dim``
    a head is ``hidden_size / num_attention_heads`` wide."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    return Arch(
        n_layers=model["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or d // heads,
        n_experts=model.get("num_local_experts") or model["num_experts"],
        top_k=model["num_experts_per_tok"],
        d_expert=(model.get("moe_intermediate_size")
                  or model["intermediate_size"]),
        vocab=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        tied=bool(model["tie_word_embeddings"]))


# -- weights from the seed ---------------------------------------------------

def _dense(key, d_in, d_out, dtype=jnp.bfloat16):
    scale = 1.0 / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def _experts(key, n, a, b):
    return (jax.random.normal(key, (n, a, b), jnp.float32)
            / np.sqrt(a)).astype(jnp.bfloat16)


def _layer_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    d, hd = arch.d_model, arch.head_dim
    k_attn, k_moe, _ = jax.random.split(key, 3)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_moe, 4)
    E, f = arch.n_experts, arch.d_expert
    return {
        "wq": _dense(ka[0], d, arch.n_heads * hd),
        "wk": _dense(ka[1], d, arch.n_kv_heads * hd),
        "wv": _dense(ka[2], d, arch.n_kv_heads * hd),
        "wo": _dense(ka[3], arch.n_heads * hd, d),
        "router": _dense(km[0], d, E, jnp.float32),
        "w1": _experts(km[1], E, d, f),
        "w3": _experts(km[2], E, d, f),
        "w2": _experts(km[3], E, f, d),
    }


# -- arithmetic ----------------------------------------------------------------

def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (S, h, hd), rotate-half form."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(arch: Arch, w, h, fp8):
    S = h.shape[0]
    H, KV, hd = arch.n_heads, arch.n_kv_heads, arch.head_dim
    pos = jnp.arange(S)
    q = _rope(_mm("sd,dh->sh", h, w["wq"], fp8).reshape(S, H, hd), pos,
              arch.rope_theta)
    k = _rope(_mm("sd,dh->sh", h, w["wk"], fp8).reshape(S, KV, hd), pos,
              arch.rope_theta)
    v = _mm("sd,dh->sh", h, w["wv"], fp8).reshape(S, KV, hd)
    rep = H // KV                       # query head j reads kv head j // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = S // _Q_BLOCK

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * _Q_BLOCK, _Q_BLOCK, 0)
        s = _mm("qhd,khd->hqk", qi, k, fp8) / np.sqrt(hd)
        qpos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, fp8)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * hd)
    return _mm("sh,hd->sd", o, w["wo"], fp8)


def _moe(arch: Arch, w, h, fp8):
    logits = _mm("sd,de->se", h, w["router"], fp8)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, arch.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)        # (S, E)

    def expert(acc, e):
        w1, w3, w2 = w["w1"][e], w["w3"][e], w["w2"][e]
        y = _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, w1, fp8))
                * _mm("sd,df->sf", h, w3, fp8), w2, fp8)
        return acc + gate[:, e, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          jnp.arange(arch.n_experts))
    return out


def _block(arch: Arch, fp8: bool, w, x):
    x = x + _attention(arch, w, _rms(x, arch.norm_eps), fp8)
    return x + _moe(arch, w, _rms(x, arch.norm_eps), fp8)


class Reference:
    """Logits of the reference for whole token sequences, from the seed."""

    def __init__(self, arch: Arch, weight_seed: int):
        self.arch = arch
        keys = jax.random.split(jax.random.PRNGKey(weight_seed), 9)
        self._embed_key, self._head_key = keys[0], keys[1]
        self._layer_keys = jax.random.split(keys[8], arch.n_layers)
        self._gen = jax.jit(functools.partial(_layer_weights, arch))
        self._blocks = {p: jax.jit(functools.partial(_block, arch, p == "fp8"))
                        for p in ("f32", "fp8")}

    def logits(self, seqs: List[np.ndarray], rows: List[np.ndarray],
               precs=("f32",)) -> Dict[str, List[np.ndarray]]:
        """For each sequence, the logits (len(rows), V) at the given row
        positions, once per precision in ``precs``. Sequences are padded at
        the end to a power of two, which a causal model never reads back."""
        arch = self.arch
        embed = _dense(self._embed_key, arch.vocab, arch.d_model)
        # power-of-two lengths from one attention block up: few programs
        pad = [max(_Q_BLOCK, 1 << (len(s) - 1).bit_length()) for s in seqs]
        xs = {p: [jnp.take(embed, jnp.asarray(np.pad(s, (0, P - len(s)))),
                           axis=0).astype(jnp.float32)
                  for s, P in zip(seqs, pad)] for p in precs}
        for layer in range(arch.n_layers):
            w = self._gen(self._layer_keys[layer])
            for p in precs:
                xs[p] = [self._blocks[p](w, x) for x in xs[p]]
            del w
        head = (embed.T if arch.tied
                else _dense(self._head_key, arch.d_model, arch.vocab))
        out: Dict[str, List[np.ndarray]] = {}
        for p in precs:
            out[p] = [np.asarray(_mm("sd,dv->sv",
                                     _rms(x[jnp.asarray(r)], arch.norm_eps),
                                     head, p == "fp8"))
                      for x, r in zip(xs[p], rows)]
        return out


def gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best, row by row of ``ref`` (n, V)."""
    return ref.max(axis=1) - ref[np.arange(len(chosen)), chosen]
