"""Whether the timed path served the right tokens.

Once the window has closed, a sample drawn from the seed of the requests it
finished, the one with the most served tokens always among them, is run
through the plain reference (``chipbench.reference``) over each prompt and
its served tokens. The number compared is the widest gap by which a served
(greedy) token's reference logit lies below the reference's best at that
position. The control computes the same positions in float8 and reads the
gap of the token it puts first.

The prompt of request ``r`` is what the engine feeds it: ``prompt_len``
ids drawn by ``numpy.random.default_rng(r)`` over the vocabulary.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.reference import Reference, gaps

__all__ = ["prompt_ids", "sample", "readings"]


def prompt_ids(req_id: int, prompt_len: int, vocab: int) -> np.ndarray:
    return np.random.default_rng(req_id).integers(
        0, vocab, size=(1, prompt_len))[0]


def sample(reqs, seed: int, tokens: int, max_requests: int) -> List:
    """Finished requests, the longest first, then others drawn from the
    seed until ``tokens`` served tokens or ``max_requests`` requests."""
    done = sorted((r for r in reqs if r.finished and r.tokens),
                  key=lambda r: r.req_id)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.req_id))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def readings(ref: Reference, picked, precs=("f32",)) -> Dict[str, float]:
    """Of the served tokens: the widest gap (``max_logit_gap``), its 99th
    percentile (``q99_logit_gap``), the mean gap (``mean_logit_gap``) and
    the share that is not the reference's first choice
    (``logit_gap_share``), with ``compared_tokens``; with ``"fp8"`` in
    ``precs`` the same of the control's first choices (``max_control_gap``,
    ``q99_control_gap``, ``mean_control_gap``, ``control_gap_share``)."""
    vocab = ref.arch.vocab
    seqs, rows, served = [], [], []
    for r in picked:
        p = prompt_ids(r.req_id, r.prompt_len, vocab)
        toks = np.asarray(r.tokens, np.int64)
        seqs.append(np.concatenate([p, toks[:-1]]))
        rows.append(np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(toks)))
        served.append(toks)
    # a random model that only echoes its input would make a weak check
    inputs = [s[r] for s, r in zip(seqs, rows)]
    out = {"compared_tokens": float(sum(len(t) for t in served)),
           "echo_share": float(np.mean(np.concatenate(
               [t == i for t, i in zip(served, inputs)]))),
           "max_logit_gap": float("inf"), "mean_logit_gap": float("inf")}
    if not picked:
        return out
    logits = ref.logits(seqs, rows, precs)
    for prec, name in (("f32", "logit_gap"), ("fp8", "control_gap")):
        if prec not in logits:
            continue
        chosen = (served if prec == "f32"
                  else [c.argmax(axis=1) for c in logits[prec]])
        g = np.concatenate([gaps(l, c) for l, c in zip(logits["f32"], chosen)])
        out[f"max_{name}"] = float(g.max())
        out[f"q99_{name}"] = float(np.quantile(g, 0.99))
        out[f"mean_{name}"] = float(g.mean())
        out[f"{name}_share"] = float(np.mean(g > 0))
    return out
