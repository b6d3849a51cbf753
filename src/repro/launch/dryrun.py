import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the *real* step function (train_step /
prefill_step / serve_step), lowers it against ShapeDtypeStruct stand-ins
with the production shardings (no allocation), compiles it, and records:

* ``memory_analysis()``   — per-device buffer sizes (proves it fits),
* ``cost_analysis()``     — XLA's module-level FLOPs (body-once),
* trip-count-corrected FLOPs / bytes / collective bytes from the compiled
  HLO text (launch/hlo_analysis.py) — the §Roofline inputs.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun

A failure here (sharding mismatch, OOM at compile, unsupported collective)
is a bug in the system, not in the run.
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL_ARCHS, EXTRA_ARCHS, SHAPES, get, shape_applicable
from repro.models import (decode_fn, init_params, loss_fn,
                          make_moe_tables, prefill_fn)
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update, \
    cosine_lr
from .hlo_analysis import parse_hlo
from .mesh import make_production_mesh
from .sharding import batch_specs, cache_specs, make_rules, param_specs, \
    tree_shardings

__all__ = ["run_cell", "input_specs", "main"]


def _struct_tree(shapes, specs, mesh):
    return jax.tree.map(
        lambda st, sp: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                            sharding=NamedSharding(mesh, sp)),
        shapes, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def input_specs(arch: str, shape_name: str, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (+ shardings) for one cell."""
    cfg = get(arch)
    shape = SHAPES[shape_name]
    phase = {"train": "train", "prefill": "prefill",
             "decode": "decode"}[shape.kind]
    rules = make_rules(cfg, mesh, phase)
    out: Dict[str, Any] = {"cfg": cfg, "rules": rules, "shape": shape,
                           "phase": phase}

    pshapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), rules, phase))
    pspecs = param_specs(cfg, rules, phase)
    out["params"] = _struct_tree(pshapes, pspecs, mesh)
    out["param_specs"] = pspecs

    if cfg.is_moe:
        st, nc, cdf = make_moe_tables(cfg, rules, phase=phase)
        out["moe_tables"] = (jax.device_put(st), jax.device_put(nc),
                             jax.device_put(cdf))
    else:
        out["moe_tables"] = None

    if shape.kind in ("train", "prefill"):
        bshapes, bspecs = batch_specs(cfg, rules, shape)
        out["batch"] = _struct_tree(bshapes, bspecs, mesh)
    if shape.kind == "train":
        oshapes = jax.eval_shape(adamw_init, pshapes)
        # moments/master mirror the param specs leaf-wise (ZeRO-style)
        ospecs = type(oshapes)(P(), pspecs, pspecs, pspecs)
        out["opt"] = _struct_tree(oshapes, ospecs, mesh)
        out["opt_specs"] = ospecs
    if shape.kind == "decode":
        B, S = shape.global_batch, shape.seq_len
        cshapes, cspecs = cache_specs(cfg, rules, B, S)
        out["cache"] = _struct_tree(cshapes, cspecs, mesh)
        out["token"] = jax.ShapeDtypeStruct(
            (B, 1), jnp.int32, sharding=NamedSharding(mesh, rules.spec(
                rules.dp if B % max(rules.axis_size(rules.dp), 1) == 0
                else None, None)))
        out["pos"] = jax.ShapeDtypeStruct(
            (B,), jnp.int32, sharding=NamedSharding(mesh, P()))
    return out


def _build_lowered(spec: Dict[str, Any], mesh):
    cfg, rules, shape = spec["cfg"], spec["rules"], spec["shape"]
    if shape.kind == "train":
        lossf = loss_fn(cfg, rules)
        ocfg = AdamWConfig()

        def step(params, opt, batch, mt):
            (loss, (tallies, aux)), grads = jax.value_and_grad(
                lossf, has_aux=True)(params, batch, mt)
            lr = cosine_lr(ocfg, opt.step)
            params, opt = adamw_update(grads, opt, params, ocfg, lr)
            return params, opt, loss, tallies

        pshard = tree_shardings(mesh, spec["param_specs"])
        oshard = tree_shardings(mesh, spec["opt_specs"])
        fn = jax.jit(step, donate_argnums=(0, 1),
                     out_shardings=(pshard, oshard,
                                    NamedSharding(mesh, P()),
                                    NamedSharding(mesh, P())))
        return fn.lower(spec["params"], spec["opt"], spec["batch"],
                        spec["moe_tables"])
    if shape.kind == "prefill":
        pf = prefill_fn(cfg, rules)
        fn = jax.jit(pf)
        return fn.lower(spec["params"], spec["batch"], spec["moe_tables"])
    df = decode_fn(cfg, rules)
    fn = jax.jit(df, donate_argnums=(2,))
    return fn.lower(spec["params"], spec["token"], spec["cache"],
                    spec["pos"], spec["moe_tables"])


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             analyze: bool = True) -> Dict[str, Any]:
    """Lower+compile one cell; returns the record for EXPERIMENTS.md."""
    cfg = get(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        spec = input_specs(arch, shape_name, mesh)
        with jax.set_mesh(mesh):
            lowered = _build_lowered(spec, mesh)
            t1 = time.time()
            compiled = lowered.compile()
        t2 = time.time()
        rec.update(status="ok", lower_s=round(t1 - t0, 1),
                   compile_s=round(t2 - t1, 1))
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes")
                if hasattr(ma, k)}
            arg = rec["memory"].get("argument_size_in_bytes", 0)
            tmp = rec["memory"].get("temp_size_in_bytes", 0)
            alias = rec["memory"].get("alias_size_in_bytes", 0)
            outb = rec["memory"].get("output_size_in_bytes", 0)
            rec["memory"]["per_device_total_bytes"] = arg + tmp + max(
                outb - alias, 0)
        except Exception as e:                      # pragma: no cover
            rec["memory_error"] = str(e)
        try:
            ca = compiled.cost_analysis()
            rec["xla_cost"] = {k: float(ca[k]) for k in
                               ("flops", "bytes accessed") if k in ca}
        except Exception as e:                      # pragma: no cover
            rec["xla_cost_error"] = str(e)
        if analyze:
            costs = parse_hlo(compiled.as_text())
            rec["hlo"] = {
                "flops_per_device": costs.flops,
                "bytes_per_device": costs.bytes_accessed,
                "collective_bytes_per_device": costs.collective_bytes,
                "collective_by_kind": costs.collective_by_kind,
                "while_trip_counts": costs.while_trip_counts,
            }
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   elapsed_s=round(time.time() - t0, 1))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-extra", action="store_true",
                    help="also run the paper's own deepseek-v3 config")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-analyze", action="store_true")
    args = ap.parse_args()

    archs = ([args.arch] if args.arch else
             ALL_ARCHS + (EXTRA_ARCHS if args.include_extra else []))
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_fail = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{'multi' if multi else 'single'}__{arch}__{shape}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached] {tag}: {prev['status']}")
                        continue
                rec = run_cell(arch, shape, multi,
                               analyze=not args.no_analyze)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                msg = rec["status"]
                if rec["status"] == "ok":
                    mem = rec.get("memory", {}).get("per_device_total_bytes", 0)
                    msg += (f" compile={rec['compile_s']}s "
                            f"mem/dev={mem/2**30:.2f}GiB "
                            f"flops/dev={rec.get('hlo', {}).get('flops_per_device', 0):.3g}")
                elif rec["status"] == "error":
                    n_fail += 1
                    msg += " " + rec["error"][:160]
                print(f"[{tag}] {msg}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
