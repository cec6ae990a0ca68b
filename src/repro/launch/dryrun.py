import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           + " " + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture × input shape) cell, lower + compile the cell's step
function on the production mesh — 16×16 single pod and 2×16×16 multi-pod —
and record ``memory_analysis()`` (proves it fits), ``cost_analysis()``, and
the Synapse static-watcher analysis (trip-count-aware FLOPs / HBM bytes /
collective wire bytes) into a JSON artifact per cell under
``experiments/artifacts/``.  The roofline table (EXPERIMENTS.md §Roofline)
is generated from these artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import dataclasses
import gc
import json
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cell_is_runnable, get_config, list_archs
from repro.obs import clock as obs_clock
from repro.configs.run import RunConfig, for_shape
from repro.core import hlo_analysis
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.specs import (batch_specs, cache_specs, decode_token_specs,
                                input_specs, rules_table_for)
from repro.models.model_zoo import build_model
from repro.optim.adamw import OptConfig
from repro.parallel.sharding import make_rules
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.step import abstract_train_state, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "artifacts")


# Gradient-accumulation defaults for the big train cells: activations scale
# with tokens/microbatch, so temp memory divides by m (§Perf iteration 3).
TRAIN_MICROBATCHES = {
    "llama4-scout-17b-a16e": 4,
    "qwen2-72b": 4,
    "moonshot-v1-16b-a3b": 4,
    "hymba-1.5b": 2,            # banded-bwd dk/dv carries need headroom
}


def _run_config(shape, overrides=None, arch=None) -> RunConfig:
    run = for_shape(shape.kind)
    if shape.kind == "train" and arch in TRAIN_MICROBATCHES:
        run = dataclasses.replace(
            run, microbatches=TRAIN_MICROBATCHES[arch])
    if overrides:
        run = dataclasses.replace(run, **overrides)
    return run


def lower_cell(cfg, shape, mesh, run: RunConfig):
    """Build and lower the cell's step function; returns (lowered, meta)."""
    model = build_model(cfg, run)
    rules = make_rules(mesh, rules_table_for(shape, run))
    meta = {"params": model.num_params(),
            "active_params": cfg.active_param_count()}

    if shape.kind == "train":
        step = make_train_step(model, OptConfig(), mesh,
                               rules_table=rules_table_for(shape, run))
        state = abstract_train_state(model, mesh, rules)
        (batch,) = input_specs(cfg, shape, mesh, run)
        lowered = jax.jit(step, donate_argnums=0).lower(state, batch)
        tokens = shape.global_batch * shape.seq_len
        if cfg.family == "encdec":
            tokens //= 2          # src/tgt split: each stack sees seq/2
        meta["model_flops"] = 6.0 * meta["active_params"] * tokens
    elif shape.kind == "prefill":
        S = shape.seq_len
        max_len = S // 2 if cfg.family == "encdec" else S
        src_len = S // 2 if cfg.family == "encdec" else None
        step = make_prefill_step(model, max_len=max_len, src_len=src_len,
                                 mesh=mesh)
        (batch,) = input_specs(cfg, shape, mesh, run)
        lowered = jax.jit(step).lower(model.abstract(mesh, rules), batch)
        tokens = shape.global_batch * shape.seq_len
        if cfg.family == "encdec":
            tokens //= 2
        meta["model_flops"] = 2.0 * meta["active_params"] * tokens
    else:  # decode
        step = make_decode_step(model, mesh=mesh,
                                rules_table=rules_table_for(shape))
        toks, cache = input_specs(cfg, shape, mesh, run)
        lowered = jax.jit(step, donate_argnums=2).lower(
            model.abstract(mesh, rules), toks, cache)
        meta["model_flops"] = 2.0 * meta["active_params"] * shape.global_batch
    return lowered, meta


def analyze(lowered, compiled, mesh, meta):
    n_dev = mesh.devices.size
    out = dict(meta)
    out["n_devices"] = int(n_dev)
    out["mesh"] = describe(mesh)

    ma = compiled.memory_analysis()
    if ma is not None:
        out["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
        out["memory"]["per_device_total"] = (
            out["memory"]["argument_bytes"] + out["memory"]["output_bytes"]
            + out["memory"]["temp_bytes"] - out["memory"]["alias_bytes"])

    ca = compiled.cost_analysis()
    if ca:
        out["xla_cost"] = {"flops": float(ca.get("flops", -1)),
                           "bytes_accessed": float(ca.get("bytes accessed", -1))}

    t0 = obs_clock.now()
    cost = hlo_analysis.analyze_hlo(compiled.as_text())
    out["walker"] = {
        "flops": cost.flops,
        "transcendentals": cost.transcendentals,
        "hbm_bytes": cost.hbm_bytes,
        "dot_bytes": cost.dot_bytes,
        "collective_bytes": cost.collective_bytes(),
        "collective_total": cost.collective_total,
        "collective_by_axis": hlo_analysis.attribute_axes(
            cost, describe(mesh)),
        "analysis_s": obs_clock.now() - t0,
        "top_ops": sorted(cost.op_flops.items(), key=lambda kv: -kv[1])[:12],
    }
    out["useful_flops_ratio"] = (
        meta["model_flops"] / (cost.flops * n_dev) if cost.flops else None)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             overrides=None, tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}" + (f"__{tag}" if tag else "")
    record = {"arch": arch, "shape": shape_name, "mesh_tag": mesh_tag,
              "tag": tag, "ok": False}

    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        record.update({"skipped": True, "skip_reason": why, "ok": True})
        _write(out_dir, name, record)
        return record

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        run = _run_config(shape, overrides, arch=arch)
        record["run_config"] = dataclasses.asdict(run)
        t0 = obs_clock.now()
        lowered, meta = lower_cell(cfg, shape, mesh, run)
        record["lower_s"] = obs_clock.now() - t0
        t0 = obs_clock.now()
        compiled = lowered.compile()
        record["compile_s"] = obs_clock.now() - t0
        record.update(analyze(lowered, compiled, mesh, meta))
        record["ok"] = True
        del compiled, lowered
        gc.collect()
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    _write(out_dir, name, record)
    return record


def _write(out_dir, name, record):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(record, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default="",
                    help="comma k=v RunConfig overrides (ints/bools/strs)")
    args = ap.parse_args()

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        if v in ("True", "False"):
            v = v == "True"
        else:
            try:
                v = int(v)
            except ValueError:
                pass
        overrides[k] = v

    cells = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    for a, s, mp in cells:
        mesh_tag = "2x16x16" if mp else "16x16"
        name = f"{a}__{s}__{mesh_tag}" + (f"__{args.tag}" if args.tag else "")
        path = os.path.join(args.out, name + ".json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"[skip] {name}")
                    continue
        t0 = obs_clock.now()
        rec = run_cell(a, s, mp, args.out, overrides or None, args.tag)
        status = "SKIP(" + rec.get("skip_reason", "")[:40] + ")" \
            if rec.get("skipped") else ("ok" if rec["ok"] else
                                        "FAIL " + rec.get("error", "")[:120])
        print(f"[{obs_clock.now()-t0:7.1f}s] {name}: {status}", flush=True)


if __name__ == "__main__":
    main()
