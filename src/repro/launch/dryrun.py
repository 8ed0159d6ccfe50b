"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) and record
memory / cost / collective analyses (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all                 # 40 pairs, single-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod     # 40 pairs, 2 pods
  ... --variant <name>   # perf-iteration variants (see repro.launch.variants)

Results are cached incrementally in benchmarks/results/dryrun/*.json.
"""
# The next lines MUST run before any other import — jax locks the device
# count on first init, and the production mesh needs 512 host devices.
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config, list_archs
from repro.launch.mesh import make_production_mesh, make_shard_ctx
from repro.launch.specs import (abstract_params, input_specs, variant_for_shape)
from repro.launch.steps import (make_train_step, make_prefill_step,
                                make_serve_step, make_shardings)
from repro.launch.variants import apply_variant, VARIANTS
from repro.sharding.ctx import use_sharding
from repro.utils.hlo import analyze as hlo_analyze
from repro.utils.peaks import chip_peaks

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# roofline constants of the chip the production mesh models (per chip),
# from the one peaks table
TARGET_CHIP = "TPU v5 lite"
_PEAKS = chip_peaks(TARGET_CHIP)
PEAK_FLOPS = _PEAKS["flops_bf16"]
HBM_BW = _PEAKS["hbm_bytes_s"]
ICI_BW = _PEAKS["ici_link_bytes_s"]


def _mem_dict(compiled):
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    if m is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes", "peak_memory_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N_active·D tokens (training) / 2·N_active·D (inference)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch        # decode: one token per sequence


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            variant: str = "baseline", force: bool = False,
            results_dir: pathlib.Path = RESULTS_DIR) -> dict:
    mesh_tag = "pod2" if multi_pod else "pod1"
    key = f"{arch}__{shape_name}__{mesh_tag}"
    if variant != "baseline":
        key += f"__{variant}"
    results_dir.mkdir(parents=True, exist_ok=True)
    out_path = results_dir / f"{key}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "variant": variant, "kind": shape.kind, "ok": False}
    t0 = time.time()
    try:
        with apply_variant(variant):
            # cfg derivation inside the variant scope: some variants transform
            # the config itself (e.g. padded_heads)
            cfg = variant_for_shape(get_config(arch), shape)
            rec.update(params=cfg.param_count(),
                       active_params=cfg.active_param_count(),
                       model_flops=_model_flops(cfg, shape))
            mesh = make_production_mesh(multi_pod=multi_pod)
            ctx = make_shard_ctx(mesh)
            params_abs = abstract_params(cfg)
            specs = input_specs(cfg, shape)

            with use_sharding(ctx):
                if shape.kind == "train":
                    step, optimizer = make_train_step(cfg)
                    opt_abs = jax.eval_shape(optimizer.init, params_abs)
                    sh = make_shardings(cfg, shape, ctx, params_abs,
                                        batch_abs=specs["batch"])
                    jitted = jax.jit(step,
                                     in_shardings=(sh["params"], sh["opt"],
                                                   sh["batch"], None),
                                     out_shardings=(sh["params"], sh["opt"], None),
                                     donate_argnums=(0, 1))
                    lowered = jitted.lower(
                        params_abs, opt_abs, specs["batch"],
                        jax.ShapeDtypeStruct((), jnp.float32))
                elif shape.kind == "prefill":
                    step = make_prefill_step(cfg)
                    sh = make_shardings(cfg, shape, ctx, params_abs,
                                        batch_abs=specs["batch"])
                    jitted = jax.jit(step, in_shardings=(sh["params"], sh["batch"]))
                    lowered = jitted.lower(params_abs, specs["batch"])
                else:  # decode
                    step = make_serve_step(cfg)
                    cache_abs = specs["cache"]
                    sh = make_shardings(cfg, shape, ctx, params_abs,
                                        cache_abs=cache_abs)
                    tok_sharding = None
                    jitted = jax.jit(step,
                                     in_shardings=(sh["params"], tok_sharding,
                                                   sh["cache"]),
                                     out_shardings=(None, sh["cache"]),
                                     donate_argnums=(2,))
                    lowered = jitted.lower(params_abs, specs["tokens"], cache_abs)

                rec["lower_s"] = round(time.time() - t0, 2)
                t1 = time.time()
                compiled = lowered.compile()
                rec["compile_s"] = round(time.time() - t1, 2)

        # the brief's required artifacts: memory_analysis proves the program
        # fits; cost_analysis feeds §Roofline (printed compactly, full record
        # goes to JSON)
        print(f"[dryrun] {key} memory_analysis: {_mem_dict(compiled)}")
        # raw XLA numbers (while bodies counted ONCE — kept for reference)
        cost = compiled.cost_analysis() or {}
        print(f"[dryrun] {key} cost_analysis: flops={cost.get('flops', 0):.4g} "
              f"bytes={cost.get('bytes accessed', 0):.4g} (raw; loop-aware "
              f"numbers in the record)")
        rec["xla_flops_raw"] = float(cost.get("flops", 0.0))
        rec["xla_bytes_raw"] = float(cost.get("bytes accessed", 0.0))
        rec["mem"] = _mem_dict(compiled)
        # loop-trip-aware walk of the compiled HLO (utils/hlo.py)
        hc = hlo_analyze(compiled.as_text())
        rec["flops_per_device"] = float(hc.flops)
        rec["bytes_per_device"] = float(hc.bytes)
        rec["collectives"] = hc.collectives
        rec["collective_bytes_per_device"] = int(hc.collective_bytes)

        # roofline terms (seconds); SPMD module stats are per-device, so
        # flops_pd/peak == HLO_FLOPs_global/(chips*peak)
        rec["compute_term_s"] = rec["flops_per_device"] / PEAK_FLOPS
        rec["memory_term_s"] = rec["bytes_per_device"] / HBM_BW
        rec["collective_term_s"] = rec["collective_bytes_per_device"] / ICI_BW
        terms = {"compute": rec["compute_term_s"], "memory": rec["memory_term_s"],
                 "collective": rec["collective_term_s"]}
        rec["dominant"] = max(terms, key=terms.get)
        chips = int(np.prod(mesh.devices.shape))
        rec["chips"] = chips
        rec["useful_flop_ratio"] = (rec["model_flops"] /
                                    max(rec["flops_per_device"] * chips, 1.0))
        rec["ok"] = True
    except Exception as e:  # record the failure for triage, don't hide it
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    out_path.write_text(json.dumps(rec, indent=1))
    status = "ok" if rec["ok"] else f"FAIL ({rec.get('error', '?')[:120]})"
    print(f"[dryrun] {key}: {status}  ({rec['total_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*INPUT_SHAPES, None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    args = ap.parse_args()

    if args.all:
        combos = [(a, s) for a in list_archs() for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape in combos:
        rec = run_one(arch, shape, multi_pod=args.multi_pod,
                      variant=args.variant, force=args.force)
        n_fail += 0 if rec["ok"] else 1
    print(f"[dryrun] done; {len(combos) - n_fail}/{len(combos)} ok")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
