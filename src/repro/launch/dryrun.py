"""Multi-pod dry run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, extract roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
        --shape decode_32k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results are appended as JSON files under experiments/dryrun/ and summarized
in EXPERIMENTS.md section Dry-run / section Roofline.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede the jax import: jax locks the device count on first
#   init, and the production meshes below need 512 placeholder host devices.

import argparse
import json
import time
import traceback

import jax

from repro.config import (ARCH_IDS, INPUT_SHAPES, get_config, shape_applies)
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh, n_chips
from repro.launch.sharding import (batch_specs, cache_specs, opt_specs,
                                   param_specs, to_shardings)
from repro.launch.steps import (input_specs, make_prefill_step,
                                make_serve_step, make_train_step_for)
from repro.models.model import build_model


def lower_case(arch: str, shape_name: str, multi_pod: bool,
               donate: bool = True, kv_shard: str | None = None,
               kv_quant: bool = False) -> dict:
    """Lower + compile one (arch, input-shape) case; return its report dict
    (memory analysis, collective bytes, roofline terms, timings)."""
    cfg = get_config(arch)
    if kv_quant:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_quant=True)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "mode": shape.mode}
    if not shape_applies(cfg, shape):
        return {**base, "skipped": "long_500k needs sub-quadratic attention "
                                   "(DESIGN.md section 5)"}

    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = input_specs(cfg, shape_name, model)
    pspecs = param_specs(spec["params"], mesh)
    pshard = to_shardings(pspecs, mesh)
    t0 = time.time()
    # ambient mesh: activates models/hints.py
    ctx = jax.set_mesh(mesh)
    ctx.__enter__()

    if spec["mode"] == "train":
        step = make_train_step_for(model)
        oshard = to_shardings(
            jax.tree.map(lambda s: s, opt_specs(spec["opt"], pspecs),
                         is_leaf=lambda x: isinstance(
                             x, jax.sharding.PartitionSpec)), mesh)
        bshard = to_shardings(batch_specs(spec["batch"], mesh), mesh)
        jf = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     donate_argnums=(0, 1) if donate else ())
        lowered = jf.lower(spec["params"], spec["opt"], spec["batch"])
    elif spec["mode"] == "prefill":
        cshard = to_shardings(cache_specs(spec["cache"], mesh,
                                          kv_shard=kv_shard), mesh)
        bshard = to_shardings(batch_specs(spec["batch"], mesh), mesh)
        base_step = make_prefill_step(model)
        keys = sorted(spec["batch"].keys())          # tokens [+ frontend]
        fr_keys = [k for k in keys if k != "tokens"]

        def step(params, cache, tokens, *fr):
            """Positional-frontend adapter for jit in_shardings."""
            kw = dict(zip(fr_keys, fr))
            return base_step(params, cache, tokens, **kw)
        jf = jax.jit(step, in_shardings=(
            pshard, cshard, bshard["tokens"],
            *[bshard[k] for k in fr_keys]),
            donate_argnums=(1,) if donate else ())
        lowered = jf.lower(spec["params"], spec["cache"],
                           spec["batch"]["tokens"],
                           *[spec["batch"][k] for k in fr_keys])
    else:
        cshard = to_shardings(cache_specs(spec["cache"], mesh,
                                          kv_shard=kv_shard), mesh)
        bshard = to_shardings(batch_specs(spec["batch"], mesh), mesh)
        step = make_serve_step(model)
        jf = jax.jit(step, in_shardings=(
            pshard, cshard, bshard["tokens"], bshard["q_prev"]),
            donate_argnums=(1,) if donate else ())
        lowered = jf.lower(spec["params"], spec["cache"],
                           spec["batch"]["tokens"], spec["batch"]["q_prev"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ctx.__exit__(None, None, None)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = analysis.parse_collectives(compiled.as_text())
    rl = analysis.Roofline(
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_bytes=float(sum(coll.values())),
        n_chips=n_chips(mesh),
        model_flops=analysis.model_flops_estimate(cfg, shape),
        model_bytes_per_device=analysis.model_bytes_estimate(
            cfg, shape, n_chips(mesh)),
        collectives=coll)
    report = {
        **base,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "peak_per_device_gb": (mem.argument_size_in_bytes
                                   + mem.output_size_in_bytes
                                   + mem.temp_size_in_bytes
                                   - mem.alias_size_in_bytes) / 1e9,
        },
        "collectives": coll,
        "roofline": rl.as_dict(),
    }
    return report


def main():
    """CLI entry: run the selected (or all) dry-run cases and save JSON."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kv-shard", choices=("seq", "hd"), default=None,
                    help="narrow-KH cache sharding mode (default: "
                         "sharding.KV_SHARD)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache with per-(token,head) scales")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cases = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                cases.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cases.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cases:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        tag = f"{arch}_{shape}_{mesh_name}"
        try:
            rep = lower_case(arch, shape, args.multi_pod,
                             kv_shard=args.kv_shard, kv_quant=args.kv_quant)
        except Exception as e:  # noqa: BLE001 — report and continue
            rep = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            failures += 1
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rep, f, indent=1)
        if "skipped" in rep:
            print(f"[skip] {tag}: {rep['skipped']}")
        elif "error" in rep:
            print(f"[FAIL] {tag}: {rep['error']}")
        else:
            r = rep["roofline"]
            print(f"[ok]  {tag}: mem {rep['memory']['peak_per_device_gb']:.2f}GB/dev "
                  f"compute {r['compute_s']:.2e}s memory {r['memory_s']:.2e}s "
                  f"coll {r['collective_s']:.2e}s -> {r['bottleneck']} "
                  f"(lower {rep['lower_s']}s compile {rep['compile_s']}s)")
    if failures:
        raise SystemExit(f"{failures} case(s) failed")


if __name__ == "__main__":
    main()
