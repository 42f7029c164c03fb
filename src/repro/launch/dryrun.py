import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count at first
init, and only the dry-run may see 512 placeholder devices (tests and
benches see 1).

Per cell this driver:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. resolves the arch's sharding rules (+ per-cell fixes, e.g. batch=1 on
     long_500k cannot shard the data axes),
  3. lowers the cell's step function against ShapeDtypeStruct stand-ins
     (params, optimizer state, caches — zero bytes allocated),
  4. compiles, prints ``memory_analysis()`` (proves it fits) and
     ``cost_analysis()``,
  5. runs the trip-corrected HLO analysis and the three-term roofline, and
  6. writes everything to ``results/dryrun/<cell>.json`` for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3_8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]   # subprocess per cell
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def build_cell(arch: str, shape_name: str, multi_pod: bool, rule_overrides=None):
    """Returns (lowered, meta) for one cell."""
    from repro.config import SHAPES
    from repro.configs import get_run
    from repro.launch.mesh import make_production_mesh, mesh_config
    from repro.models import base as mbase
    from repro.models.model import build_model, input_specs
    from repro.optim import build_optimizer
    from repro.serve.steps import make_decode_step, make_prefill_step
    from repro.sharding.rules import Dist, Rules
    from repro.train.steps import make_train_step

    run = get_run(arch, shape_name, mesh_config(multi_pod=multi_pod))
    cfg, shape = run.model, run.shape
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_data = 1
    for ax in mesh.axis_names:
        if ax != "model":
            n_data *= mesh.shape[ax]

    rules = Rules(mesh_axes=tuple(mesh.axis_names)).with_overrides(cfg.sharding_overrides)
    if shape.global_batch % n_data:
        # batch can't shard the data axes (long_500k: B=1) — replicate it.
        rules = rules.with_overrides({"batch": None, "cache_batch": None})
    if rule_overrides:
        rules = rules.with_overrides(rule_overrides)
    dist = Dist.for_mesh(mesh, rules)

    model = build_model(cfg)
    param_specs = model.param_specs()
    params = mbase.shape_structs(param_specs, rules, mesh)
    inputs = input_specs(cfg, shape, mesh, rules)

    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "params_b": model.n_params() / 1e9,
        "kind": shape.kind,
    }

    with mesh:
        if shape.kind == "train":
            # donate params + opt state: the update aliases them in place
            # (without donation the optimizer temporarily doubles the f32
            # param + grad buffers — the difference between grok fitting
            # 16 GB and not).
            step_fn, opt = make_train_step(model, run, dist)
            opt_specs = opt.state_specs(param_specs)
            opt_state = mbase.shape_structs(opt_specs, rules, mesh)
            step_ct = jax.ShapeDtypeStruct((), jnp.int32,
                                           sharding=NamedSharding(mesh, P()))
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params, opt_state, step_ct, inputs
            )
        elif shape.kind == "prefill":
            step_fn = make_prefill_step(model, run, dist)
            cache = model.cache_structs(shape.global_batch, run.max_cache_len, rules, mesh)
            lowered = jax.jit(step_fn, donate_argnums=(1,)).lower(params, cache, inputs)
        else:  # decode
            step_fn = make_decode_step(model, run, dist)
            cache = model.cache_structs(shape.global_batch, run.max_cache_len, rules, mesh)
            lowered = jax.jit(step_fn, donate_argnums=(1,)).lower(
                params, cache, inputs["tokens"], inputs["cache_pos"]
            )
    return lowered, meta, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path) -> dict:
    from repro.analysis.hlo import analyze_module
    from repro.analysis.roofline import roofline_terms

    t0 = time.time()
    lowered, meta, mesh, cfg, shape = build_cell(arch, shape_name, multi_pod)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(f"[{arch} x {shape_name}] memory_analysis: {mem}")
    cost = compiled.cost_analysis()
    print(f"[{arch} x {shape_name}] cost_analysis flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e} (while bodies counted once)")

    hlo = compiled.as_text()
    hlo_dir = out_dir / "hlo"
    hlo_dir.mkdir(parents=True, exist_ok=True)
    import gzip
    tag = "multipod" if multi_pod else "pod"
    with gzip.open(hlo_dir / f"{arch}__{shape_name}__{tag}.hlo.gz", "wt") as f:
        f.write(hlo)
    stats = analyze_module(hlo, mesh.size)
    roof = roofline_terms(
        cfg, shape,
        per_device_flops=stats.flops,
        per_device_bytes=stats.traffic_bytes,
        per_device_coll_bytes=stats.coll_operand_bytes,
        n_chips=mesh.size,
    )

    arg_gb = mem.argument_size_in_bytes / 1e9
    temp_gb = mem.temp_size_in_bytes / 1e9
    fits = (arg_gb + temp_gb) < 16.0
    result = {
        **meta,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_gb": arg_gb,
            "temp_gb": temp_gb,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "fits_16gb": fits,
        },
        "cost_analysis": {
            "flops_body_once": cost.get("flops", 0.0),
            "bytes_body_once": cost.get("bytes accessed", 0.0),
        },
        "hlo_stats": stats.to_json(),
        "roofline": roof.to_json(),
        "status": "ok",
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    cell = f"{arch}__{shape_name}__{'multipod' if multi_pod else 'pod'}.json"
    (out_dir / cell).write_text(json.dumps(result, indent=2))
    print(f"[{arch} x {shape_name}] OK  lower {t_lower:.0f}s compile {t_compile:.0f}s "
          f"args {arg_gb:.2f}GB temp {temp_gb:.2f}GB fits16={fits} "
          f"dominant={roof.dominant} "
          f"terms(c/m/x)=({roof.compute_s:.3e},{roof.memory_s:.3e},{roof.collective_s:.3e})s")
    return result


def all_cells():
    from repro.config import SHAPES
    from repro.configs import ARCH_IDS, get_config, shape_applicable

    for arch in ARCH_IDS:
        if arch == "paper_sfa":
            continue
        cfg = get_config(arch)
        for shape_name, shape in SHAPES.items():
            ok, why = shape_applicable(cfg, shape)
            yield arch, shape_name, ok, why


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.all:
        failures = []
        for arch, shape_name, ok, why in all_cells():
            tag = "multipod" if args.multi_pod else "pod"
            cell_file = out_dir / f"{arch}__{shape_name}__{tag}.json"
            if not ok:
                out_dir.mkdir(parents=True, exist_ok=True)
                cell_file.write_text(json.dumps(
                    {"arch": arch, "shape": shape_name, "status": "skipped",
                     "reason": why}, indent=2))
                print(f"[{arch} x {shape_name}] SKIP: {why}")
                continue
            if cell_file.exists() and json.loads(cell_file.read_text()).get("status") == "ok":
                print(f"[{arch} x {shape_name}] cached")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--out", str(out_dir)]
            if args.multi_pod:
                cmd.append("--multi-pod")
            r = subprocess.run(cmd, timeout=args.timeout)
            if r.returncode != 0:
                failures.append((arch, shape_name))
                cell_file.write_text(json.dumps(
                    {"arch": arch, "shape": shape_name, "status": "failed"}, indent=2))
        print(f"\n=== dry-run sweep done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    try:
        run_cell(args.arch, args.shape, args.multi_pod, out_dir)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
