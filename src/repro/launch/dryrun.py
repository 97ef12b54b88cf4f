import os
# the dry run compiles for 512 placeholder CPU devices; it never takes
# a TPU this host may have
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step, in_shardings=…).lower(**ShapeDtypeStructs).compile()``
must succeed on the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh
for every assigned architecture × input shape.  The compiled artifact
yields ``memory_analysis()`` (fits-per-device proof) and
``cost_analysis()`` + the SPMD HLO (roofline terms, §Roofline).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from functools import partial
from pathlib import Path


def build_lowered(arch: str, shape_name: str, mesh_kind: str,
                  overrides=None):
    """Build and lower the cell's step.  Imports happen here, after the
    XLA device-count env var is set."""
    import jax

    from repro.configs import get_config
    from repro.configs.shapes import SHAPES, cell_applicable, input_specs
    from repro.distributed.mesh import plan_from_mesh
    from repro.distributed.sharding import (
        batch_shardings,
        param_shardings,
        state_shardings,
    )
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import Model, init_params
    from repro.optim import adamw, cosine_warmup
    from repro.runtime.train_loop import build_train_step, init_train_state

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(cfg, shape)
    if not ok:
        return None, None, None, reason

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    plan = plan_from_mesh(mesh)
    opts = dict(attn_chunk=1024, loss_chunk=512, remat=True)
    if overrides:
        opts.update(overrides)
    accum_override = opts.pop("accum_steps", None)
    aligned_decode = opts.pop("aligned_decode", False)
    param_mode = opts.pop("param_mode", "fsdp")
    model = Model(cfg, plan=plan, **opts)
    specs = input_specs(cfg, shape)
    if aligned_decode and "pos" in specs:
        # continuous-batching variant: one shared decode position
        specs["pos"] = jax.ShapeDtypeStruct((), specs["pos"].dtype)

    params_shapes = jax.eval_shape(
        partial(init_params, cfg), jax.random.PRNGKey(0))
    param_sh = param_shardings(cfg, plan, params_shapes,
                               drop_data=(param_mode == "tp"))

    if shape.kind == "train":
        opt = adamw(cosine_warmup(3e-4, 2000, 100_000))
        state_shapes = jax.eval_shape(
            partial(init_train_state, model, opt), jax.random.PRNGKey(0))
        state_sh = param_shardings(cfg, plan, state_shapes)
        batch_sh = batch_shardings(cfg, plan, specs)
        # grad accumulation keeps per-microbatch activations ≈ 2 seqs per
        # device live (94-layer models would otherwise hold the full
        # global batch's layer carries for backward)
        b_loc = shape.global_batch // plan.dp_size
        if accum_override is not None:
            accum = accum_override
        elif cfg.param_count() > 5e10:
            accum = max(1, b_loc)        # micro-batch 1/device: giants
        else:
            accum = max(1, b_loc // 2)   # micro-batch 2/device
        grad_sh = None
        if accum > 1 and "pod" in mesh.axis_names:
            grad_sh = param_shardings(cfg, plan, params_shapes, zero1=True)
        step = build_train_step(model, opt, accum_steps=accum,
                                grad_shardings=grad_sh)
        lowered = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            donate_argnums=(0,),
        ).lower(state_shapes, specs)
        return lowered, mesh, cfg, None

    if shape.kind == "prefill":
        batch_sh = batch_shardings(cfg, plan, specs)

        def prefill_step(params, inputs):
            return model.prefill(params, inputs["tokens"],
                                 inputs.get("frontend_embed"))

        lowered = jax.jit(
            prefill_step,
            in_shardings=(param_sh, batch_sh),
        ).lower(params_shapes, specs)
        return lowered, mesh, cfg, None

    # decode
    cache_specs = specs["cache"]
    cache_sh = state_shardings(cfg, plan, cache_specs)
    pos_spec = specs["pos"]
    tok_sh = batch_shardings(cfg, plan, {"tokens": specs["tokens"],
                                         "pos": pos_spec})

    def decode(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    lowered = jax.jit(
        decode,
        in_shardings=(param_sh, cache_sh, tok_sh["tokens"],
                      tok_sh["pos"]),
        donate_argnums=(1,),
    ).lower(params_shapes, cache_specs, specs["tokens"], specs["pos"])
    return lowered, mesh, cfg, None


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             overrides=None, tag: str = "") -> dict:
    from repro.configs.shapes import SHAPES
    from repro.launch.hlo_costs import analyze_hlo
    from repro.launch.roofline import (
        RooflineReport,
        extract_cost,
        extract_memory,
        model_flops_for,
    )

    t0 = time.perf_counter()
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "status": "ok"}
    lowered, mesh, cfg, skip_reason = build_lowered(
        arch, shape_name, mesh_kind, overrides)
    if lowered is None:
        record["status"] = "skip"
        record["reason"] = skip_reason
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{arch}_{shape_name}_{mesh_kind}.json").write_text(
            json.dumps(record, indent=2))
        print(f"SKIP {arch} × {shape_name} × {mesh_kind}: {skip_reason}")
        return record
    t_lower = time.perf_counter() - t0

    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    print(f"memory_analysis: {mem}")        # proves it fits
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # some jax/XLA versions return
        cost = cost[0] if cost else {}    # one dict per program
    print(f"cost_analysis (xla, while-body-once, per-device): "
          f"flops={cost.get('flops', 0.0):.3e} "
          f"bytes={cost.get('bytes accessed', 0.0):.3e}")

    chips = 1
    for v in mesh.shape.values():
        chips *= v
    # trip-count-aware accounting over the SPMD HLO (per-device → ×chips)
    hlo = compiled.as_text()
    hcost = analyze_hlo(hlo)
    shape = SHAPES[shape_name]
    report = RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        hlo_flops=hcost.flops * chips,
        hlo_bytes=hcost.bytes_hbm_model * chips,
        hlo_bytes_raw=hcost.bytes_accessed * chips,
        bytes_vmem_tagged=hcost.bytes_vmem_tagged * chips,
        coll_bytes=hcost.coll_bytes * chips,
        coll_by_op={k: v * chips for k, v in
                    hcost.coll_bytes_by_op.items()},
        model_flops=model_flops_for(cfg, shape, shape.kind),
        bytes_per_device=extract_memory(compiled),
    )
    record.update(report.to_dict())
    record["coll_counts"] = hcost.coll_count_by_op
    xla_flops, xla_bytes = extract_cost(compiled)
    record["xla_flops_per_device_body_once"] = xla_flops
    record["xla_bytes_per_device_body_once"] = xla_bytes
    record["hlo_bytes_len"] = len(hlo)
    record["lower_s"] = round(t_lower, 1)
    record["compile_s"] = round(t_compile, 1)
    if tag:
        record["tag"] = tag

    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}_{shape_name}_{mesh_kind}" + (f"_{tag}" if tag else "")
    (out_dir / f"{name}.json").write_text(json.dumps(record, indent=2))
    print(f"OK {arch} × {shape_name} × {mesh_kind}: "
          f"compute={report.t_compute:.4f}s memory={report.t_memory:.4f}s "
          f"collective={report.t_collective:.4f}s "
          f"bottleneck={report.bottleneck} "
          f"roofline={report.roofline_fraction:.3f} "
          f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) cell in subprocesses")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="", help="variant tag for §Perf runs")
    ap.add_argument("--override", default="",
                    help="JSON dict of Model kwargs (perf experiments)")
    args = ap.parse_args(argv)
    out_dir = Path(args.out)

    if args.all:
        from repro.configs import ASSIGNED_ARCHS
        from repro.configs.shapes import SHAPES

        meshes = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])
        failures = []
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                for mesh in meshes:
                    dest = out_dir / f"{arch}_{shape}_{mesh}.json"
                    if dest.exists():
                        print(f"cached {dest}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--mesh", mesh, "--out", str(out_dir)]
                    # the child inherits this module's JAX_PLATFORMS=cpu
                    r = subprocess.run(cmd)
                    if r.returncode != 0:
                        failures.append((arch, shape, mesh))
        if failures:
            print(f"FAILED cells: {failures}")
            return 1
        print("all cells passed")
        return 0

    overrides = json.loads(args.override) if args.override else None
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    try:
        for mesh in meshes:
            run_cell(args.arch, args.shape, mesh, out_dir,
                     overrides=overrides, tag=args.tag)
    except Exception:
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
