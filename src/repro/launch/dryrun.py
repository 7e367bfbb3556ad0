import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede every other import (jax locks the device
count at first init). For each cell we:

  1. build the production mesh (16x16 single pod / 2x16x16 multi-pod),
  2. build the step function (train_step / prefill_step / decode_step per
     the shape's kind) with the arch's logical-axis rules installed,
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(**abstract)``
     then ``.compile()``,
  4. record memory_analysis / cost_analysis / HLO-derived roofline terms to
     reports/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from ..configs import SHAPES, get_config, list_archs, shape_applicable  # noqa: E402
from ..models.model import Model, count_params, count_active_params  # noqa: E402
from ..models.partitioning import logical_axis_rules  # noqa: E402
from ..optim.adamw import AdamW  # noqa: E402
from ..roofline.analysis import roofline_terms  # noqa: E402
from ..roofline.model_flops import model_bytes, model_flops  # noqa: E402
from ..train.serve_step import make_decode_step, make_prefill_step  # noqa: E402
from ..train.train_step import make_train_step  # noqa: E402
from . import sharding as shd  # noqa: E402
from . import specs as specs_mod  # noqa: E402
from .mesh import auto_mesh, make_production_mesh  # noqa: E402

REPORT_DIR = Path(__file__).resolve().parents[3] / "reports" / "dryrun"


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               attn_chunk: int = 1024, remat: str = "dots_no_batch",
               extra_rules=None, save_hlo: bool = False,
               grad_rs: bool = True, microbatches: int = 1,
               mesh_override=None):
    """mesh_override: (shape_tuple, axis_names) for elastic/degraded meshes
    (e.g. ((8, 16), ("data", "model")) = half the DP hosts survived) — the
    compile-success proof behind fault_tolerance.plan_elastic_restart."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if mesh_override is not None:
        mesh_name = "x".join(str(s) for s in mesh_override[0])
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped(full-attention long-context)"}
    if mesh_override is not None:
        mshape, maxes = mesh_override
        n = 1
        for s in mshape:
            n *= s
        mesh = auto_mesh(mshape, maxes, jax.devices()[:n])
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    optimizer = AdamW(learning_rate=3e-4)
    seq_for_rules = shape.seq_len if shape.kind != "decode" else None
    rules = shd.logical_rules(cfg, mesh, batch_size=shape.global_batch,
                              seq_len=seq_for_rules)
    if extra_rules:
        rules.update(extra_rules)

    t0 = time.time()
    with logical_axis_rules(mesh, rules):
        params_spec = shd.param_specs(cfg, model.abstract_params(), mesh)
        params_sh = shd.as_named(mesh, params_spec)
        bspec = shd.batch_specs(cfg, shape, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(mesh, P())

        if shape.kind == "train":
            step = make_train_step(model, optimizer, remat=remat,
                                   attn_chunk=attn_chunk,
                                   microbatches=microbatches,
                                   grad_shardings=params_sh if grad_rs else None)
            params, opt_state, batch = specs_mod.train_abstract(
                model, shape, optimizer)
            opt_sh = jax.tree.map(
                lambda s: s, type(opt_state)(
                    repl, params_sh, jax.tree.map(lambda x: x, params_sh)))
            batch_sh = {k: NamedSharding(mesh, v) for k, v in bspec.items()}
            in_sh = (params_sh, opt_sh, batch_sh)
            out_sh = (params_sh, opt_sh, None)
            args = (params, opt_state, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(model, attn_chunk=attn_chunk)
            params, batch = specs_mod.prefill_abstract(model, shape)
            cache_abs = jax.eval_shape(
                lambda p, b: step(p, b)[1], params, batch)
            cache_spec = shd.cache_specs(cfg, cache_abs, mesh,
                                         shape.global_batch)
            batch_sh = {k: NamedSharding(mesh, v) for k, v in bspec.items()}
            in_sh = (params_sh, batch_sh)
            out_sh = (None, shd.as_named(mesh, cache_spec))
            args = (params, batch)
        else:  # decode
            step = make_decode_step(model)
            params, cache, token, pos = specs_mod.decode_abstract(model, shape)
            cache_spec = shd.cache_specs(cfg, cache, mesh, shape.global_batch)
            cache_sh = shd.as_named(mesh, cache_spec)
            b_axes = rules["batch"]
            tok_sh = NamedSharding(mesh, P(b_axes))
            in_sh = (params_sh, cache_sh, tok_sh, repl)
            out_sh = (None, cache_sh)
            args = (params, cache, token, pos)

        # Donation mirrors deployment: params/opt (train) and cache (decode)
        # are updated in place, halving their memory footprint.
        donate = {"train": (0, 1), "prefill": (), "decode": (1,)}[shape.kind]
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    params_n = count_params(model.abstract_params())
    mf = model_flops(cfg, shape, model.abstract_params())
    mb = model_bytes(cfg, shape, model.abstract_params())
    mem_per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    report = roofline_terms(
        arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_chips=mesh.size, hlo_text=hlo, cost=cost,
        memory_per_device=mem_per_dev, model_flops_global=mf,
        model_bytes_global=mb)
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "n_chips": mesh.size,
        "compile_seconds": round(compile_s, 1),
        "param_count": params_n,
        "active_param_count": count_active_params(cfg, model.abstract_params()),
        "model_flops_global": mf,
        "model_bytes_global": mb,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": mem_per_dev,
        },
        "cost_analysis": {"flops": cost.get("flops", 0.0),
                          "bytes_accessed": cost.get("bytes accessed", 0.0)},
        "hlo_flops_per_chip": report.hlo_flops,
        "hlo_bytes_per_chip": report.hlo_bytes,
        "collective_bytes_per_chip": report.collective_bytes,
        "collective_breakdown": report.collective_breakdown,
        "terms": {"compute_s": report.t_compute, "memory_s": report.t_memory,
                  "collective_s": report.t_collective},
        "bottleneck": report.bottleneck,
        "useful_ratio": report.useful_ratio,
        "roofline_fraction": report.roofline_fraction,
    }
    if save_hlo:
        out["hlo_path"] = str(REPORT_DIR / f"{arch}__{shape_name}__{mesh_name}.hlo")
        Path(out["hlo_path"]).write_text(hlo)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, **kw):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    try:
        out = build_cell(arch, shape_name, multi_pod, **kw)
    except Exception as e:  # a failing cell is a bug we must surface
        out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": f"FAILED: {type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    path = REPORT_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
    path.write_text(json.dumps(out, indent=1, default=float))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="dots_no_batch")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    for arch, shape in cells:
        for mp in meshes:
            t0 = time.time()
            out = run_cell(arch, shape, mp, remat=args.remat,
                           attn_chunk=args.attn_chunk)
            status = out["status"]
            extra = ""
            if status == "ok":
                extra = (f" C={out['terms']['compute_s']:.2e} "
                         f"M={out['terms']['memory_s']:.2e} "
                         f"X={out['terms']['collective_s']:.2e} "
                         f"{out['bottleneck']:9s} "
                         f"rf={out['roofline_fraction']:.3f} "
                         f"mem/dev={out['memory']['per_device_total']/2**30:.2f}GiB")
            print(f"[{time.time()-t0:7.1f}s] {arch:20s} {shape:12s} "
                  f"{'2x16x16' if mp else '16x16':8s} {status[:60]:60s}{extra}",
                  flush=True)


if __name__ == "__main__":
    main()
