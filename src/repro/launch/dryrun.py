import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import (jax locks the device
# count on first init).  The dry-run is a CPU-only rehearsal on 512 forced
# host devices, never a chip run.  Everything below is ordinary.

"""Multi-pod dry-run: lower + compile every (arch x shape-cell x mesh).

For each cell this lowers the real step function (train_step for train_4k,
serve_prefill for prefill_32k, serve_decode for decode_32k / long_500k)
against pure ShapeDtypeStruct inputs on the production mesh, compiles it,
and records memory_analysis / cost_analysis / the HLO collective schedule
into results/dryrun/<arch>__<cell>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch deepseek-67b --cell train_4k --mesh single
  python -m repro.launch.dryrun --all            # orchestrates subprocesses
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, cell: str, mesh_kind: str, opt_level: int = 0) -> dict:
    import jax

    from ..configs.base import SHAPE_CELLS, input_specs
    from ..configs.registry import get_arch
    from ..distributed import sharding as shard
    from ..models import registry as M
    from ..roofline.hlo import parse_collectives
    from ..train.optimizer import abstract_opt_state, opt_state_axes
    from ..train.step import make_serve_decode, make_serve_prefill, make_train_step
    from .mesh import make_production_mesh

    cfg = get_arch(arch)
    if opt_level:
        cfg = apply_opt_level(cfg, cell, opt_level)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    from ..distributed import context as mesh_ctx
    mesh_ctx.set_mesh(mesh)
    ns = lambda tree: shard.named(tree, mesh)
    kind = SHAPE_CELLS[cell]["kind"]
    b, s = SHAPE_CELLS[cell]["global_batch"], SHAPE_CELLS[cell]["seq_len"]

    t0 = time.time()
    abs_params = M.abstract_params(cfg)
    p_axes = M.param_axes(cfg)
    p_specs = shard.tree_specs(p_axes, abs_params, mesh)

    if kind == "train":
        batch_abs = input_specs(cfg, cell)
        batch_specs = shard.batch_specs(batch_abs, mesh)
        abs_opt = abstract_opt_state(cfg.optimizer, abs_params)
        o_axes = opt_state_axes(cfg.optimizer, p_axes)
        o_specs = shard.tree_specs(o_axes, abs_opt, mesh)
        step_fn, _ = make_train_step(cfg)
        jitted = jax.jit(
            step_fn,
            in_shardings=(ns(p_specs), ns(o_specs), ns(batch_specs)),
            out_shardings=(ns(p_specs), ns(o_specs), None),
            donate_argnums=(0, 1))
        with mesh:
            lowered = jitted.lower(abs_params, abs_opt, batch_abs)
    elif kind == "prefill":
        batch_abs = input_specs(cfg, cell)
        batch_specs = shard.batch_specs(batch_abs, mesh)
        cache_axes = M.cache_axes(cfg, b, s)
        cache_abs = M.abstract_cache(cfg, b, s)
        c_specs = shard.cache_specs(cfg, cache_axes, cache_abs, mesh)
        step_fn = make_serve_prefill(cfg)
        jitted = jax.jit(step_fn,
                         in_shardings=(ns(p_specs), ns(batch_specs)),
                         out_shardings=(None, ns(c_specs)))
        with mesh:
            lowered = jitted.lower(abs_params, batch_abs)
    else:  # decode
        batch_abs = input_specs(cfg, cell)
        cache_axes = M.cache_axes(cfg, b, s)
        cache_abs = M.abstract_cache(cfg, b, s)
        c_specs = shard.cache_specs(cfg, cache_axes, cache_abs, mesh)
        tok_spec = shard.batch_specs(
            {"token": batch_abs["token"], "pos": batch_abs["pos"]}, mesh)
        step_fn = make_serve_decode(cfg)
        jitted = jax.jit(
            step_fn,
            in_shardings=(ns(p_specs), ns(c_specs),
                          ns(tok_spec)["token"], ns(tok_spec)["pos"]),
            out_shardings=(ns(tok_spec)["token"], None, ns(c_specs)),
            donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(abs_params, cache_abs,
                                   batch_abs["token"], batch_abs["pos"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_d = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_d[f] = int(v)
    cost = compiled.cost_analysis() or {}
    cost_d = {k: float(v) for k, v in cost.items()
              if isinstance(v, (int, float)) and (
                  "flops" in k or "bytes" in k or k in ("utilization",))}

    hlo = compiled.as_text()
    coll = parse_collectives(hlo, pod_size=256)
    # exact per-device dot FLOPs + collective bytes with while-loop trip
    # multipliers (XLA cost_analysis counts loop bodies once — verified)
    from ..roofline.hlo_exact import analyze as hlo_analyze
    from ..roofline.analytic import hbm_bytes_per_device, model_flops
    exact = hlo_analyze(hlo, pod_size=256)
    import gzip
    tag = f"{arch}__{cell}__{mesh_kind}" + (f"__opt{opt_level}" if opt_level else "")
    RESULTS.mkdir(parents=True, exist_ok=True)
    with gzip.open(RESULTS / f"{tag}.hlo.gz", "wt") as fh:
        fh.write(hlo)

    n_dev = mesh.devices.size
    result = dict(
        arch=arch, cell=cell, mesh=mesh_kind, devices=int(n_dev),
        mesh_shape=list(mesh.devices.shape), axes=list(mesh.axis_names),
        kind=kind, global_batch=b, seq_len=s, opt_level=opt_level,
        ok=True, t_lower_s=t_lower, t_compile_s=t_compile,
        memory=mem_d,
        flops_per_device=cost_d.get("flops", 0.0),
        bytes_accessed_per_device=cost_d.get("bytes accessed", 0.0),
        cost_analysis=cost_d,
        collectives=coll,
        hlo_exact=exact,
        analytic_hbm_bytes_per_device=float(
            hbm_bytes_per_device(cfg, cell, n_dev)),
        model_flops=float(model_flops(cfg, cell)),
        model_params=int(cfg.param_count()),
        active_params=int(cfg.active_param_count()),
        hlo_bytes=len(hlo),
    )
    return result


from .optlevels import apply_opt_level  # noqa: E402  (re-export)


def cell_list(only_arch=None, only_cell=None):
    from ..configs.registry import ARCHS
    cells = []
    # cheapest architectures first so results stream in early
    for name, cfg in sorted(ARCHS.items(), key=lambda kv: kv[1].param_count()):
        for cell in cfg.runnable_cells():
            if only_arch and name != only_arch:
                continue
            if only_cell and cell != only_cell:
                continue
            cells.append((name, cell))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--cell")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--opt-level", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        failures = []
        todo = cell_list(args.arch, args.cell)
        meshes = args.meshes.split(",")
        for name, cell in todo:
            for mesh_kind in meshes:
                tag = f"{name}__{cell}__{mesh_kind}"
                if args.opt_level:
                    tag += f"__opt{args.opt_level}"
                out = RESULTS / f"{tag}.json"
                if out.exists() and not args.force:
                    print(f"[skip] {tag}", flush=True)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", name, "--cell", cell, "--mesh", mesh_kind,
                       "--opt-level", str(args.opt_level)]
                print(f"[run ] {tag}", flush=True)
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout,
                                   cwd=str(Path(__file__).resolve().parents[3]),
                                   env={**os.environ,
                                        "PYTHONPATH": "src"})
                dt = time.time() - t0
                if r.returncode != 0:
                    failures.append(tag)
                    err = (r.stderr or "")[-2000:]
                    out.write_text(json.dumps(dict(
                        arch=name, cell=cell, mesh=mesh_kind, ok=False,
                        error=err, opt_level=args.opt_level), indent=1))
                    print(f"[FAIL] {tag} ({dt:.0f}s): {err[-300:]}", flush=True)
                else:
                    print(f"[ ok ] {tag} ({dt:.0f}s)", flush=True)
        print(f"done; {len(failures)} failures: {failures}", flush=True)
        sys.exit(1 if failures else 0)

    assert args.arch and args.cell
    tag = f"{args.arch}__{args.cell}__{args.mesh}"
    if args.opt_level:
        tag += f"__opt{args.opt_level}"
    try:
        result = run_cell(args.arch, args.cell, args.mesh, args.opt_level)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    out = RESULTS / f"{tag}.json"
    out.write_text(json.dumps(result, indent=1))
    print(json.dumps({k: result[k] for k in
                      ("arch", "cell", "mesh", "ok", "t_compile_s")}))


if __name__ == "__main__":
    main()
