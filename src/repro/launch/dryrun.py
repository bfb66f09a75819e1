import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first executable statements — jax locks the
device count at first init, and the dry-run (and only the dry-run) needs 512
placeholder CPU devices to build the production meshes.

Per cell this script:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. builds the step function + shardings (launch.steps) with the paper's
     DP remat plan applied,
  3. ``jax.jit(fn, in_shardings, out_shardings).lower(*specs).compile()``,
  4. records memory_analysis / cost_analysis / per-collective byte counts
     parsed from the post-SPMD HLO into a JSON blob for
     benchmarks/roofline.py and EXPERIMENTS.md §Dry-run.

Budget math: the per-device activation budget and chain-node byte sizes in
each record come from the shared sharding-aware accounting
(``launch.plan.plan_inputs`` → ``repro.parallel.sharding``) under the same
rules table the step compiled with — the dry-run carries no byte arithmetic
of its own.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

# HLO text parsing lives in repro.analysis.hlo_text (shared with the
# compiler-truth checkers); the historical underscore names stay as aliases
# for existing callers of the dry-run module.
from repro.analysis.hlo_text import (
    COLLECTIVES as _COLLECTIVES,  # noqa: F401  (re-exported alias)
    DTYPE_BYTES as _DTYPE_BYTES,  # noqa: F401
    SHAPE_RE as _SHAPE_RE,  # noqa: F401
    collective_bytes,
    shape_bytes as _shape_bytes,  # noqa: F401
    split_computations as _split_computations,  # noqa: F401
)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             objective: Optional[str] = None,
             opts: tuple = (),
             keep_hlo: bool = False) -> Dict[str, Any]:
    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.launch.mesh import make_production_mesh, mesh_num_devices
    from repro.launch.steps import build_step, segment_plan

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "full-attention arch at 500k ctx (DESIGN.md §Arch-applicability)"}
    if cfg.encoder_decoder and shape.kind == "decode" and shape.seq_len > 32_768:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": "enc-dec 500k decode inapplicable"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.perf_counter()
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": mesh_num_devices(mesh),
    }
    if opts:
        rec["opts"] = list(opts)
    with jax.sharding.set_mesh(mesh):
        fn, in_sh, out_sh, example = build_step(cfg, shape, mesh, opts=opts)
        sp, plan_res = (segment_plan(cfg, shape, mesh)
                        if shape.kind == "train" else (None, None))
        if sp is not None:
            rec["segment_sizes"] = list(sp.sizes)
            rec["segment_remat"] = [bool(r) for r in sp.remat]
            rec["n_micro"] = sp.n_micro
            rec["plan_feasible"] = bool(plan_res.feasible)
            rec["plan_overhead_T"] = plan_res.overhead if plan_res.feasible else None
            rec["plan_peak_M"] = plan_res.peak_memory if plan_res.feasible else None
            # per-device budget bookkeeping, straight from the shared
            # sharding-aware accounting (launch.plan.plan_inputs →
            # repro.parallel.sharding) — no separate byte math here
            from repro.launch.plan import plan_inputs
            from repro.launch.steps import _dp_shards, _model_shards, _seq_shards
            from repro.parallel.sharding import get_rules

            pi = plan_inputs(
                cfg, shape, _dp_shards(mesh), _seq_shards(mesh, shape),
                _model_shards(mesh), n_micro=sp.n_micro, rules=get_rules(),
            )
            rec["budget_per_device"] = pi.budget
            rec["bytes_interior_per_device"] = pi.bytes_interior
            rec["bytes_boundary_per_device"] = pi.bytes_boundary
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*example)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    rec["lower_s"] = round(t_lower, 2)
    rec["compile_s"] = round(t_compile, 2)

    # Global, scan-aware FLOP/byte totals from the jaxpr (XLA cost_analysis
    # counts while-loop bodies once, so it is unusable for scan-over-layers).
    try:
        from repro.core.jaxpr_graph import jaxpr_totals

        closed = jax.make_jaxpr(fn)(*example)
        tot = jaxpr_totals(closed)
        rec["jaxpr_flops_global"] = tot["flops"]
        rec["jaxpr_bytes_global"] = tot["bytes"]
    except Exception as e:  # pragma: no cover - diagnostics only
        rec["jaxpr_totals_error"] = str(e)

    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
    cost = compiled.cost_analysis()
    if cost:
        c = cost[0] if isinstance(cost, (list, tuple)) else cost
        rec["hlo_flops"] = float(c.get("flops", -1))
        rec["hlo_transcendentals"] = float(c.get("transcendentals", -1))
        rec["hlo_bytes_accessed"] = float(c.get("bytes accessed", -1))
    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["hlo_lines"] = hlo.count("\n")
    if keep_hlo:
        rec["hlo"] = hlo
    rec["status"] = "ok"
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="all (arch × shape) cells")
    ap.add_argument("--out", default=None, help="directory for per-cell JSON")
    ap.add_argument("--objective", default=None,
                    choices=[None, "time_centric", "memory_centric"])
    ap.add_argument("--opts", default="",
                    help="comma-separated hillclimb knobs (mp, ws, …)")
    args = ap.parse_args(argv)
    opts = tuple(o for o in args.opts.split(",") if o)

    from repro.configs import ARCH_IDS, SHAPES

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                tag = f"{arch}|{shape}|{mk}"
                try:
                    rec = run_cell(arch, shape, mk, objective=args.objective,
                                   opts=opts)
                except Exception as e:  # a failure here is a bug in the system
                    rec = {"arch": arch, "shape": shape, "mesh": mk,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    failures += 1
                line = {k: v for k, v in rec.items() if k not in ("hlo", "traceback")}
                print(json.dumps(line), flush=True)
                if rec["status"] == "error":
                    print(rec["traceback"], file=sys.stderr, flush=True)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    suffix = ("__" + "_".join(opts)) if opts else ""
                    fname = f"{arch}__{shape}__{mk}{suffix}.json".replace("/", "_")
                    with open(os.path.join(args.out, fname), "w") as f:
                        json.dump(rec, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
