"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the full production stack end to end on whatever devices the host has:
config → DP remat plan (the unified pipeline: chain carrier → Planner →
segment lowering) → sharded train step → fault-tolerant loop
(checkpoint/restart, NaN guard, straggler hooks) over the synthetic
pipeline.  The mesh is ("data", "model") over this host's devices;
parameters are initialised directly into their shardings
(``launch.specs.param_shardings``), so no device holds more than its share.

``--layers N`` is the depth cut: it replaces ``n_layers`` and nothing else,
so every width stays the published one.  ``--reduced`` is the tiny CPU
config of the same family.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import param_shardings
from repro.launch.steps import segment_plan
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train import TrainConfig, Trainer


def main(argv=None):
    """Train; returns the :meth:`Trainer.run` result plus ``"config"``,
    ``"plan"`` (None under ``--no-plan``) and the closed ``"trainer"``."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny config of the same family (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut: replace n_layers, keep every width")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-axis", type=int, default=1,
                    help="size of the mesh's 'model' axis")
    ap.add_argument("--devices", type=int, default=None,
                    help="use this host's first N devices (default: all)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--plan-cache-dir", default=None,
                    help="on-disk recomputation-plan cache (restart = lookup)")
    ap.add_argument("--objective", default="time_centric",
                    choices=["time_centric", "memory_centric"])
    ap.add_argument("--no-plan", action="store_true",
                    help="disable the DP remat plan (vanilla remat fallback)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    model = build_model(cfg)
    mesh = make_host_mesh(model=args.model_axis, n_devices=args.devices)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    print(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}x{cfg.head_dim} kv={cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={cfg.num_params()} "
          f"batch={args.batch} seq={args.seq} mesh={dict(mesh.shape)}")

    if args.plan_cache_dir:
        from repro.core.plan_cache import set_default_cache_dir

        set_default_cache_dir(args.plan_cache_dir)

    segment_sizes = segment_remat = None
    plan = None
    if not args.no_plan:
        sp, res = segment_plan(cfg, shape, mesh, objective=args.objective)
        if sp is not None:
            remat_units = sum(s for s, r in zip(sp.sizes, sp.remat) if r)
            plan = {"segments": list(sp.sizes), "remat_units": remat_units,
                    "n_micro": sp.n_micro, "feasible": bool(res.feasible),
                    "peak_bytes": float(res.peak_memory),
                    "budget_bytes": float(sp.budget)}
            print(f"plan: {sp.n_segments} segments {list(sp.sizes)}, remat "
                  f"{remat_units}/{sum(sp.sizes)} units, micro={sp.n_micro}, "
                  f"feasible={res.feasible}, activation peak "
                  f"{res.peak_memory:.0f} of budget {sp.budget:.0f} B/device")
            if sp.n_micro > 1:
                # the Trainer has no gradient accumulation: running the whole
                # batch would exceed the budget the plan was made for
                raise ValueError(
                    f"the plan needs n_micro={sp.n_micro} microbatches to fit "
                    f"batch {args.batch} x seq {args.seq}; the Trainer does "
                    "not accumulate gradients — lower --batch"
                )
            segment_sizes, segment_remat = sp.sizes, sp.remat

    with jax.sharding.set_mesh(mesh):
        params = jax.jit(model.init, out_shardings=param_shardings(cfg, mesh))(
            jax.random.PRNGKey(0)
        )

    def loss_fn(p, batch):
        return model.loss(p, batch, segment_sizes=segment_sizes,
                          segment_remat=segment_remat)

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch
    ))
    tc = TrainConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        plan_cache_dir=args.plan_cache_dir,
        log_every=max(1, args.steps // 20),
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                              total_steps=args.steps),
    )
    with jax.sharding.set_mesh(mesh):
        tr = Trainer(loss_fn, params, tc, mesh=mesh)
        del params  # the Trainer holds its own copy
        if tr.maybe_restore():
            print(f"restored from step {tr.step}")
        out = tr.run(iter(data))
        tr.close()
    print(f"done: step={out['step']} final_loss={out['final_loss']:.4f} "
          f"skipped={out['skipped']} stragglers={out['straggler_steps']}")
    return dict(out, config=cfg, plan=plan, trainer=tr)


if __name__ == "__main__":
    main()
