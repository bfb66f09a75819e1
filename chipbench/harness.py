"""The benchmark's machinery: find a cell by name, build the program's
training path for it, drive set-up and the measured window, read the
per-layer metrics, and decide ``correct`` against the reference.

Everything that belongs to one cell is data, found by name from
``BENCHMARK.json``: the configuration (``configs/<name>.json``), the traffic
(``traffic/<name>.json``), the limits of the comparison
(``limits/<workload>.json``) and one reader per per-layer metric
(``metrics/<name>.py``, a function ``read(ctx)`` that returns a number or
``None`` when it finds nothing to read).

The program is driven as ``repro.launch.train.main`` drives it: the model
from ``repro.models.build_model``, the remat plan from
``repro.launch.steps.segment_plan`` (or the sqrt(n) segmentation),
parameters made on the device into ``repro.launch.specs.param_shardings``,
and ``repro.train.Trainer``, whose ``run`` takes every step.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import statistics
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference
import devtrace as tracing
import traffic as traffic_gen
import weights

HERE = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the numbers ``correct`` compares, in the order they are printed
CHECKS = ("loss_gap", "grad_gap", "grad_error", "change_gap")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------- cells


def load_cell(root: Path, workload: str) -> SimpleNamespace:
    """The cell named ``workload`` with its configuration, traffic, limits
    and per-layer metrics, all read from files named in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    m = json.loads((root / conf["file"]).read_text())
    tr = json.loads((root / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits_file = root / "chipbench" / "limits" / f"{workload}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.exists() else {}
    e2e = [x for x in bench["end_to_end"] if workload in x.get("workloads", [workload])]
    moved = {x["name"] for x in e2e}
    per_layer = [x for x in bench["per_layer"]
                 if workload in x.get("workloads", [workload] if x["moves"] in moved else [])]
    return SimpleNamespace(name=workload, cell=cell, m=m, traffic=tr, limits=limits,
                           end_to_end=e2e, per_layer=per_layer, root=root)


def chips(n: int) -> List[Any]:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                     "this benchmark never runs on anything else")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def peak_of(kind: str) -> Dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def model_config(m: Dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    own architecture entry with the file's sizes put in."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(m["program_arch"]),
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_head=m["head_dim"], d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"],
    )


# ---------------------------------------------------------------- the feed


class Feed:
    """The batches the Trainer pulls, cycled; every ``next()`` is stamped,
    so successive stamps tile the steps with all their host work."""

    def __init__(self, batches):
        self.batches, self.i, self.stamps = batches, 0, []

    def __iter__(self):
        return self

    def __next__(self):
        with jax.profiler.TraceAnnotation("chipbench.next_batch"):
            self.stamps.append(time.perf_counter())
            b = self.batches[self.i % len(self.batches)]
            self.i += 1
            return b


# ----------------------------------------------------------------- program


class Program:
    """The training path of one cell, built once and driven step by step."""

    def __init__(self, c: SimpleNamespace, seed: int, devices):
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import auto_mesh
        from repro.launch.specs import param_shardings, params_specs
        from repro.launch.steps import segment_plan
        from repro.models import build_model, default_segments
        from repro.optim.adamw import AdamWConfig
        from repro.train import TrainConfig, Trainer

        m, t = c.m, c.traffic
        self.c, self.seed = c, seed
        self.cfg = cfg = model_config(m)
        model = build_model(cfg)
        data, mod = m["mesh"]
        self.mesh = mesh = auto_mesh((data, mod), ("data", "model"), devices=devices)
        shape = ShapeConfig("bench", t["seq_len"], t["batch"], "train")
        self.plan_s = self.plan_peak_bytes = None
        if t["plan"] == "sqrtn":
            sizes, remat = default_segments(cfg.n_layers), None
        else:
            t0 = time.perf_counter()
            sp, res = segment_plan(cfg, shape, mesh, objective=t["plan"])
            self.plan_s = time.perf_counter() - t0
            if sp.n_micro != 1:
                raise ValueError(f"the plan asks for {sp.n_micro} microbatches; "
                                 "the Trainer takes whole batches")
            sizes, remat = sp.sizes, sp.remat
            self.plan_peak_bytes = float(res.peak_memory)
        self.segments = (list(sizes), None if remat is None else list(remat))
        self.key = weights.seed_key(seed)
        self.batches = traffic_gen.batches(t, m["vocab_size"], seed)
        with jax.sharding.set_mesh(mesh):
            specs = params_specs(cfg)
            made = jax.eval_shape(weights.program_params_fn(m), self.key)
            if (jax.tree_util.tree_structure(made) != jax.tree_util.tree_structure(specs)
                    or any(a.shape != b.shape for a, b in zip(
                        jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(specs)))):
                raise ValueError("the program's parameter tree is not the one "
                                 "chipbench/weights.py lays out")
            self.make_params = jax.jit(weights.program_params_fn(m),
                                       out_shardings=param_shardings(cfg, mesh))
            params = self.make_params(self.key)
            loss_fn = lambda p, b: model.loss(p, b, segment_sizes=sizes, segment_remat=remat)
            tc = TrainConfig(total_steps=0, log_every=0, optimizer=AdamWConfig(**t["optimizer"]))
            self.trainer = Trainer(loss_fn, params, tc, mesh=mesh)
            del params
            first = {k: jnp.asarray(v) for k, v in self.batches[0].items()}
            t0 = time.perf_counter()
            self.compiled = self.trainer.lower(first).compile()
            self.compile_s = time.perf_counter() - t0
        self.feed = Feed(self.batches)
        self.losses: List[float] = []
        self.skipped = 0

    def memory(self) -> Dict[str, int]:
        ma = self.compiled.memory_analysis()
        return {"args": ma.argument_size_in_bytes, "temp": ma.temp_size_in_bytes,
                "out": ma.output_size_in_bytes, "alias": ma.alias_size_in_bytes}

    def step(self) -> None:
        """One step through ``Trainer.run``."""
        tr = self.trainer
        tr.cfg = dataclasses.replace(tr.cfg, total_steps=tr.step + 1)
        with jax.profiler.TraceAnnotation("chipbench.trainer_run"), \
                jax.sharding.set_mesh(self.mesh):
            out = tr.run(self.feed)
        self.losses.extend(out["losses"])
        self.skipped = out["skipped"]

    def first_steps(self) -> Dict[str, Any]:
        """Steps 1 to 3 on the first three batches, with what the comparison
        reads: each loss, the first gradient as the optimizer got it (its
        first moment after one step, over 1 - b1; the norm of each leaf, and
        the whole gradient copied to the host, in the reference's layout),
        and the weights' change after the three steps."""
        b1 = self.c.traffic["optimizer"]["b1"]
        with jax.sharding.set_mesh(self.mesh):
            self.step()
            mu = self.trainer.opt_state.mu
            grad = jax.jit(lambda t: weights.leaf_norms(weights.from_program(t)))(mu)
            grad = {k: np.asarray(v, np.float64) / (1 - b1) for k, v in grad.items()}
            first_grad = jax.device_get(jax.jit(lambda t: jax.tree_util.tree_map(
                lambda x: x / (1 - b1), weights.from_program(t)))(mu))
            self.step()
            self.step()
            start = self.make_params(self.key)
            change = jax.jit(lambda p, s: weights.change_norms(
                weights.from_program(p), weights.from_program(s)))(self.trainer.params, start)
            change = {k: np.asarray(v, np.float64) for k, v in change.items()}
            del start
        return {"losses": list(self.losses[:3]), "grad_norms": grad, "change_norms": change,
                "first_grad": first_grad}

    def free(self) -> None:
        """Drop the program's state from the device before the reference."""
        self.trainer = self.compiled = self.feed = self.make_params = None
        gc.collect()
        for a in jax.live_arrays():
            a.delete()


# -------------------------------------------------------------- comparison


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three numbers ``correct`` compares.

    loss_gap: the largest relative gap of the three steps' losses.
    grad_gap / change_gap: over every leaf (one layer's matrix), the gap
    between the program's norm and the reference's, over the larger of the
    reference's norm of that leaf and of the median leaf.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of change_gap.
    grad_error: over every leaf, the norm of the difference between the
    program's first gradient and the reference's (``ref["diff_norms"]``,
    which the reference took against the program's), over the same
    denominator.  A gap of norms is blind to rounding that is as likely up
    as down; this number is not.
    """
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))

    def worst(p: Dict, r: Dict, keep=None) -> float:
        keys = sorted(r)
        rv = np.concatenate([np.ravel(r[k]) for k in keys])
        pv = np.concatenate([np.ravel(p[k]) for k in keys])
        if keep is not None:
            rv, pv = rv[keep], pv[keep]
        den = np.maximum(rv, np.median(rv))
        return float(np.max(np.abs(pv - rv) / den))

    def error(d: Dict, r: Dict) -> float:
        keys = sorted(r)
        rv = np.concatenate([np.ravel(r[k]) for k in keys])
        dv = np.concatenate([np.ravel(d[k]) for k in keys])
        return float(np.max(dv / np.maximum(rv, np.median(rv))))

    raw = np.concatenate([np.ravel(ref["raw_grad_norms"][k]) for k in sorted(ref["raw_grad_norms"])])
    keep = raw >= 1e-3 * np.median(raw)
    return {"loss_gap": float(loss),
            "grad_gap": worst(prog["grad_norms"], ref["grad_norms"]),
            "grad_error": error(ref["diff_norms"], ref["grad_norms"]),
            "change_gap": worst(prog["change_norms"], ref["change_norms"], keep)}


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """``(correct, checks)``: every limited number at or under its limit."""
    checks = {k: {"value": readings[k], "limit": limits.get(k)} for k in CHECKS}
    ok = bool(limits) and all(
        math.isfinite(readings[k]) and readings[k] <= limits[k] for k in limits)
    return ok, checks


# ------------------------------------------------------------------ window


def run_window(prog: Program, seconds: float) -> Dict[str, Any]:
    """Whole steps until ``seconds`` have passed; intervals between the
    feed's stamps, the last one closed when its step returned."""
    feed = prog.feed
    start = len(feed.stamps)
    n0 = len(prog.losses)
    prog.step()
    t_first = feed.stamps[start]
    while time.perf_counter() - t_first < seconds:
        prog.step()
    t_end = time.perf_counter()
    stamps = feed.stamps[start:] + [t_end]
    intervals = [b - a for a, b in zip(stamps, stamps[1:])]
    return {"t_first": t_first, "t_end": t_end, "intervals": intervals,
            "losses": prog.losses[n0:]}


def traced_window(prog: Program, steps: int) -> SimpleNamespace:
    """``steps`` more steps under the profiler, the python tracer off;
    ``kinds`` names the flash kernels among the compiled step's ops."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            with jax.profiler.TraceAnnotation("chipbench.window"):
                for _ in range(steps):
                    prog.step()
        devices, spans = tracing.load(d)
    lo, hi = tracing.window(spans, "chipbench.window")
    kinds = tracing.kernel_kinds(prog.compiled.as_text())
    return SimpleNamespace(devices=devices, spans=spans, lo=lo, hi=hi, kinds=kinds)


def read_metric(name: str, ctx: SimpleNamespace) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# --------------------------------------------------------------------- run


def run(c: SimpleNamespace, seed: int, seconds: float, traced: bool, t_start: float,
        log=print) -> Dict[str, Any]:
    """One run of one cell: the result line as a dict."""
    devices = chips(c.cell["chips"])
    compiles: List[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: compiles.append(name) if name == COMPILE_EVENT else None)
    t_build = time.perf_counter()
    prog = Program(c, seed, devices)
    t_steps = time.perf_counter()
    first = prog.first_steps()
    before = len(compiles)
    win = run_window(prog, seconds)
    setup_s = win["t_first"] - t_start
    in_window = len(compiles) - before
    t = c.traffic
    n = len(win["intervals"])
    tokens_per_s = n * t["batch"] * t["seq_len"] / (win["t_end"] - win["t_first"])
    mem = prog.memory()
    ctx = SimpleNamespace(m=c.m, traffic=t, cell=c.cell, peak=peak_of(devices[0].device_kind),
                          chips=len(devices), mesh=c.m["mesh"], tokens_per_s=tokens_per_s,
                          plan_s=prog.plan_s, plan_peak_bytes=prog.plan_peak_bytes,
                          memory=mem, compile_s=prog.compile_s, trace=None)
    if traced:
        ctx.trace = traced_window(prog, t["trace_steps"])
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    losses = first["losses"] + win["losses"]
    failed = prog.skipped + sum(not math.isfinite(x) for x in win["losses"])
    segments = prog.segments
    prog.free()
    ref = reference.run(c.m, t["optimizer"], weights.seed_key(seed), prog.batches,
                        against=first.pop("first_grad"))
    readings = gaps(first, ref)
    correct, checks = judge(readings, c.limits)
    correct = correct and failed == 0 and all(math.isfinite(x) for x in losses)

    if traced:
        metrics = {}
        for spec in c.per_layer:
            v = read_metric(spec["name"], ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        values = {"tokens_per_s": tokens_per_s, "setup_s": setup_s}
        metrics = {x["name"]: {"value": values[x["name"]], "unit": x["unit"]}
                   for x in c.end_to_end}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": n, "failed": failed, "metrics": metrics,
              "device": device}
    if traced:
        tr_ = ctx.trace
        busy = [tracing.busy_ns(ev, tr_.lo, tr_.hi) for ev in tr_.devices.values()]
        device["busy_s"] = sum(busy) / max(1, len(busy)) * 1e-9
        device["window_s"] = (tr_.hi - tr_.lo) * 1e-9
        ops = next(iter(tr_.devices.values()), [])
        result["breakdown"] = {"device_ops": tracing.top_ops(ops, tr_.lo, tr_.hi, tr_.kinds),
                               "idle_gaps": tracing.idle_gaps(ops, tr_.spans, tr_.lo, tr_.hi)}
    log(json.dumps({"workload": c.name, "seed": seed, "segments": segments,
                    "start_s": t_build - t_start, "build_s": t_steps - t_build,
                    "first_steps_s": win["t_first"] - t_steps,
                    "compile_s": prog.compile_s, "plan_s": prog.plan_s,
                    "memory_analysis": mem, "steps": n, "compiles_in_setup": before,
                    "compiles_in_window": in_window,
                    "step_s_median": statistics.median(win["intervals"]),
                    "step_s": win["intervals"],
                    "first_losses": first["losses"], "reference_losses": ref["losses"]}))
    result["checks"] = checks
    return result
