"""Fault-tolerant training loop.

Responsibilities (each one individually testable — see tests/test_train_loop.py):

* build the jitted ``train_step`` with donated params/opt-state, the
  recomputation plan (the paper's technique) applied via ``segment_sizes``,
  and optional int8 error-feedback gradient compression (the numerical twin
  of the cross-pod hierarchical all-reduce);
* **NaN guard** — a non-finite loss or grad-norm skips the parameter update
  (params pass through unchanged) and increments a skip counter; the run
  never poisons its weights;
* **checkpoint/restart** — async committed checkpoints every
  ``ckpt_every`` steps; on start, the loop resumes from the latest committed
  step automatically (crash-restart = rerun the same command);
* **straggler mitigation** — per-step wall-times feed an EWMA; steps slower
  than ``straggler_factor``× the EWMA are counted and surfaced through
  ``on_straggler`` (on a real pod this hook re-dispatches that host's data
  slice and flags the host for replacement; in tests it is observed
  directly);
* **elastic re-mesh** — ``Trainer.remesh(new_mesh)`` re-jits the step and
  reshard-restores the live state onto the new mesh via the mesh-agnostic
  checkpoint format;
* **plan cache** — ``plan_cache_dir`` attaches the on-disk recomputation-plan
  store (core.plan_cache): crash-restarts and elastic re-meshes recover their
  DP remat segmentation as a content-addressed lookup instead of a re-solve.
  Planning itself goes through the unified pipeline (``core.lowering``):
  the launchers hand this loop a loss whose remat segmentation is the
  ``"segment"`` lowering of a Planner ExecutionPlan on the unit chain;
* **sharded planned steps** — ``plan_budget`` routes the loss through
  ``repro.plan_function(loss_fn, budget, mesh=..., in_shardings=...)``: the
  Trainer's mesh and input shardings flow into the traced carrier, the DP
  budgets **per-device** activation bytes, and the planned twin keeps the
  caller's shardings (pjit-composable).  ``in_shardings`` is then the
  2-tuple ``(param_shardings, batch_shardings)`` matching the loss args;
* **tracing** — inside the jitted step the loss runs under
  ``jax.named_scope("model")`` (so its ops carry ``jvp(model)`` and
  ``transpose(jvp(model))`` in their op_name metadata, and recomputed ops
  JAX's ``rematted_computation``), the AdamW update and NaN guard under
  ``"optimizer"`` and the int8 round trip under ``"grad_compression"``.
  Each step of :meth:`Trainer.run` is a
  ``jax.profiler.StepTraceAnnotation("repro.train.step")`` holding the host
  spans ``repro.train.next_batch``, ``put_batch``, ``dispatch``, ``sync``
  and ``checkpoint``; all of it costs nothing measurable with no profiler
  attached.  ``run`` returns per step ``step_seconds`` (fetch to sync) and
  ``input_seconds`` (fetch and host-to-device copy), and ``compiles``, the
  step executables built or loaded from the persistent cache during the
  run.  ``straggler_steps`` and the ``log_every`` line are for the
  operator.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import AsyncCheckpointer, latest_step, restore
from repro.optim import adamw
from repro.optim.compression import (
    init_error_feedback,
    quantize_roundtrip_with_feedback,
)

#: JAX's event around every backend compile or persistent-cache load.  One
#: listener, registered at import, serves every Trainer: ``Trainer.run``
#: counts the events that fire inside its own dispatches.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0


def _count_compile(event: str, duration: float, **kwargs: Any) -> None:
    global _compiles
    if event == _COMPILE_EVENT:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    ewma_alpha: float = 0.1
    compress_grads: bool = False
    # On-disk recomputation-plan cache (core.plan_cache): a restarted or
    # re-meshed job re-plans its remat segmentation from the store instead of
    # re-running the DP.  None keeps the cache in-memory only.
    plan_cache_dir: Optional[str] = None
    # Per-device activation-byte budget for the DP recomputation plan: when
    # set, the step's value_and_grad is ``repro.plan_function(loss_fn,
    # plan_budget, mesh=..., in_shardings=...)`` — the Trainer's mesh and
    # input shardings flow into the traced carrier, so the plan budgets
    # per-device bytes of the *sharded* step.  None keeps vanilla
    # jax.value_and_grad (losses whose remat the launchers already planned
    # via segment_sizes stay on that path).
    plan_budget: Optional[float] = None
    plan_backend: str = "auto"
    optimizer: adamw.AdamWConfig = dataclasses.field(
        default_factory=adamw.AdamWConfig
    )


class Trainer:
    def __init__(
        self,
        loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
        params: Any,
        cfg: TrainConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        in_shardings: Any = None,
        donate: bool = True,
    ):
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.in_shardings = in_shardings
        if cfg.plan_cache_dir:
            from repro.core.plan_cache import set_default_cache_dir

            set_default_cache_dir(cfg.plan_cache_dir)
        # Private copy: the jitted step donates params/opt-state buffers, and
        # donating the *caller's* arrays would delete them under the caller
        # (breaks restart-from-same-init and interactive use).
        self.params = jax.tree_util.tree_map(
            lambda x: jnp.array(x, copy=True), params
        )
        self.opt_state = adamw.init(params)
        self.err_fb = init_error_feedback(params) if cfg.compress_grads else None
        self.step = 0
        self.skipped = 0
        self.straggler_steps = 0
        self._ewma: Optional[float] = None
        self.on_straggler: Optional[Callable[[int, float, float], None]] = None
        self._ckpt = (
            AsyncCheckpointer(cfg.ckpt_dir, keep=cfg.ckpt_keep)
            if cfg.ckpt_dir
            else None
        )
        self._train_step = self._build_step(donate=donate)

    # ------------------------------------------------------------- step fn

    def _value_and_grad(self):
        """The step's value_and_grad: vanilla, or the planned twin.

        With ``cfg.plan_budget`` the loss goes through the one planning
        pipeline (``repro.plan_function``): trace → per-device budget →
        plan cache → checkpoint lowering, sharding-aware via the Trainer's
        mesh + input shardings.  Re-jitting after ``remesh`` re-plans under
        the new mesh (different per-device bytes → different digest).
        """
        loss_fn = self.loss_fn

        def model_loss(params, batch):
            with jax.named_scope("model"):
                return loss_fn(params, batch)

        if self.cfg.plan_budget is None:
            return jax.value_and_grad(model_loss)
        from repro.core.lowering import plan_function

        return plan_function(
            model_loss, self.cfg.plan_budget,
            backend=self.cfg.plan_backend, mesh=self.mesh,
            in_shardings=self.in_shardings,
        )

    def _build_step(self, donate: bool):
        ocfg = self.cfg.optimizer
        compress = self.cfg.compress_grads
        value_and_grad = self._value_and_grad()

        def step_fn(params, opt_state, err_fb, batch):
            loss, grads = value_and_grad(params, batch)
            if compress:
                with jax.named_scope("grad_compression"):
                    grads, err_fb = quantize_roundtrip_with_feedback(
                        grads, err_fb
                    )
            with jax.named_scope("optimizer"):
                new_params, new_opt, metrics = adamw.update(
                    ocfg, grads, opt_state, params
                )
                # NaN guard: skip the update when loss/grad-norm is non-finite.
                ok = jnp.isfinite(loss) & jnp.isfinite(metrics["grad_norm"])
                sel = lambda a, b: jax.tree_util.tree_map(
                    lambda x, y: jnp.where(ok, x, y), a, b
                )
                new_params = sel(new_params, params)
                new_opt = adamw.AdamWState(
                    step=jnp.where(ok, new_opt.step, opt_state.step),
                    mu=sel(new_opt.mu, opt_state.mu),
                    nu=sel(new_opt.nu, opt_state.nu),
                )
            metrics = dict(metrics, loss=loss, ok=ok)
            return new_params, new_opt, err_fb, metrics

        donate_argnums = (0, 1, 2) if donate else ()
        return jax.jit(step_fn, donate_argnums=donate_argnums)

    def lower(self, batch: Dict[str, Any]) -> jax.stages.Lowered:
        """The jitted step lowered for ``batch`` (arrays or
        ShapeDtypeStructs) against the live state: ``.compile().as_text()``
        is the program :meth:`run` executes."""
        return self._train_step.lower(
            self.params, self.opt_state, self.err_fb, batch
        )

    # --------------------------------------------------------- run control

    def maybe_restore(self) -> bool:
        """Resume from the latest committed checkpoint, if any."""
        if not self.cfg.ckpt_dir:
            return False
        s = latest_step(self.cfg.ckpt_dir)
        if s is None:
            return False
        state = {"params": self.params, "opt": self.opt_state}
        restored = restore(self.cfg.ckpt_dir, s, state)
        as_jnp = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        self.params = as_jnp(restored["params"])
        self.opt_state = as_jnp(restored["opt"])
        self.step = s
        return True

    def save(self, wait: bool = False) -> None:
        if not self._ckpt:
            return
        self._ckpt.save_async(
            self.step, {"params": self.params, "opt": self.opt_state}
        )
        if wait:
            self._ckpt.wait()

    def _track_time(self, dt: float) -> None:
        if self._ewma is None:
            self._ewma = dt
            return
        if dt > self.cfg.straggler_factor * self._ewma:
            self.straggler_steps += 1
            if self.on_straggler:
                self.on_straggler(self.step, dt, self._ewma)
        a = self.cfg.ewma_alpha
        self._ewma = (1 - a) * self._ewma + a * dt

    def run(
        self,
        batches,
        log: Callable[[str], None] = print,
    ) -> Dict[str, Any]:
        """Run to total_steps; ``batches`` is an iterable of host batches."""
        c = self.cfg
        it = iter(batches)
        losses, seconds, input_seconds = [], [], []
        compiles = 0
        while self.step < c.total_steps:
            with jax.profiler.StepTraceAnnotation(
                "repro.train.step", step_num=self.step
            ):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("repro.train.next_batch"):
                    batch = next(it)
                with jax.profiler.TraceAnnotation("repro.train.put_batch"):
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                t_in = time.perf_counter() - t0
                before = _compiles
                with jax.profiler.TraceAnnotation("repro.train.dispatch"):
                    self.params, self.opt_state, self.err_fb, m = self._train_step(
                        self.params, self.opt_state, self.err_fb, batch
                    )
                compiles += _compiles - before
                with jax.profiler.TraceAnnotation("repro.train.sync"):
                    loss = float(m["loss"])
                    ok = bool(m["ok"])
                dt = time.perf_counter() - t0
                self._track_time(dt)
                if not ok:
                    self.skipped += 1
                self.step += 1
                losses.append(loss)
                seconds.append(dt)
                input_seconds.append(t_in)
                if c.log_every and self.step % c.log_every == 0:
                    log(
                        f"step {self.step:6d}  loss {loss:.4f}  "
                        f"gnorm {float(m['grad_norm']):.3f}  lr {float(m['lr']):.2e}  "
                        f"{dt*1e3:.0f} ms  input {t_in*1e3:.1f} ms"
                        + (f"  [skipped={self.skipped}]" if self.skipped else "")
                    )
                if self._ckpt and self.step % c.ckpt_every == 0:
                    with jax.profiler.TraceAnnotation("repro.train.checkpoint"):
                        self.save()
        if self._ckpt:
            with jax.profiler.TraceAnnotation("repro.train.checkpoint"):
                self.save(wait=True)
        return {
            "final_loss": losses[-1] if losses else float("nan"),
            "losses": losses,
            "step_seconds": seconds,
            "input_seconds": input_seconds,
            "compiles": compiles,
            "skipped": self.skipped,
            "straggler_steps": self.straggler_steps,
            "step": self.step,
        }

    # ------------------------------------------------------ elastic re-mesh

    def remesh(self, new_mesh: jax.sharding.Mesh, shardings: Any = None) -> None:
        """Re-jit for a new mesh; reshard live state (elastic scale up/down).

        The checkpoint format stores full arrays, so resharding is a
        device_put onto the new shardings; with shardings=None the state
        stays as fully-replicated host arrays and the next jit call lays it
        out under the new mesh.
        """
        self.mesh = new_mesh
        if shardings is not None:
            self.params = jax.device_put(self.params, shardings)
        self._train_step = self._build_step(donate=True)

    def close(self) -> None:
        if self._ckpt:
            self._ckpt.close()
