"""AdamW with decoupled weight decay, global-norm clipping and LR schedules.

Pure-JAX (no optax dependency).  Optimizer state is a pytree shaped like the
params, so it inherits the parameter sharding (and can additionally be
ZeRO-1-scattered over the data axis — see ``zero1_partition_spec``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array  # ()
    mu: Any  # pytree like params
    nu: Any  # pytree like params


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def lr_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup → cosine decay to min_lr_frac·lr."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / jnp.maximum(1, cfg.warmup_steps))
    prog = jnp.clip(
        (step - cfg.warmup_steps)
        / jnp.maximum(1, cfg.total_steps - cfg.warmup_steps),
        0.0,
        1.0,
    )
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def init(params: Any) -> AdamWState:
    # zeros_like keeps each parameter's sharding: moments of a sharded
    # model are laid out like it, not piled on the default device
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree_util.tree_map(zeros, params),
        nu=jax.tree_util.tree_map(zeros, params),
    )


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


def update(
    cfg: AdamWConfig,
    grads: Any,
    state: AdamWState,
    params: Any,
) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
    """Returns (new_params, new_state, metrics)."""
    step = state.step + 1
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

    b1, b2 = cfg.b1, cfg.b2
    lr = lr_schedule(cfg, step)
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay and p.ndim >= 2:  # no decay on norms/biases
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    new_p, new_m, new_v = [], [], []
    for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p):
        np_, nm, nv = upd(g, m, v, p)
        new_p.append(np_)
        new_m.append(nm)
        new_v.append(nv)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return (
        jax.tree_util.tree_unflatten(treedef, new_p),
        AdamWState(
            step=step,
            mu=jax.tree_util.tree_unflatten(treedef, new_m),
            nu=jax.tree_util.tree_unflatten(treedef, new_v),
        ),
        metrics,
    )
