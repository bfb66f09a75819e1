"""Mixture-of-Experts layer: top-k routing with capacity, expert parallelism.

Two execution paths:

* **shard_map EP path** (production, chosen whenever a mesh is bound and the
  shapes divide): tokens are split over every mesh axis (batch over
  pod/data, sequence over model), each device routes and packs its own
  (E, C_loc, D) dispatch buffer with a *local* scatter, and — when the
  expert count divides the model axis — one ``all_to_all`` pair moves rows
  to their expert owners and back (the Switch/Tutel schedule).  When E
  doesn't divide the axis (granite's 40 on tp=16) the expert weights stay
  replicated and the layer is entirely local: zero collectives.  Letting
  GSPMD infer this from a global scatter instead produces hundreds of GB of
  gather traffic per step — measured in EXPERIMENTS.md §Dry-run.

* **dense fallback** (no mesh / indivisible shapes / CPU tests): global
  scatter-add dispatch with the same routing math, bit-comparable at
  single-device shapes.

Tokens over capacity are dropped (standard Switch behaviour); the router
runs in float32.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.parallel.sharding import _axis_sizes, shard
from .layers import _init_normal


def moe_init(rng, d_model: int, cfg: MoEConfig):
    r0, r1, r2, r3 = jax.random.split(rng, 4)
    E, F = cfg.num_experts, cfg.d_ff_expert
    scale_in = d_model**-0.5
    return {
        "router": _init_normal(r0, (d_model, E), scale_in),
        "experts": {
            "w_gate": _init_normal(r1, (E, d_model, F), scale_in),
            "w_up": _init_normal(r2, (E, d_model, F), scale_in),
            "w_down": _init_normal(r3, (E, F, d_model), F**-0.5),
        },
    }


def _route(router_w, xt, cfg: MoEConfig):
    """Shared routing math: (T, D) → gates (T, K), expert ids (T, K), logits."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(axis=-1, keepdims=True), 1e-9)
    return gate_vals, expert_ids, logits


def _pack(xt, gate_vals, expert_ids, E: int, capacity: int, dt):
    """Scatter tokens into an (E, C, D) buffer; returns (disp, eid, pos, keep)."""
    T, D = xt.shape
    K = expert_ids.shape[-1]
    onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.int32)  # (T, K, E)
    flat_onehot = onehot.reshape(T * K, E)
    pos_in_expert = jnp.cumsum(flat_onehot, axis=0) - flat_onehot
    pos = jnp.sum(pos_in_expert * flat_onehot, axis=-1)  # (T·K,)
    eid = expert_ids.reshape(T * K)
    keep = pos < capacity
    src = jnp.repeat(xt, K, axis=0)
    src = jnp.where(keep[:, None], src, 0)
    pos_c = jnp.minimum(pos, capacity - 1)
    disp = jnp.zeros((E, capacity, D), dt).at[eid, pos_c].add(src)
    return disp, eid, pos_c, keep


def _expert_ffn(w, disp, dt):
    g = jnp.einsum("ecd,edf->ecf", disp, w["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", disp, w["w_up"].astype(dt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    return jnp.einsum("ecf,efd->ecd", h, w["w_down"].astype(dt))


def _combine(out_e, eid, pos_c, keep, gate_vals, T: int, K: int, D: int, dt):
    gathered = out_e[eid, pos_c]
    gathered = jnp.where(keep[:, None], gathered, 0)
    weights = gate_vals.reshape(T * K).astype(dt)
    return (gathered * weights[:, None]).reshape(T, K, D).sum(axis=1)


def _moe_shard_map(p, x: jax.Array, cfg: MoEConfig, mesh) -> Optional[jax.Array]:
    """Expert-parallel MoE under shard_map; None if the mesh/shape doesn't fit."""
    B, S, D = x.shape
    dt = x.dtype
    E, K = cfg.num_experts, cfg.top_k
    sizes = _axis_sizes(mesh)
    names = set(mesh.axis_names)
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    tp = sizes.get("model", 1) if "model" in names else 1
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]
    if dp == 1 and tp == 1:
        return None
    if B % dp:
        return None
    seq_split = tp if (tp > 1 and S % tp == 0) else 1
    ep = tp > 1 and E % tp == 0 and seq_split == tp  # all_to_all EP layout
    T_loc = (B // dp) * (S // seq_split)
    C_loc = max(8, int(math.ceil(cfg.capacity_factor * T_loc * K / E)))
    if ep and C_loc % 1:
        return None

    x_spec = P(dp_axes if dp_axes else None, "model" if seq_split > 1 else None, None)
    e_spec = (
        {k: P("model", None, None) for k in ("w_gate", "w_up", "w_down")}
        if ep
        else {k: P(None, None, None) for k in ("w_gate", "w_up", "w_down")}
    )

    def local_fn(router_w, experts_w, x_loc):
        b, s, _ = x_loc.shape
        xt = x_loc.reshape(b * s, D)
        gate_vals, expert_ids, _ = _route(router_w, xt, cfg)
        disp, eid, pos_c, keep = _pack(xt, gate_vals, expert_ids, E, C_loc, dt)
        if ep:
            # (E, C_loc, D) → (E/tp, C_loc·tp, D): rows travel to expert owners.
            # optimization_barrier pins the collective to the bf16 tensors —
            # without it XLA hoists the expert-silu f32 convert *before* the
            # all-to-all and doubles its bytes (measured: EXPERIMENTS §Perf).
            disp = jax.lax.all_to_all(
                disp, "model", split_axis=0, concat_axis=1, tiled=True
            )
            disp = jax.lax.optimization_barrier(disp)
            out = _expert_ffn(experts_w, disp, dt)
            out = jax.lax.optimization_barrier(out)
            out = jax.lax.all_to_all(
                out, "model", split_axis=1, concat_axis=0, tiled=True
            )
        else:
            out = _expert_ffn(experts_w, disp, dt)
        y = _combine(out, eid, pos_c, keep, gate_vals, b * s, K, D, dt)
        return y.reshape(b, s, D)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), e_spec, x_spec),
        out_specs=x_spec,
    )
    return fn(p["router"], p["experts"], x)


def moe_apply(
    p, x: jax.Array, cfg: MoEConfig, return_aux: bool = False
):
    """x: (B, S, D) → (B, S, D)[, aux-loss scalars]."""
    if not return_aux:
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty:
            y = _moe_shard_map(p, x, cfg, mesh)
            if y is not None:
                return y
    B, S, D = x.shape
    dt = x.dtype
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    capacity = max(8, int(cfg.capacity_factor * T * K / E))

    # position of each (token, k) slot within its expert queue
    onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.int32)  # (T, K, E)
    flat_onehot = onehot.reshape(T * K, E)
    pos_in_expert = jnp.cumsum(flat_onehot, axis=0) - flat_onehot  # (T·K, E)
    pos = jnp.sum(pos_in_expert * flat_onehot, axis=-1)  # (T·K,)
    eid = expert_ids.reshape(T * K)
    keep = pos < capacity

    # dispatch: (E, C, D)
    disp = jnp.zeros((E, capacity, D), dt)
    src = jnp.repeat(xt, K, axis=0)  # (T·K, D) token replicated per route
    src = jnp.where(keep[:, None], src, 0)
    pos_c = jnp.minimum(pos, capacity - 1)
    disp = disp.at[eid, pos_c].add(src)
    # EP over the expert axis; when E doesn't divide the model axis (e.g.
    # granite's 40 experts on tp=16) the capacity rows shard instead — an
    # unsharded dispatch buffer is ~32 GB/device at production scale.
    disp = shard(disp, "experts", "expert_cap", None)

    # expert computation (batched over E, sharded = expert parallel)
    w = p["experts"]
    g = jnp.einsum("ecd,edf->ecf", disp, w["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", disp, w["w_up"].astype(dt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, w["w_down"].astype(dt))
    out_e = shard(out_e, "experts", "expert_cap", None)

    # combine: gather each route's output, weight, sum over K
    gathered = out_e[eid, pos_c]  # (T·K, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    weights = gate_vals.reshape(T * K).astype(dt)
    combined = (gathered * weights[:, None]).reshape(T, K, D).sum(axis=1)
    y = combined.reshape(B, S, D)
    y = shard(y, "batch", None, "model")

    if not return_aux:
        return y
    # Switch-style load-balance loss + router z-loss
    density = probs.mean(axis=0)  # (E,)
    usage = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32).mean(axis=0)
    lb_loss = E * jnp.sum(density * usage)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, {"lb_loss": lb_loss, "z_loss": z_loss}
