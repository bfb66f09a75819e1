"""Logical-axis sharding rules (DP/TP/EP/SP over ("pod", "data", "model")).

Models annotate activations with *logical* axis names; a rules table maps
them to mesh axes.  Changing the table re-shards the whole model — this is
the knob the §Perf hillclimb turns.

Default mapping:

  batch    → ("pod", "data")   data parallelism (hierarchical across pods)
  seq      → None              (sequence kept local for training shapes)
  seq_sp   → "data"            sequence parallelism for long-context decode
  model    → "model"           d_model kept replicated by default; the TP
                               split lives on heads / ffn / vocab instead
  heads    → "model"           tensor parallelism over attention heads
  kv_heads → "model"           (GQA: kv heads ≤ TP size is handled by rules)
  ffn      → "model"           MLP hidden dim
  experts  → "model"           expert parallelism
  vocab    → "model"           embedding / logits split
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Rules = Dict[str, Any]  # logical name -> mesh axis (str | tuple | None)

DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_sp": "data",
    "seq_act": "model",  # Megatron-style sequence parallelism: the residual
    #                      stream between layer groups lives S/tp per device
    "model": None,
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "experts": "model",
    "expert_cap": "model",  # fallback: shard expert capacity rows when the
    #                         expert count doesn't divide the model axis
    "vocab": "model",
    "state": None,
}

# §Perf hillclimb alternative: NO tensor parallelism — the "model" mesh axis
# joins data parallelism and params are fully sharded (ZeRO-3).  For models
# whose per-chip matmul shards would be tiny under tp=16 (≤ ~4B params at 256
# chips), this removes every activation-cotangent all-reduce and replaces it
# with per-layer weight all-gathers an order of magnitude smaller.
DP_ONLY_RULES: Rules = {
    **DEFAULT_RULES,
    "batch": ("pod", "data", "model"),
    "seq_act": None,
    "heads": None,
    "kv_heads": None,
    "ffn": None,
    "experts": None,
    "expert_cap": None,
    "vocab": None,
}

# MoE hybrid: attention/dense parts ZeRO-sharded over data (no TP — their
# per-chip shards are tiny next to the experts), experts stay EP over the
# model axis with the all-to-all schedule.
DP_ATTN_RULES: Rules = {
    **DEFAULT_RULES,
    "seq_act": None,
    "heads": None,
    "kv_heads": None,
    "ffn": None,
    # vocab stays TP over "model": un-sharding it makes every chip hold the
    # full (B_loc, S, V) logits — 40 GB/chip at this cell's shape.
}

# Active rules — module-level so layer code stays signature-light; the
# launcher swaps them per run (hillclimb knob).
_ACTIVE_RULES: Rules = dict(DEFAULT_RULES)


def set_rules(rules: Rules) -> None:
    global _ACTIVE_RULES
    _ACTIVE_RULES = dict(rules)


def get_rules() -> Rules:
    return dict(_ACTIVE_RULES)


def resolve_spec(
    logical: Sequence[Optional[str]],
    axis_sizes: Dict[str, int],
    shape: Optional[Sequence[int]] = None,
    rules: Optional[Rules] = None,
    pad_dims: Sequence[int] = (),
) -> P:
    """Logical names → PartitionSpec under ``rules`` and abstract axis sizes.

    The mesh-free core of :func:`resolve`, shared with the planners'
    byte accounting (``launch.plan`` budgets per-device bytes through this
    exact function, so the sharding the model compiles to and the sharding
    the DP budgets against cannot drift apart).

    With ``shape``, divisibility is checked inline so an axis rejected on one
    dim (e.g. "model" on 40 experts) stays available for a later dim (e.g.
    the expert-capacity fallback) instead of being consumed and dropped.
    Dims listed in ``pad_dims`` skip the divisibility check — GSPMD pads
    those (sequence dims at odd lengths), and ``local_shape``'s ceil
    division accounts the padded shard.
    """
    rules = _ACTIVE_RULES if rules is None else rules
    axes = set(axis_sizes)
    pad = set(pad_dims)
    used: set = set()
    spec = []
    for i, name in enumerate(logical):
        if name is None:
            spec.append(None)
            continue
        target = rules.get(name)
        if target is None:
            spec.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        eff = []
        dim = shape[i] if shape is not None and i < len(shape) else None
        prod = 1
        for a in target:
            if a not in axes or a in used:
                continue
            if (dim is not None and i not in pad
                    and dim % (prod * axis_sizes.get(a, 1)) != 0):
                continue  # this axis would not divide — leave it available
            eff.append(a)
            prod *= axis_sizes.get(a, 1)
        used.update(eff)
        eff = tuple(eff)
        spec.append(eff if len(eff) > 1 else (eff[0] if eff else None))
    return P(*spec)


def resolve(
    logical: Sequence[Optional[str]],
    mesh: Optional[Mesh] = None,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Logical names → PartitionSpec under the active rules + mesh axes."""
    src = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
    return resolve_spec(logical, _axis_sizes(src), shape=shape)


def _axis_sizes(mesh) -> Dict[str, int]:
    """Axis name → size of a ``Mesh`` or ``AbstractMesh``."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def drop_indivisible(spec: P, shape: Tuple[int, ...], axis_sizes: Dict[str, int]) -> P:
    """Replicate any dim the mesh axes don't divide evenly (e.g. kv_heads=8
    on a 16-way model axis, or an odd vocab).  GSPMD *would* pad, but padded
    shards waste memory/compute — replication is the perf-correct fallback."""
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for a in axes:
            total *= axis_sizes.get(a, 1)
        out.append(entry if total > 0 and dim % total == 0 else None)
    return P(*out)


def shard(x, *logical: Optional[str]):
    """with_sharding_constraint by logical axis names (no-op without a mesh).

    A constraint the mesh cannot take raises: a layout that silently fell
    back to replicated would run the model unsharded with no signal.
    """
    if jax.sharding.get_abstract_mesh().empty:
        return x
    spec = resolve(logical, shape=tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter sharding: map a param-tree path to a PartitionSpec.
# ---------------------------------------------------------------------------


def param_spec(path: str, shape: Tuple[int, ...]) -> P:
    """Sharding rule for one parameter, keyed on its tree path.

    Conventions (matching repro.models param names):
      embed / unembed   : (vocab, d_model)          → vocab over "model"
      wq/wk/wv          : (d_model, heads·dh)       → out dim over "model"
      wo                : (heads·dh, d_model)       → in dim over "model"
      w_gate/w_up       : (d_model, d_ff)           → d_ff over "model"
      w_down            : (d_ff, d_model)           → d_ff over "model"
      experts.*         : (E, …)                    → E over "model"
      norms / biases / scalars                      → replicated
    """
    rules = _ACTIVE_RULES

    def ax(name):
        t = rules.get(name)
        return t if t is not None else None

    if len(shape) == 0 or min(shape) == 0:
        return P()
    last = path.split("/")[-1]
    if "expert" in path:
        # stacked experts: leading E axis
        spec = [ax("experts")] + [None] * (len(shape) - 1)
        if last in ("w_gate", "w_up") and len(shape) == 3:
            spec[2] = None  # E already takes "model"
        return P(*spec)
    if last in ("embed", "unembed", "lm_head"):
        return P(ax("vocab"), None) if len(shape) == 2 else P()
    if last in ("wq", "wk", "wv", "wqkv"):
        return P(None, ax("heads")) if len(shape) >= 2 else P(ax("heads"))
    if last == "wo":
        return P(ax("heads"), None)
    if last in ("w_gate", "w_up", "w13"):
        return P(None, ax("ffn"))
    if last in ("w_down", "w2"):
        return P(ax("ffn"), None)
    if last in ("in_proj", "x_proj", "dt_proj"):
        return P(None, ax("ffn")) if len(shape) == 2 else P()
    if last == "out_proj":
        return P(ax("ffn"), None) if len(shape) == 2 else P()
    return P(*([None] * len(shape)))


def stacked_param_spec(path: str, shape: Tuple[int, ...]) -> P:
    """Same, for layer-stacked params with a leading [n_layers] axis."""
    inner = param_spec(path, shape[1:])
    return P(None, *inner)


def tree_param_specs(params, stacked_prefixes: Sequence[str] = ("layers",)):
    """PartitionSpec pytree matching a parameter pytree."""

    def visit(path_tuple, leaf):
        keys = []
        for p in path_tuple:
            if hasattr(p, "key"):
                keys.append(str(p.key))
            elif hasattr(p, "idx"):
                keys.append(str(p.idx))
            else:
                keys.append(str(p))
        path = "/".join(keys)
        shape = tuple(leaf.shape) if hasattr(leaf, "shape") else ()
        if any(path.startswith(pref) for pref in stacked_prefixes) and len(shape) >= 1:
            return stacked_param_spec(path, shape)
        return param_spec(path, shape)

    return jax.tree_util.tree_map_with_path(visit, params)


def fsdp_extend(spec: P, shape: Tuple[int, ...], axis_sizes: Dict[str, int],
                fsdp_axis: str = "data", min_elems: int = 1 << 16) -> P:
    """ZeRO-3/FSDP: additionally shard the largest still-replicated dim of a
    big tensor over the data axis.  Keeps small tensors (norms, biases)
    replicated."""
    n = 1
    for d in shape:
        n *= d
    if n < min_elems or fsdp_axis not in axis_sizes:
        return spec
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    # never reuse an axis that already shards some dim
    for e in entries:
        taken = e if isinstance(e, tuple) else (e,)
        if fsdp_axis in taken:
            return spec
    size = axis_sizes[fsdp_axis]
    # largest unsharded, divisible dim
    best, best_dim = -1, -1
    for i, (d, e) in enumerate(zip(shape, entries)):
        if e is None and d % size == 0 and d > best_dim:
            best, best_dim = i, d
    if best < 0:
        return spec
    entries[best] = fsdp_axis
    return P(*entries)


# ---------------------------------------------------------------------------
# Per-device byte accounting (the paper's budget B is ONE accelerator's
# memory, §3): everything that budgets bytes — the traced carriers
# (core.jaxpr_graph), BlockGraph annotations, and the launchers' chain
# graphs (launch.plan) — prices tensors through these helpers, so there is
# exactly one definition of "per-device bytes" in the system.
# ---------------------------------------------------------------------------


def axis_sizes_of(mesh) -> Dict[str, int]:
    """Axis-name → size for a Mesh/AbstractMesh, or a dict passed through.

    Accepting a plain ``{"data": 8, "model": 2}`` dict lets the byte
    accounting (and with it the whole planning pipeline) run without any
    real devices — only the lowerings need a concrete ``Mesh``.
    """
    if mesh is None:
        return {}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    return _axis_sizes(mesh)


def _entry_shards(entry, axis_sizes: Dict[str, int]) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    k = 1
    for a in axes:
        k *= max(1, int(axis_sizes.get(a, 1)))
    return k


def local_shape(
    shape: Sequence[int], spec, axis_sizes: Dict[str, int]
) -> Tuple[int, ...]:
    """Per-device shard shape of a global ``shape`` under ``spec``.

    GSPMD semantics: each sharded dim is ceil-divided by the product of its
    mesh axis sizes (padding counts — padded shards still occupy HBM).
    """
    entries = tuple(spec) if spec is not None else ()
    entries = entries + (None,) * (len(shape) - len(entries))
    return tuple(
        -(-int(d) // _entry_shards(e, axis_sizes))
        for d, e in zip(shape, entries)
    )


def num_shards(shape: Sequence[int], spec, axis_sizes: Dict[str, int]) -> int:
    """Effective #devices a tensor is split across: global/local elems."""
    loc = local_shape(shape, spec, axis_sizes)
    g = l = 1
    for d, ld in zip(shape, loc):
        g *= max(1, int(d))
        l *= max(1, int(ld))
    return max(1, g // max(1, l))


def local_bytes(
    shape: Sequence[int], spec, axis_sizes: Dict[str, int], itemsize: int
) -> int:
    """Per-device bytes of one tensor (ceil-divided shard × itemsize)."""
    n = 1
    for d in local_shape(shape, spec, axis_sizes):
        n *= max(1, int(d))
    return n * int(itemsize)


def normalize_spec(sharding) -> P:
    """NamedSharding | PartitionSpec | None → a plain PartitionSpec."""
    if sharding is None:
        return P()
    if isinstance(sharding, NamedSharding):
        return sharding.spec
    if isinstance(sharding, P):
        return sharding
    raise TypeError(
        f"expected PartitionSpec/NamedSharding/None, got {type(sharding).__name__}"
    )


def sharded_aval_bytes(aval, spec, axis_sizes: Dict[str, int]) -> int:
    """Per-device byte size of one aval under ``spec`` (replicated: global)."""
    import numpy as _np

    if not hasattr(aval, "shape") or not hasattr(aval, "dtype"):
        return 1
    return local_bytes(
        aval.shape, spec, axis_sizes, _np.dtype(aval.dtype).itemsize
    )


# ---------------------------------------------------------------------------
# Conservative sharding propagation over a jaxpr.
#
# The traced carrier needs a per-equation output sharding to emit per-device
# M_v.  Full GSPMD propagation lives inside XLA; here we follow the specs
# through the primitives whose propagation is unambiguous (elementwise /
# same-shape, transpose, broadcast, reductions, dot_general) and fall back
# to **replicated** everywhere else.  Replicated is the conservative
# direction for a memory planner: per-device bytes are over-, never
# under-estimated, so a plan that fits the modeled budget fits the machine.
# ---------------------------------------------------------------------------

_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
})


def _spec_entries(spec: Optional[P], ndim: int) -> Tuple:
    entries = tuple(spec) if spec is not None else ()
    return entries + (None,) * (ndim - len(entries))


def propagate_eqn_specs(
    closed_jaxpr, in_specs: Sequence[P], axis_sizes: Dict[str, int]
):
    """Per-equation output PartitionSpecs for a ClosedJaxpr.

    ``in_specs`` aligns with ``jaxpr.invars``.  Returns a list (one entry
    per equation) of tuples of PartitionSpecs aligned with the equation's
    outvars.  Unknown primitives propagate replicated (see module note).
    """
    from jax.extend import core as _jcore

    jaxpr = closed_jaxpr.jaxpr
    env: Dict[Any, P] = {}
    for v in jaxpr.constvars:
        env[v] = P()
    for v, s in zip(jaxpr.invars, in_specs):
        env[v] = normalize_spec(s)

    def spec_of(var) -> P:
        # Literals (e.g. the divisor of jnp.mean) are unhashable on older
        # JAX and always replicated — never probe the env with one
        if isinstance(var, _jcore.Literal):
            return P()
        return env.get(var, P())

    out: list = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        specs = None
        try:
            if name == "dot_general":
                specs = (_dot_general_spec(eqn, spec_of),)
            elif name == "transpose":
                perm = eqn.params["permutation"]
                ent = _spec_entries(spec_of(eqn.invars[0]),
                                    len(eqn.invars[0].aval.shape))
                specs = (P(*[ent[p] for p in perm]),)
            elif name == "broadcast_in_dim":
                specs = (_broadcast_spec(eqn, spec_of),)
            elif name in _REDUCE_PRIMS:
                axes = set(eqn.params.get("axes", ()))
                iv = eqn.invars[0]
                ent = _spec_entries(spec_of(iv), len(iv.aval.shape))
                specs = (P(*[e for i, e in enumerate(ent) if i not in axes]),)
        except Exception:
            specs = None
        if specs is None:
            specs = tuple(_same_shape_spec(ov, eqn, spec_of)
                          for ov in eqn.outvars)
        for ov, s in zip(eqn.outvars, specs):
            if type(ov).__name__ != "DropVar":
                env[ov] = s
        out.append(specs)
    return out


def _same_shape_spec(ov, eqn, spec_of) -> P:
    """Shape-preserving passthrough: adopt the most-sharded operand whose
    shape equals the output's; replicated otherwise."""
    shape = getattr(getattr(ov, "aval", None), "shape", None)
    if shape is None:
        return P()
    best, best_k = P(), 1
    for iv in eqn.invars:
        if getattr(getattr(iv, "aval", None), "shape", None) != shape:
            continue
        s = spec_of(iv)
        # rank operands by how many ways they split the tensor
        k = num_shards(shape, s, {a: 2 for a in _spec_axes(s)})
        if k > best_k:
            best, best_k = s, k
    return best


def _spec_axes(spec: P):
    axes = []
    for e in tuple(spec):
        if e is None:
            continue
        axes.extend(e if isinstance(e, tuple) else (e,))
    return axes


def _dot_general_spec(eqn, spec_of) -> P:
    """Output spec of dot_general: (batch…, lhs-free…, rhs-free…) dims keep
    their operand's sharding; contracted dims disappear."""
    lhs, rhs = eqn.invars[0], eqn.invars[1]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    l_ent = _spec_entries(spec_of(lhs), len(lhs.aval.shape))
    r_ent = _spec_entries(spec_of(rhs), len(rhs.aval.shape))
    out = [l_ent[i] for i in lb]
    out += [l_ent[i] for i in range(len(l_ent)) if i not in set(lc) | set(lb)]
    out += [r_ent[i] for i in range(len(r_ent)) if i not in set(rc) | set(rb)]
    # one mesh axis must not shard two output dims (lhs/rhs may both carry it)
    seen: set = set()
    clean = []
    for e in out:
        axes = e if isinstance(e, tuple) else ((e,) if e is not None else ())
        if any(a in seen for a in axes):
            clean.append(None)
            continue
        seen.update(axes)
        clean.append(e)
    return P(*clean)


def _broadcast_spec(eqn, spec_of) -> P:
    iv = eqn.invars[0]
    bdims = eqn.params["broadcast_dimensions"]
    in_shape = iv.aval.shape
    ent = _spec_entries(spec_of(iv), len(in_shape))
    out_shape = eqn.outvars[0].aval.shape
    out = [None] * len(out_shape)
    for i, j in enumerate(bdims):
        if in_shape[i] == out_shape[j]:
            out[j] = ent[i]
    return P(*out)


def named_sharding_tree(params, mesh: Mesh, fsdp: bool = False,
                        fsdp_axes: Tuple[str, ...] = ("data",), **kw):
    specs = tree_param_specs(params, **kw)
    sizes = _axis_sizes(mesh)

    def to_sharding(spec, leaf):
        shape = tuple(leaf.shape) if hasattr(leaf, "shape") else ()
        p = drop_indivisible(spec, shape, sizes)
        if fsdp:
            for ax in fsdp_axes:
                p = fsdp_extend(p, shape, sizes, fsdp_axis=ax)
        return NamedSharding(mesh, p)

    return jax.tree_util.tree_map(to_sharding, specs, params)
