"""Weights made by the benchmark from ``--seed``, for the program and the
reference alike.

Every value is drawn from a key of its own: ``fold_in(fold_in(seed key,
leaf index), layer)``.  So the program's tree (layers stacked, in its
shardings) and the reference's tree (its own names) hold the same numbers,
and either can be made again leaf by leaf without the other.  Neither side
takes anything the other made.

Scales follow the program's own initialisation: normal with standard
deviation ``fan_in ** -0.5`` for projections, 1 for an embedding of its own,
ones for norm scales.  A tied embedding is also the output projection, so it
takes the projection's scale, ``hidden_size ** -0.5``.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

#: per-layer leaves of a dense block, in a fixed order (the index is part of
#: each value's key, so this order never changes)
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down")
#: leaves outside the layer stack
GLOBAL_LEAVES = ("embed", "final_norm", "head")


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number below 2**64 (more than 32 bits hold)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def layer_shapes(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Leaf name -> (shape, scale); scale 0 means ones."""
    d, f, dh = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    return {
        "ln1": ((d,), 0.0),
        "wq": ((d, hq), d ** -0.5),
        "wk": ((d, hkv), d ** -0.5),
        "wv": ((d, hkv), d ** -0.5),
        "wo": ((hq, d), hq ** -0.5),
        "ln2": ((d,), 0.0),
        "w_gate": ((d, f), d ** -0.5),
        "w_up": ((d, f), d ** -0.5),
        "w_down": ((f, d), f ** -0.5),
    }


def global_shapes(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    d, v = m["hidden_size"], m["vocab_size"]
    tied = m["tie_word_embeddings"]
    out = {"embed": ((v, d), d ** -0.5 if tied else 1.0), "final_norm": ((d,), 0.0)}
    if not tied:
        out["head"] = ((v, d), d ** -0.5)
    return out


def _draw(key, shape, scale):
    if scale == 0.0:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * scale


def global_leaf(key, m: Dict, name: str) -> jax.Array:
    shape, scale = global_shapes(m)[name]
    return _draw(jax.random.fold_in(key, len(LAYER_LEAVES) + GLOBAL_LEAVES.index(name)),
                 shape, scale)


def layer_leaf(key, m: Dict, name: str, layer) -> jax.Array:
    shape, scale = layer_shapes(m)[name]
    k = jax.random.fold_in(jax.random.fold_in(key, LAYER_LEAVES.index(name)), layer)
    return _draw(k, shape, scale)


def stacked_leaf(key, m: Dict, name: str) -> jax.Array:
    """All layers of one leaf, (L, ...): the same values as ``layer_leaf``."""
    layers = jnp.arange(m["num_hidden_layers"], dtype=jnp.uint32)
    return jax.vmap(lambda i: layer_leaf(key, m, name, i))(layers)


def reference_tree(key, m: Dict) -> Dict:
    """The reference's layout: globals by name, layers stacked by name."""
    tree = {n: global_leaf(key, m, n) for n in global_shapes(m)}
    tree["layers"] = {n: stacked_leaf(key, m, n) for n in LAYER_LEAVES}
    return tree


# ------------------------------------------------------- the program's tree

#: where each leaf sits in the program's parameter tree
#: (``repro.models.transformer.LM.init`` for a dense block)
PROGRAM_LAYER_PATHS = {
    "ln1": ("ln1", "scale"), "wq": ("attn", "wq"), "wk": ("attn", "wk"),
    "wv": ("attn", "wv"), "wo": ("attn", "wo"), "ln2": ("ln2", "scale"),
    "w_gate": ("mlp", "w_gate"), "w_up": ("mlp", "w_up"),
    "w_down": ("mlp", "w_down"),
}
PROGRAM_GLOBAL_PATHS = {
    "embed": ("embedding", "embed"), "final_norm": ("final_norm", "scale"),
    "head": ("head", "unembed"),
}
BLOCK = "b0_attn_mlp"


def to_program(ref_like: Dict) -> Dict:
    """Rename a reference-layout tree into the program's layout."""
    block: Dict = {}
    for name, (a, b) in PROGRAM_LAYER_PATHS.items():
        block.setdefault(a, {})[b] = ref_like["layers"][name]
    tree: Dict = {"layers": {BLOCK: block}}
    for name, (a, b) in PROGRAM_GLOBAL_PATHS.items():
        if name in ref_like:
            tree[a] = {b: ref_like[name]}
    return tree


def from_program(tree: Dict) -> Dict:
    """The inverse of :func:`to_program`."""
    out: Dict = {"layers": {}}
    for name, (a, b) in PROGRAM_LAYER_PATHS.items():
        out["layers"][name] = tree["layers"][BLOCK][a][b]
    for name, (a, b) in PROGRAM_GLOBAL_PATHS.items():
        if a in tree:
            out[name] = tree[a][b]
    return out


def program_params_fn(m: Dict) -> Callable[[jax.Array], Dict]:
    """``key -> params`` in the program's layout; jit it with the program's
    shardings as ``out_shardings`` to make the weights on the device."""
    return lambda key: to_program(reference_tree(key, m))


def leaf_norms(ref_like: Dict) -> Dict[str, jax.Array]:
    """Frobenius norm of every leaf, per layer for the stacked ones:
    ``{"wq": (L,), "embed": ()}``.  A leaf here is one layer's matrix."""
    sq = lambda x, axes: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))
    out = {n: sq(v, tuple(range(1, v.ndim))) for n, v in ref_like["layers"].items()}
    for n in GLOBAL_LEAVES:
        if n in ref_like:
            out[n] = sq(ref_like[n], None)
    return out


def change_norms(now: Dict, start: Dict) -> Dict[str, jax.Array]:
    """Norms of ``now - start``, leaf by leaf.  The caller draws ``start``
    again with the very function that made it, so that the two agree to
    the bit, rather than keeping a copy beside the state."""
    diff = jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32) - b, now, start)
    return leaf_norms(diff)
