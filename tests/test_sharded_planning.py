"""Sharded planning: per-device M_v, per-device budgets, pjit-composable twins.

Most assertions need only the *accounting* — sharding-aware tracing works
with an abstract ``{axis: size}`` mesh dict, no devices required.  The
end-to-end assertions (bit-identical gradients of the sharded planned twin
vs vanilla ``jax.value_and_grad`` of the sharded function) need 8 devices:
in tier-1 they run through the subprocess wrapper at the bottom
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``); CI also runs this
file directly under that flag — the "8-fake-device sharded smoke".
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import repro
from repro.core import PlanCache, Planner
from repro.core.graph import graph_digest
from repro.core.jaxpr_graph import trace
from repro.core.liveness import vanilla_peak

DN = (((1,), (0,)), ((), ()))


def _mlp(n_layers=6, width=16, batch=8):
    def fn(params, x):
        h = x
        for w in params:
            h = lax.tanh(lax.dot_general(h, w, DN))
        return jnp.sum(h * h)

    key = jax.random.PRNGKey(0)
    params = [
        jax.random.normal(jax.random.fold_in(key, i), (width, width)) * 0.3
        for i in range(n_layers)
    ]
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, width))
    return fn, params, x


def _bits(a, b):
    return all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


# ---------------------------------------------------------------------------
# Accounting (abstract mesh — no devices needed)
# ---------------------------------------------------------------------------


def test_per_device_mv_is_global_over_shards():
    """Every batch-carrying equation output is split 8 ways → M_v = global/8;
    the scalar loss stays replicated."""
    fn, params, x = _mlp()
    n = len(params)
    plain = trace(fn, params, x).graph
    sh = trace(fn, params, x, mesh={"data": 8},
               in_shardings=[P()] * n + [P("data", None)]).graph
    assert plain.n == sh.n
    for a, b in zip(plain.nodes, sh.nodes):
        if a.kind == "reduce_sum":
            assert b.memory == a.memory  # scalar: replicated
        else:
            assert b.memory == a.memory / 8, (a.name, a.memory, b.memory)


def test_mean_style_loss_with_literal_operands():
    """jnp.mean lowers to reduce_sum + div-by-literal: literals are
    unhashable on this JAX and must propagate as replicated, not crash."""

    def fn(params, x):
        h = x
        for w in params:
            h = jnp.tanh(lax.dot_general(h, w, DN))
        return jnp.mean(h * h)

    _, params, x = _mlp()
    sh = trace(fn, params, x, mesh={"data": 8},
               in_shardings=[P()] * len(params) + [P("data", None)]).graph
    assert sh.n > 0  # propagation completed
    pf = repro.plan_function(fn, None, mesh={"data": 8},
                             in_shardings=(None, P("data", None)),
                             planner=Planner(cache=PlanCache()))
    loss, _ = pf(params, x)
    np.testing.assert_allclose(
        np.asarray(loss), np.asarray(fn(params, x)), rtol=1e-6
    )


def test_unknown_primitive_falls_back_to_replicated():
    """Conservative fallback: a reshape (not in the propagation rules)
    replicates — per-device bytes are over-, never under-estimated."""

    def fn(x):
        h = lax.reshape(x, (x.shape[0] * x.shape[1],))
        return jnp.sum(h * h)

    x = jnp.ones((8, 4), jnp.float32)
    sh = trace(fn, x, mesh={"data": 8}, in_shardings=[P("data", None)]).graph
    reshaped = [nd for nd in sh.nodes if nd.kind == "reshape"]
    assert reshaped and reshaped[0].memory == 8 * 4 * 4  # full global bytes


def test_distinct_shardings_distinct_digests():
    """Sharded and unsharded traces (and different shard counts) must not
    collide in the plan cache — per-device M_v is part of the digest."""
    fn, params, x = _mlp()
    n = len(params)
    d_plain = graph_digest(trace(fn, params, x).graph)
    shard8 = [P()] * n + [P("data", None)]
    d8 = graph_digest(trace(fn, params, x, mesh={"data": 8},
                            in_shardings=shard8).graph)
    d4 = graph_digest(trace(fn, params, x, mesh={"data": 4},
                            in_shardings=shard8).graph)
    d8_again = graph_digest(trace(fn, params, x, mesh={"data": 8},
                                  in_shardings=shard8).graph)
    assert len({d_plain, d8, d4}) == 3
    assert d8 == d8_again  # deterministic: same sharding → same key


def test_sharded_and_unsharded_plans_cached_separately():
    fn, params, x = _mlp()
    planner = Planner(cache=PlanCache())
    budget = vanilla_peak(trace(fn, params, x).graph, liveness=False) / 2
    pf_plain = repro.plan_function(fn, budget, planner=planner)
    pf_plain(params, x)
    misses_after_plain = planner.cache.stats()["misses"]
    pf_sh = repro.plan_function(fn, budget, mesh={"data": 8},
                                in_shardings=(None, P("data", None)),
                                planner=planner)
    pf_sh(params, x)
    # the sharded graph is a different planning problem: it must MISS
    assert planner.cache.stats()["misses"] > misses_after_plain


def test_per_device_budget_semantics():
    """The budget the planner enforces is per-device: a budget far below the
    unsharded minimum plans fine when 8 devices share the activations."""
    fn, params, x = _mlp()
    planner = Planner(cache=PlanCache())
    g_plain = trace(fn, params, x).graph
    g_sh = trace(fn, params, x, mesh={"data": 8},
                 in_shardings=[P()] * len(params) + [P("data", None)]).graph
    mfb_plain = planner.min_feasible_budget(g_plain)
    mfb_sh = planner.min_feasible_budget(g_sh)
    assert mfb_sh < mfb_plain / 4  # activations dominate → ≈ /8
    pf = repro.plan_function(fn, mfb_sh, mesh={"data": 8},
                             in_shardings=(None, P("data", None)),
                             planner=planner)
    lowered = pf.lowered_for(params, x)
    assert lowered.plan.peak_memory <= mfb_sh
    assert _bits(pf(params, x), jax.value_and_grad(fn)(params, x))


def test_check_lowering_conformant_on_sharded_carrier():
    """Lowering conformance on a sharded twin: the save-set of the jaxpr
    backend's lowering matches the plan computed on per-device bytes
    (abstract mesh — no devices needed)."""
    from repro.analysis import check_lowering
    from repro.core.lowering.carriers import TracedCarrier

    fn, params, x = _mlp()
    carrier = TracedCarrier.trace(
        fn, (params, x), mesh={"data": 8},
        in_shardings=(None, P("data", None)),
    )
    g = carrier.to_graph()
    planner = Planner(cache=PlanCache())
    rep = planner.plan(g, planner.min_feasible_budget(g))
    assert rep.plan is not None
    report = check_lowering(carrier, rep.plan)
    assert report.ok, str(report.findings)

    # drift detection still works on sharded carriers: a plan for a roomier
    # budget has a different save-set, so checking it against the tight
    # lowering must fail
    from repro.core.liveness import vanilla_peak
    from repro.core.lowering.policy import traced_value_and_grad

    roomy = planner.plan(g, vanilla_peak(g, liveness=True)).plan
    if roomy.cached != rep.plan.cached:
        stale = traced_value_and_grad(carrier, rep.plan)
        r2 = check_lowering(carrier, roomy, lowered=stale)
        assert not r2.ok


# ---------------------------------------------------------------------------
# End to end on 8 (fake) devices
# ---------------------------------------------------------------------------

requires8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


def _mesh8():
    from repro.launch.mesh import auto_mesh

    return auto_mesh((8,), ("data",))


@requires8
def test_sharded_planned_twin_bit_identical_to_vanilla():
    """Acceptance: plan_function over a sharded function on an 8-device mesh
    plans against a per-device budget and returns bit-identical loss/grads
    to vanilla jax.value_and_grad of the same sharded function."""
    mesh = _mesh8()
    fn, params, x = _mlp(batch=16)
    xs = NamedSharding(mesh, P("data", None))
    x = jax.device_put(x, xs)
    params = [jax.device_put(w, NamedSharding(mesh, P())) for w in params]

    g_sh = trace(fn, params, x, mesh=mesh,
                 in_shardings=[P()] * len(params) + [P("data", None)]).graph
    budget = vanilla_peak(g_sh, liveness=False) / 2  # per-device halved

    planned = repro.plan_function(
        fn, budget, mesh=mesh, in_shardings=(None, P("data", None)),
        planner=Planner(cache=PlanCache()),
    )
    lowered = planned.lowered_for(params, x)
    assert lowered.backend == "jaxpr"
    assert lowered.plan.overhead > 0  # the per-device budget forces recompute
    assert lowered.plan.peak_memory <= budget

    got = jax.jit(lowered.run)(params, x)
    ref = jax.jit(jax.value_and_grad(fn))(params, x)
    assert _bits(got, ref)


@requires8
def test_sharded_twin_preserves_input_sharding_on_grads():
    """pjit-composability: grads w.r.t. the sharded argument come back in
    the caller's layout (with_sharding_constraint transposes to itself)."""
    mesh = _mesh8()
    fn, params, x = _mlp(batch=16)
    xs = NamedSharding(mesh, P("data", None))
    x = jax.device_put(x, xs)
    planned = repro.plan_function(
        fn, None, argnums=1, mesh=mesh,
        in_shardings=(None, P("data", None)),
        planner=Planner(cache=PlanCache()),
    )
    _, gx = jax.jit(planned.lowered_for(params, x).run)(params, x)
    assert gx.sharding.is_equivalent_to(xs, gx.ndim)
    ref = jax.jit(jax.value_and_grad(fn, argnums=1))(params, x)
    assert _bits(gx, ref[1])


@requires8
def test_check_lowering_on_concrete_mesh_twin():
    """Satellite coverage: conformance over a twin traced with a *concrete*
    8-device mesh + in_shardings — the post-SPMD planning path."""
    from repro.analysis import check_lowering
    from repro.core.lowering.carriers import TracedCarrier

    mesh = _mesh8()
    fn, params, x = _mlp(batch=16)
    carrier = TracedCarrier.trace(
        fn, (params, x), mesh=mesh,
        in_shardings=(None, P("data", None)),
    )
    g = carrier.to_graph()
    planner = Planner(cache=PlanCache())
    rep = planner.plan(g, planner.min_feasible_budget(g))
    assert rep.plan is not None
    report = check_lowering(carrier, rep.plan)
    assert report.ok, str(report.findings)


@requires8
def test_blockgraph_jaxpr_backend_sharded():
    """BlockGraph planned at equation granularity under a mesh: the traced
    carrier sees more nodes than blocks and the grads match vanilla."""
    from repro.core.blockgraph import Block, BlockGraph

    def mk_block(name, src):
        return Block(
            name=name,
            apply=lambda p, h: lax.tanh(lax.dot_general(h, p["w"], DN)),
            inputs=(src,),
            init=lambda rng, shp: {
                "w": jax.random.normal(rng, (shp[-1], shp[-1])) * 0.3
            },
            out_sharding=("batch", None),
        )

    bg = BlockGraph([mk_block(f"b{i}", "x" if i == 0 else f"b{i-1}")
                     for i in range(5)], ["x"], ["b4"])
    params = bg.init(jax.random.PRNGKey(0), {"x": (16, 8)})
    inputs = {"x": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}
    loss = lambda out: jnp.sum(out * out)

    mesh = _mesh8()
    pf = repro.plan_function(bg, None, backend="jaxpr", loss_fn=loss,
                             mesh=mesh, planner=Planner(cache=PlanCache()))
    lowered = pf.lowered_for(params, inputs)
    assert lowered.backend == "jaxpr"
    assert lowered.carrier.to_graph().n > len(bg.blocks)  # eqn granularity

    got = pf(params, inputs)
    ref = jax.value_and_grad(
        lambda p: loss(bg.apply(p, inputs))
    )(params)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(ref[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# Tier-1 wrapper: run the 8-device half in a fresh process under the flag
# (jax pins the device count at first init, so the flag cannot be set here).
# ---------------------------------------------------------------------------


def test_eight_device_suite_in_subprocess():
    if jax.device_count() >= 8:
        pytest.skip("already running under the 8-device flag")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", "--no-header",
         os.path.abspath(__file__)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, f"\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert " passed" in r.stdout
