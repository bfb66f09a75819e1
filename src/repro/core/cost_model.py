"""Measured per-op cost model: profiled T_v instead of FLOP proxies.

§3 of the paper: "We can either directly measure T_v … or use some form of
approximation."  The seed repo only approximated (10/1 for heavy/light, or
analytic FLOPs); this module *measures*.  It times three representative op
classes on the current backend — a matmul (the ``dot_general`` family), the
Pallas flash-attention kernel from ``repro.kernels`` (interpret mode off-TPU,
compiled on TPU), and a memory-bound elementwise chain — and distills them
into throughput rates:

* ``sec_per_flop_matmul``     — compute-bound ops priced by their FLOPs;
* ``sec_per_flop_attention``  — attention-kind nodes (the recompute-in-bwd
  kernel has a different achieved-FLOP rate than a plain matmul);
* ``sec_per_byte_elementwise``— everything else priced by its output bytes
  (memory-bound on every backend).

``calibrated_graph`` maps a FLOP-carrying graph (``jaxpr_graph`` with
``cost_model="flops"``, or ``launch.plan.chain_graph`` whose interior nodes
carry unit FLOPs) to measured seconds, then feeds the result through
``dp.quantize_times`` — giving the DP an integer t-axis whose *ratios* are
hardware-true rather than FLOP-proportional.  Profiles are content-addressed
on disk (backend + JAX version) via the same atomic-JSON machinery as the
plan cache, so a process profiles at most once per backend, ever.

Sharded graphs price **per shard**: a carrier traced under a mesh
(``core.jaxpr_graph`` with ``mesh=``) emits per-shard FLOPs in ``time`` for
compute-bound kinds (a matmul/attention output split k ways costs each
device 1/k of the global work) and per-device bytes in ``memory`` for
everything else — so ``node_seconds`` below yields per-device seconds with
no sharding-specific branch here, and the DP trades one accelerator's time
against one accelerator's memory, exactly the paper's single-device budget
semantics lifted onto a mesh.

Calibration deliberately changes ``T_v`` and therefore the graph digest
(``core.graph.graph_digest``): plans cached under a FLOP cost model and
plans cached under a measured profile never alias, and re-profiling on new
hardware invalidates old plans by construction.

Not meaningful for the paper's abstract {1, 10} cost graphs — those already
*are* a (coarse) measured model; calibration is for production graphs whose
``time`` field carries FLOPs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Sequence

from .dp import quantize_times
from .graph import Graph, Node
from .prims import ATTENTION_KINDS, MATMUL_KINDS  # shared tables (core.prims)

# Host-link (PCIe-gen4-x16-class) and int8 block-codec throughputs pricing
# the "offload"/"quantize" storage strategies.  Defined in core.strategies
# (import-light) and re-exported here as the cost-model surface; a measured
# OpProfile can override them per backend.
from .strategies import (  # noqa: F401  (re-export)
    DEFAULT_HOST_BYTES_PER_SEC,
    DEFAULT_QUANTIZE_BYTES_PER_SEC,
)

PROFILE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class OpProfile:
    """Measured throughput rates for one backend (seconds per unit work)."""

    sec_per_flop_matmul: float
    sec_per_flop_attention: float
    sec_per_byte_elementwise: float
    backend: str = "unknown"
    jax_version: str = "unknown"
    #: Host-link (PCIe/ICI) bandwidth for offloaded residuals; defaulted so
    #: profiles serialized before the strategy lattice existed still load.
    host_bytes_per_sec: float = DEFAULT_HOST_BYTES_PER_SEC
    #: int8 block-codec throughput for quantized residuals.
    quantize_bytes_per_sec: float = DEFAULT_QUANTIZE_BYTES_PER_SEC
    #: Where the rates came from: "measured" (microbenchmarks, the default),
    #: "analytic" (DEFAULT_PROFILE's roofline constants), or "compiled"
    #: (XLA cost_analysis per-segment numbers, see
    #: ``compiled_calibrated_graph``).  Non-measured sources are suffixed
    #: into ``profile_key`` so differently-sourced calibrations never share
    #: a cache identity.
    source: str = "measured"

    def profile_key(self) -> str:
        base = f"{self.backend}-{self.jax_version}-v{PROFILE_VERSION}"
        return base if self.source == "measured" else f"{base}-{self.source}"


#: Analytical fallback (rough TPU-v5e-class numbers) used when profiling is
#: disabled or fails — keeps calibration total-order-correct without timing.
DEFAULT_PROFILE = OpProfile(
    sec_per_flop_matmul=1.0 / 100e12,
    sec_per_flop_attention=1.0 / 50e12,
    sec_per_byte_elementwise=1.0 / 500e9,
    backend="analytic",
    jax_version="-",
    source="analytic",
)


def _median(xs: Sequence[float]) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _time_call(fn: Any, *args: Any, repeats: int = 3) -> float:
    """Median wall time of ``fn(*args)`` with warmup (jit compile excluded)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # warmup / compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return max(_median(ts), 1e-9)


def profile_ops(
    matmul_dim: int = 512,
    elem_elems: int = 1 << 22,
    attn_shape: tuple = (1, 128, 2, 32),
    repeats: int = 3,
) -> OpProfile:
    """Time representative ops on the current backend and fit the rates.

    Shapes are deliberately small: this runs inside tests and cold starts.
    On CPU the flash-attention kernel runs in Pallas interpret mode — the
    same kernel body, so the measured ratio is still the right *relative*
    signal, which is all the DP consumes after quantization.
    """
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    key = jax.random.PRNGKey(0)

    # --- matmul: 2·n³ FLOPs --------------------------------------------------
    a = jax.random.normal(key, (matmul_dim, matmul_dim), jnp.float32)
    b = jax.random.normal(key, (matmul_dim, matmul_dim), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    t_mm = _time_call(mm, a, b, repeats=repeats)
    sec_per_flop_mm = t_mm / (2.0 * matmul_dim**3)

    # --- elementwise chain: memory-bound, ~4 passes over the array -----------
    x = jax.random.normal(key, (elem_elems,), jnp.float32)
    ew = jax.jit(lambda v: jnp.tanh(v * 1.5 + 0.5) * v)
    t_ew = _time_call(ew, x, repeats=repeats)
    sec_per_byte = t_ew / (4.0 * elem_elems * 4)

    # --- attention kernel: a kernel that fails to run raises, rather than
    # leave the DP a rate nobody measured -------------------------------------
    from repro.kernels import flash_attention

    B, S, H, D = attn_shape
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    fa = jax.jit(lambda qq: flash_attention(qq, qq, qq, causal=True))
    t_fa = _time_call(fa, q, repeats=max(1, repeats - 1))
    attn_flops = 4.0 * B * H * S * S * D  # qk^T + pv
    sec_per_flop_attn = t_fa / attn_flops

    return OpProfile(
        sec_per_flop_matmul=float(sec_per_flop_mm),
        sec_per_flop_attention=float(sec_per_flop_attn),
        sec_per_byte_elementwise=float(sec_per_byte),
        backend=backend,
        jax_version=jax.__version__,
    )


# ---------------------------------------------------------------------------
# Disk-cached profiles (one timing run per backend, ever).
# ---------------------------------------------------------------------------


def _profile_path(cache_dir: str, backend: str, jax_version: str) -> str:
    import os

    name = f"op_profile_{backend}_{jax_version}_v{PROFILE_VERSION}.json"
    return os.path.join(cache_dir, "profiles", name.replace("/", "_"))


def load_or_profile(
    cache_dir: Optional[str] = None, profiler: Any = profile_ops
) -> OpProfile:
    """Load the backend's profile from ``cache_dir`` or measure and store it.

    With ``cache_dir=None`` the plan cache's directory is used when attached
    (so plans and the profile that priced them live side by side); without
    either, the profile is measured fresh (still just a few hundred ms).
    """
    import jax

    from repro.checkpointing.store import atomic_write_json, read_json

    from .plan_cache import default_cache

    cache_dir = cache_dir or default_cache().cache_dir
    backend, version = jax.default_backend(), jax.__version__
    path = _profile_path(cache_dir, backend, version) if cache_dir else None

    if path:
        raw = read_json(path)
        if raw and raw.get("version") == PROFILE_VERSION:
            try:
                return OpProfile(
                    sec_per_flop_matmul=float(raw["sec_per_flop_matmul"]),
                    sec_per_flop_attention=float(raw["sec_per_flop_attention"]),
                    sec_per_byte_elementwise=float(raw["sec_per_byte_elementwise"]),
                    backend=str(raw["backend"]),
                    jax_version=str(raw["jax_version"]),
                    source=str(raw.get("source", "measured")),
                )
            except (KeyError, TypeError, ValueError):
                pass  # torn/stale file → re-profile

    prof = profiler()
    if path:
        try:
            atomic_write_json(
                path, {"version": PROFILE_VERSION, **dataclasses.asdict(prof)}
            )
        except OSError:
            pass  # unusable store → just re-profile next process
    return prof


# ---------------------------------------------------------------------------
# Applying a profile to a graph.
# ---------------------------------------------------------------------------


def node_seconds(nd: Node, profile: OpProfile) -> float:
    """Calibrated wall-clock estimate for one node.

    Compute-bound kinds read FLOPs from ``time``; all other kinds are priced
    memory-bound from their output bytes (``memory``).  The floor keeps
    Graph's positive-cost invariant.
    """
    if nd.kind in MATMUL_KINDS:
        sec = nd.time * profile.sec_per_flop_matmul
    elif nd.kind in ATTENTION_KINDS:
        sec = nd.time * profile.sec_per_flop_attention
    else:
        sec = nd.memory * profile.sec_per_byte_elementwise
    return max(sec, 1e-12)


def measured_times(g: Graph, profile: OpProfile) -> Graph:
    """New graph with ``T_v`` = calibrated seconds (topology/memory kept)."""
    nodes = [
        Node(nd.idx, nd.name, node_seconds(nd, profile), nd.memory, nd.kind,
             must_store=nd.must_store)
        for nd in g.nodes
    ]
    return Graph(nodes, g.edges,
                 cost_source=f"profile:{profile.profile_key()}")


def calibrated_graph(g: Graph, profile: OpProfile, levels: int = 64) -> Graph:
    """Measured seconds → integer DP t-axis (``dp.quantize_times``).

    This is the drop-in replacement for ``quantize_times(flop_graph)``: same
    output contract (small positive integer ``T_v``), hardware-true ratios.
    """
    return quantize_times(measured_times(g, profile), levels=levels)


# ---------------------------------------------------------------------------
# Compiled-cost calibration (XLA cost_analysis instead of microbenchmarks).
# ---------------------------------------------------------------------------


def roofline_seconds(flops: float, nbytes: float, profile: OpProfile) -> float:
    """Roofline wall-clock estimate: max of compute and memory time."""
    return max(
        flops * profile.sec_per_flop_matmul,
        nbytes * profile.sec_per_byte_elementwise,
        1e-12,
    )


def compiled_calibrated_graph(
    g: Graph,
    plan: Any,
    seg_costs: Sequence[Dict[str, float]],
    profile: Optional[OpProfile] = None,
    levels: int = 64,
) -> Graph:
    """Re-price ``T_v`` from XLA's own per-segment FLOPs / bytes-accessed.

    ``seg_costs`` is ``analysis.hlo.extract_segment_costs`` output: one
    ``{"flops", "bytes"}`` dict per ``plan.segments`` entry, measured by
    compiling each segment's sub-jaxpr in isolation and asking
    ``compiled.cost_analysis()`` — compiler truth after fusion and
    simplification, which analytic FLOP counting cannot see.  Each segment's
    roofline seconds are distributed over its nodes proportionally to their
    analytic ``T_v`` (compiler truth at segment granularity, analytic ratios
    within), then quantized for the DP.  The result carries
    ``cost_source="compiled:<profile key>"`` so compiled-calibrated plans
    never collide with flops- or microbenchmark-priced ones in the plan
    cache.
    """
    if profile is None:
        profile = dataclasses.replace(DEFAULT_PROFILE, source="compiled")
    secs = list(g.time_v)
    for seg, cost in zip(plan.segments, seg_costs):
        seg_sec = roofline_seconds(
            float(cost.get("flops", 0.0)), float(cost.get("bytes", 0.0)), profile
        )
        total = sum(g.time_v[v] for v in seg.nodes) or 1.0
        for v in seg.nodes:
            secs[v] = max(seg_sec * (g.time_v[v] / total), 1e-12)
    nodes = [
        Node(nd.idx, nd.name, secs[nd.idx], nd.memory, nd.kind,
             must_store=nd.must_store)
        for nd in g.nodes
    ]
    priced = Graph(nodes, g.edges,
                   cost_source=f"compiled:{profile.profile_key()}")
    return quantize_times(priced, levels=levels)
