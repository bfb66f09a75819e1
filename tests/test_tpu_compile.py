"""The main-path kernels compiled for a described TPU v5e (no chip needed).

Interpret mode runs the kernel bodies on the CPU but never asks Mosaic:
block shapes it refuses, 1-D vector layouts and kernels that GSPMD cannot
partition only show when the TPU compiler itself is run.  The topology is
described inside a fixture — never at import — so that under several test
workers only the one given this file loads the TPU library.
"""

import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from helpers import kernel_reader, phase_reader
from repro.kernels.ops import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: a compile for a
    described device is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (B, S, H, KV, D): stablelm-3b's heads, qwen2.5-14b's GQA heads, and the
# benchmark cells' own attention (batch 2 of stablelm-3b)
SHAPES = {"d80_h32": (1, 2048, 32, 32, 80), "gqa_d128_h40_kv8": (1, 2048, 40, 8, 128),
          "d80_h32_b2": (2, 2048, 32, 32, 80)}


def _fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _fwd_bwd(q, k, v):
    loss = lambda *a: jnp.sum(_fwd(*a).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("fn", [_fwd, _fwd_bwd], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_flash_attention_compiles_for_v5e(one_chip, shape, fn):
    B, S, H, KV, D = shape
    sds = lambda h: jax.ShapeDtypeStruct((B, S, h, D), jnp.bfloat16,
                                         sharding=one_chip)
    compiled = jax.jit(fn).lower(sds(H), sds(KV), sds(KV)).compile()
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    names = ["flash_fwd"] + (["flash_dq", "flash_dkv"] if fn is _fwd_bwd else [])
    # one kernel of each kind per attention, each told apart by its results
    # as the benchmark's kernel reader tells them apart
    assert len(kernels) == len(names)
    for name in names:
        assert sum(name in ln for ln in kernels) == 1, name
    kinds = kernel_reader().kernel_kinds(text)
    assert Counter(kinds.values()) == Counter(n[len("flash_"):] for n in names)
    heads = [ln.split("custom-call(")[0].split("=", 1)[1] for ln in kernels]
    results = {re.search(r"flash_(fwd|dq|dkv)", ln).group(1):
               re.findall(r"(\w+)\[([\d,]*)\]", head)
               for ln, head in zip(kernels, heads)}
    full = ",".join(map(str, (B, H, S, D)))
    assert results["fwd"] == [("bf16", full), ("f32", f"{B},{H},{S},1")]
    if fn is _fwd_bwd:
        assert results["dq"] == [("bf16", full)]
        assert results["dkv"] == [("bf16", full)] * 2


def _model_fwd_bwd(q, k, v):
    def loss(*a):
        with jax.named_scope("model"):
            return jnp.sum(_fwd(*a).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_flash_kernels_fall_in_their_phases(one_chip, shape):
    """Under the Trainer's ``model`` scope the forward kernel reads as
    forward and dq/dk/dv as backward, by the benchmark's phase reader."""
    phases = phase_reader()
    B, S, H, KV, D = shape
    sds = lambda h: jax.ShapeDtypeStruct((B, S, h, D), jnp.bfloat16,
                                         sharding=one_chip)
    text = jax.jit(_model_fwd_bwd).lower(sds(H), sds(KV), sds(KV)).compile().as_text()
    by_kernel = {}
    for ln in text.splitlines():
        if "tpu_custom_call" in ln:
            kernel = re.search(r"flash_(fwd|dq|dkv)", ln).group(0)
            by_kernel.setdefault(kernel, set()).update(phases.op_phases(ln).values())
    assert by_kernel["flash_fwd"] == {"forward"}
    assert by_kernel["flash_dq"] == by_kernel["flash_dkv"] == {"backward"}
