"""Smoke run of the training path on a TPU: ``python chip_smoke.py [--chips 4]``.

One process drives ``repro.launch.train.main`` — config → DP remat plan →
``Trainer`` — on the chip at the published widths of ``stablelm-3b``
(d_model 2560, 32 heads of 80, d_ff 6912, vocab 50304) with only the depth
cut (``--layers``), at seq 2048 so the Pallas flash-attention kernel is on
the path.  Weights are random, made from a fixed seed.

Default (one chip):
  1. the flash kernel, compiled, against the plain-jnp reference
     (``repro.kernels.ref``) at the model's head shape: output and gradients;
  2. a few training steps: every loss finite, and the compiled step holds
     ``tpu_custom_call`` (the kernel ran, no XLA fallback took its place);
  3. the plan beside the device's own memory counters.

``--chips 4``: only the sharded path and what it is compared with — the
same trainer on a 2x2 ("data", "model") mesh, then on one chip with the
same seed and batch; first-step losses must agree within a bf16 tolerance,
and every device must report its share of the peak.

Per-step seconds printed here are a smoke reading, not a benchmark.  Exits
non-zero, without a result line, when no TPU is found or any phase fails.
The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "stablelm-3b"
#: 4 of 32 layers (≈575M parameters, 9.2 GB of f32 weights, gradients and
#: Adam moments).  Batch 2 x seq 2048: the described-v5e compile refuses
#: batch 4 (17.13G of 15.75G HBM) and batch 3 leaves no headroom.
LAYERS, SEQ, BATCH, STEPS = 4, 2048, 2, 3
#: first-step loss, one chip vs the 2x2 mesh: relative, one bf16 ulp at 1.0
LOSS_RTOL = 2.0 ** -7


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _train(extra):
    from repro.launch import train

    argv = ["--arch", ARCH, "--layers", str(LAYERS), "--seq", str(SEQ),
            "--batch", str(BATCH), "--steps", str(STEPS), *extra]
    print(f"train: {' '.join(argv)}", flush=True)
    out = train.main(argv)
    losses = out["losses"]
    if len(losses) != STEPS or not all(math.isfinite(x) for x in losses):
        _fail(f"losses {losses}: expected {STEPS} finite values")
    print("step seconds (smoke reading, not a benchmark): "
          + ", ".join(repr(t) for t in out["step_seconds"]), flush=True)
    return out


def _kernel_vs_reference():
    """The compiled kernel at the model's head shape against kernels.ref."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import attention_ref

    B, S, H, D = 1, 512, 32, 80
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(r, (B, S, H, D), jnp.bfloat16) for r in ks)
    to_bhsd = lambda x: x.transpose(0, 2, 1, 3)

    def loss_kernel(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

    def loss_ref(q, k, v):
        o = to_bhsd(attention_ref(to_bhsd(q), to_bhsd(k), to_bhsd(v)))
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

    grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))
    gk, ok = grad(loss_kernel)(q, k, v)
    gr, orf = grad(loss_ref)(q, k, v)
    for name, a, b in [("out", ok, orf), ("dq", gk[0], gr[0]),
                       ("dk", gk[1], gr[1]), ("dv", gk[2], gr[2])]:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        print(f"kernel vs reference: {name} max|diff|/max|ref| = {err!r}",
              flush=True)
        if not (err <= 2e-2):
            _fail(f"flash kernel {name} disagrees with kernels.ref ({err})")


def _compiled_step_text(out):
    import jax

    tr = out["trainer"]
    spec = jax.ShapeDtypeStruct((BATCH, SEQ), jax.numpy.int32)
    with jax.sharding.set_mesh(tr.mesh):
        return tr.lower({"tokens": spec, "labels": spec}).compile().as_text()


def _memory_line(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"{dev}: peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_in_use={st.get('bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")


def one_chip() -> None:
    import jax

    _kernel_vs_reference()
    out = _train(["--devices", "1"])
    if "tpu_custom_call" not in _compiled_step_text(out):
        _fail("the compiled train step holds no tpu_custom_call: "
              "attention did not run the Pallas kernel")
    print("compiled step: tpu_custom_call present", flush=True)
    plan, cfg = out["plan"], out["config"]
    static = cfg.num_params() * 16  # f32 weights, gradients, Adam mu and nu
    print(f"memory: plan activation peak {plan['peak_bytes']:.0f} B, budget "
          f"{plan['budget_bytes']:.0f} B, analytic static {static} B; device "
          + _memory_line(jax.devices()[0]), flush=True)


def four_chips() -> None:
    import gc

    import jax

    if len(jax.devices()) < 4:
        _fail(f"--chips 4 needs four devices, found {len(jax.devices())}")
    sharded = _train(["--model-axis", "2"])
    if "tpu_custom_call" not in _compiled_step_text(sharded):
        _fail("the sharded train step holds no tpu_custom_call")
    peaks = []
    for dev in jax.devices()[:4]:
        print("memory (2x2 mesh): " + _memory_line(dev), flush=True)
        peaks.append((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    # state piled on one device shows as a device with no real share, or as
    # one that peaks far above the others
    if min(peaks) < 2 ** 30 or max(peaks) > 2 * min(peaks):
        _fail(f"per-device peaks {peaks}: the state is not evenly sharded")
    sharded_losses = sharded["losses"]
    del sharded
    gc.collect()
    single = _train(["--devices", "1"])
    a, b = sharded_losses[0], single["losses"][0]
    rel = abs(a - b) / abs(b)
    print(f"first-step loss: 2x2 mesh {a!r}, one chip {b!r}, relative "
          f"difference {rel!r} (tolerance {LOSS_RTOL!r})", flush=True)
    if not rel <= LOSS_RTOL:
        _fail("the sharded first-step loss disagrees with the one-chip run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found (JAX sees {devices[0].platform}); "
              "this smoke run never falls back to the CPU")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    kind = devices[0].device_kind
    print(f"device: {kind} x {len(devices)}", flush=True)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
