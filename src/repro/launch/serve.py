"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Spins up the slot-based continuous-batching engine on a (reduced) model and
drives a batch of synthetic requests through it, reporting throughput.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Engine


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan-cache-dir", default=None,
                    help="shared on-disk recomputation-plan cache")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    kw = {}
    if cfg.encoder_decoder:
        kw["frames"] = jax.random.normal(
            jax.random.PRNGKey(1), (args.slots, cfg.frontend_seq, cfg.d_model)
        )
    eng = Engine(model, params, max_slots=args.slots, max_seq=args.max_seq,
                 plan_cache_dir=args.plan_cache_dir, **kw)

    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size, size=rng.integers(4, 32)).tolist()
        eng.submit(prompt, max_new_tokens=args.new_tokens,
                   temperature=args.temperature)

    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s)")
    return done


if __name__ == "__main__":
    main()
