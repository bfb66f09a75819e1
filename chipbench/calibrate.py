"""The readings the limits of ``correct`` are set from, for one cell, in one
process on the chip:

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,... [--controls 3]

For every seed: the program's set-up and first three steps, then the
float32 reference, and the four numbers between them (the lower readings).
For the first ``--controls`` seeds also the control, the reference in float8
put in the program's place, and the half-batch fault, the reference on the
first half of every batch (the upper readings); the float32 reference runs
once against each, so that grad_error holds each one's first gradient
against its own.  A state left unchanged
reads 1 on grad_gap and change_gap by construction and needs no run.
Every row is also judged by ``harness.judge`` against the cell's committed
limits, as a run would judge it.  One JSON line per seed and kind on
standard output.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None, root: Path = ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # no size cap: capped, the cache's eviction reads a stamp file per entry,
    # and one missing stamp makes every later write fail
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    for p in (ROOT / "src", ROOT / "chipbench"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import harness
    import reference
    import weights

    c = harness.load_cell(root, args.workload)
    devices = harness.chips(c.cell["chips"])
    opt, B = c.traffic["optimizer"], c.traffic["batch"]
    out = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        prog = harness.Program(c, seed, devices)
        first = prog.first_steps()
        prog.free()
        key = weights.seed_key(seed)
        rows = [("program", first)]
        if i < args.controls:
            rows.append(("control_fp8", reference.run(c.m, opt, key, prog.batches, "fp8",
                                                      keep_first=True)))
            rows.append(("fault_half_batch", reference.run(
                c.m, opt, key, prog.batches, rows=slice(0, B // 2), keep_first=True)))
        for kind, got in rows:
            ref = reference.run(c.m, opt, key, prog.batches, against=got.pop("first_grad"))
            readings = harness.gaps(got, ref)
            correct, _ = harness.judge(readings, c.limits)
            line = {"workload": c.name, "seed": seed, "kind": kind, **readings,
                    "correct": correct, "losses": got["losses"],
                    "reference_losses": ref["losses"]}
            out.append(line)
            print(json.dumps(line), flush=True)
    return out


if __name__ == "__main__":
    main()
