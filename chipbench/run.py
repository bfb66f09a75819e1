"""One run of one benchmark cell on the chips of this machine:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's training path from the seed, compiles it, and takes
three steps that the reference checks afterwards; then whole steps run for
``--seconds``.  With ``--trace 1`` a few more steps run under the profiler
and the per-layer metrics are read from that trace.  Once the program's
state is freed, the float32 reference repeats the three steps and decides
``correct``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, and last ``checks``: each compared number beside its limit);
the last lines of standard error give the same checks.  Without a TPU, or
with fewer chips than the cell asks for, it exits with code 2 and prints no
result.  JAX's compilation cache lives in ``.jax_cache`` at the root of the
checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None, root: Path = ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
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

    cell = harness.load_cell(root, args.workload)
    err = lambda s: print(s, file=sys.stderr, flush=True)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START, log=err)
    except harness.NoChip as e:
        err(f"chipbench: {e}")
        sys.exit(2)
    for k, v in result["checks"].items():
        err(f"check {k} = {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
