"""Where one cell's step spends its time, by the program's own names:

    python3 chipbench/steptrace.py --workload <name> --seed <n> [--seconds 10]

Builds the cell's training path as ``run.py`` does, takes the three set-up
steps and an untraced window of ``--seconds``, then the cell's
``trace_steps`` under the profiler.  The last line of standard output is one
JSON object:

* ``phase_ms``: device ms per step of forward, backward, recompute,
  optimizer and other (``phases.py``), and ``ops_ms``, their sum;
* ``input_wait_ms`` and ``compiles``: ``Trainer.run``'s own ``input_seconds``
  (mean per step) and ``compiles`` (summed) over the untraced window;
* ``traced_input_wait_ms``: mean ``repro.train.next_batch`` + ``put_batch``
  per traced step;
* ``idle_ms_per_gap``: the device's idle stretches of 0.1 ms or more in the
  traced window, each ms put down to the program span the host was in;
* ``tokens_per_s`` of the untraced window and of the traced steps, both
  from the feed's stamps, and the cost of tracing between them.

No reference runs: this shows where the time goes and decides no
``correct``.  Without a TPU it exits with code 2.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def rate(stamps, tokens):
    """Tokens per second over the whole intervals between feed stamps."""
    return (len(stamps) - 1) * tokens / (stamps[-1] - stamps[0])


def main(argv=None, root: Path = ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    for p in (ROOT / "src", ROOT / "chipbench"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import devtrace
    import harness
    import phases

    # the trace keeps the program's spans beside the benchmark's
    devtrace.SPAN_PREFIX = ("chipbench.", phases.PROGRAM_SPAN)
    cell = harness.load_cell(root, args.workload)
    try:
        devices = harness.chips(cell.cell["chips"])
    except harness.NoChip as e:
        print(f"steptrace: {e}", file=sys.stderr)
        sys.exit(2)
    prog = harness.Program(cell, args.seed, devices)
    outs = []
    run = prog.trainer.run
    prog.trainer.run = lambda batches: outs.append(run(batches)) or outs[-1]
    prog.first_steps()
    n0, s0 = len(outs), len(prog.feed.stamps)
    win = harness.run_window(prog, args.seconds)
    window = outs[n0:]
    t = cell.traffic
    tokens = t["batch"] * t["seq_len"]
    untraced = rate(prog.feed.stamps[s0:] + [win["t_end"]], tokens)
    s1 = len(prog.feed.stamps)
    tr = harness.traced_window(prog, t["trace_steps"])
    traced = rate(prog.feed.stamps[s1:], tokens)

    known = phases.op_phases(prog.compiled.as_text())
    lo, hi = tr.lo, tr.hi
    steps = sum(1 for n, s, _ in tr.spans if n == "repro.train.step" and lo <= s < hi)
    inp = sum(d for n, s, d in tr.spans if lo <= s < hi
              and n in ("repro.train.next_batch", "repro.train.put_batch"))
    result = {
        "workload": cell.name, "seed": args.seed, "steps": len(window),
        "trace_steps": steps,
        "input_wait_ms": 1e3 * sum(sum(o["input_seconds"]) for o in window) / len(window),
        "compiles": sum(o["compiles"] for o in window),
        "traced_input_wait_ms": inp * 1e-6 / max(1, steps),
        "tokens_per_s": untraced, "traced_tokens_per_s": traced,
        "tracing_cost": 1 - traced / untraced,
    }
    if tr.devices and steps:
        times = [phases.phase_time(ev, known, lo, hi) for ev in tr.devices.values()]
        result["phase_ms"] = {k: sum(x[k] for x in times) / len(times) / steps * 1e-6
                              for k in phases.PHASES}
        result["ops_ms"] = sum(result["phase_ms"].values())
        split = phases.idle_split(next(iter(tr.devices.values())), tr.spans, lo, hi, 1e5)
        gaps = split.pop("gaps")
        result["idle_gaps"] = int(gaps)
        result["idle_ms_per_gap"] = {k: v * 1e-6 / gaps for k, v in split.items()} if gaps else {}
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
