"""Run one cell of BENCHMARK.json once, in this process, on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

builds the model on the device from the seed, warms the cell's own
shapes, measures for `--seconds`, shuts everything down and prints one
JSON object as the last line of standard output (everything else goes
to standard error).  Any platform but a TPU whose kind is in
`peaks.json`, or fewer chips than the cell asks for, is exit code 2 and
no result.

`--rehearse-cpu FILE` walks the same code at the toy sizes the data
files give under "rehearsal", on whatever backend JAX has but a TPU.
It prints no result line; the would-be object goes to FILE, marked as
a rehearsal.  It proves nothing about the chip.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None, t_process=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", metavar="FILE", default=None)
    args = ap.parse_args(argv)

    from benchmarks import harness

    if t_process is None:
        t_process = time.perf_counter()
    cell = harness.Cell(args.workload, args.seed, args.seconds, args.trace,
                        args.rehearse_cpu is not None)
    try:
        harness.claim_device(cell)
    except SystemExit as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    runner = harness.load_module("runners", cell.mix["runner"])
    # the mix says which seconds of the window to profile; a window
    # shorter than that is profiled at its end
    trace_s = min(float(cell.mix.get("trace_s", 3.0)), cell.seconds)
    tracer = harness.TraceWindow(
        cell.trace,
        min(float(cell.mix.get("trace_start_s", 0.0)),
            cell.seconds - trace_s),
        trace_s)
    outcome = runner.run(cell, tracer)
    out = harness.result(cell, outcome, outcome.pop("capture", None),
                         t_process)
    if args.rehearse_cpu is not None:
        with open(args.rehearse_cpu, "w") as f:
            json.dump({"rehearsal": True, "result": out}, f)
        harness.say("rehearsal finished: NOT a chip result")
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
