"""Find an open-loop cell's knee once: one process, one set-up, a
handful of fixed rates, a window each.

    python3 benchmarks/sweep.py --workload gpt3-1.3b.serve-chat \
        --rates 1.5,2,2.5,3,3.5 --seconds 20

The knee is the highest rate the server sustains without a growing
backlog: requests in flight when the window ends stay near what the
slots hold, and the queue wait stays under a step or two.  The cell's
mix then fixes its rate at about four fifths of it, as a number.  This
is a tool for the PR that defines or re-centres a cell, not part of a
run: it prints a table and no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import harness
    from benchmarks.stats import percentile

    cell = harness.Cell(args.workload, args.seed, args.seconds, False,
                        args.rehearse_cpu)
    try:
        harness.claim_device(cell)
    except SystemExit as e:
        print(f"benchmarks/sweep.py: {e}", file=sys.stderr)
        return 2
    runner = harness.load_module("runners", cell.mix["runner"])
    cfg, srv, checks = runner.set_up(cell)
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            # drain in full between rates, so each starts from idle
            mix = dict(cell.mix, rate_rps=rate, drain_s=120.0)
            out = runner.measure(cell, mix, cfg, srv,
                                 harness.TraceWindow(False, 0, 0),
                                 args.seconds)
            facts, e2e = out["facts"], out["end_to_end"]
            rows.append({
                "rate_rps": rate, "due": out["attempted"],
                "failed": out["failed"],
                "in_flight_at_end": facts["in_flight_at_end"],
                "request_p50_ms": e2e.get("request_p50_ms"),
                "request_p90_ms": e2e.get("request_p90_ms"),
                "queue_wait_p90_ms": 1e3 * percentile(facts["queue_s"], 90),
                "tokens_per_s": e2e["serve_tokens_per_s"],
                "prefix_hit_share": 100.0 * facts["delta"]["prefix_hit_tokens"]
                / max(facts["delta"]["prompt_tokens"], 1),
                "late_max_ms": 1e3 * facts["late_max_s"],
                "correct": all(ok for _, ok, _ in checks + out["checks"]),
            })
            harness.say("sweep row: " + json.dumps(rows[-1]))
    finally:
        srv.shutdown(drain=False)
    print(json.dumps({"sweep": rows, "device": cell.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
