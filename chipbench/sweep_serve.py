"""Find the knee of a serve cell once, by a sweep of client counts on the
chip.

    python3 chipbench/sweep_serve.py --workload <serve cell> --seed <n> \
        --clients 50,100,200 --seconds 10 --out <file.json>

One set-up, then one window per client count, each as a run measures
it: the decision latency's p50 and p95, the p95 of each half of the
window (a second half above the first is a growing backlog), how late
the generator sent, the mean micro-batch and failures.  The knee is the
highest count whose p95 stays within ``--budget-ms`` (the paper's 100 ms
decision budget) without a growing backlog; the cell runs at four fifths
of it.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import common, manifest, run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--clients", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--budget-ms", type=float, default=100.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    devices = run.configure(cell)
    work = manifest.kind_module(cell.kind).build(cell, args.seed,
                                                 common.NoSpans())
    points = []
    try:
        for n in (int(c) for c in args.clients.split(",")):
            work.deal(n)
            w = work.window(args.seconds)
            c = w["counters"]
            point = {"clients": n, "offered_per_s": n * work.rate,
                     "p95_ms": c["p95_ms"],
                     "p50_ms": w["metrics"]["decision_p50_ms"],
                     "p95_first_half_ms": c["p95_first_half_ms"],
                     "p95_second_half_ms": c["p95_second_half_ms"],
                     "gen_lag_p95_ms": c["gen_lag_p95_ms"],
                     "batch_mean": sum(c["batches"]) / max(1, len(
                         c["batches"])),
                     "requests": w["attempted"], "failed": w["failed"]}
            point["within_budget"] = (
                w["failed"] == 0 and point["p95_ms"] <= args.budget_ms
                and point["p95_second_half_ms"]
                <= 1.5 * point["p95_first_half_ms"] + 1.0)
            print(json.dumps(point), flush=True)
            points.append(point)
    finally:
        work.release()
    ok = [p["clients"] for p in points if p["within_budget"]]
    knee = max(ok) if ok else None
    record = {"workload": cell.name, "seed": args.seed,
              "seconds": args.seconds, "budget_ms": args.budget_ms,
              "device": devices[0].device_kind, "points": points,
              "knee_clients": knee,
              "cell_clients": None if knee is None else int(0.8 * knee)}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(record, indent=1) + "\n")
    print(json.dumps({k: v for k, v in record.items() if k != "points"}))


if __name__ == "__main__":
    main()
