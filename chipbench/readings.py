"""Readings that set a cell's limits, in one process on the chip.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 2 [--control reference|altered]

For each seed: the cell's set-up, a window of ``--seconds``, then the
comparison with the reference, as a run makes it; prints one JSON line
per seed with the numbers compared.  ``--control reference`` puts the
reference at ``bf16_3x`` in the program's place, ``--control altered``
alters one element of every answer (``chipbench.controls``).  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import common, controls, manifest, run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", choices=("reference", "altered"))
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    run.configure(cell)
    kind = manifest.kind_module(cell.kind)
    patch = {"reference": controls.reference_in_place,
             "altered": controls.altered_answer}.get(args.control)
    with patch() if patch else contextlib.nullcontext():
        for seed in (int(s) for s in args.seeds.split(",")):
            work = kind.build(cell, seed, common.NoSpans())
            window = work.window(args.seconds)
            work.release()
            checks = work.check()
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": args.control,
                              "attempted": window["attempted"],
                              "failed": window["failed"],
                              "metrics": window["metrics"],
                              "checks": checks}), flush=True)


if __name__ == "__main__":
    main()
