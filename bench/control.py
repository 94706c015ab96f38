#!/usr/bin/env python3
"""Readings behind a cell's correctness limit: the program's and the
control's, on many seeds in one process.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed it builds the cell (weights from the seed), serves the
cell's mix for its preroll and ``--seconds`` at the cell's own load, frees
the engine, and runs the check of ``run.py`` twice on the same sample of
finished requests: once on what the program served, and once with the
control in the program's place (the plain reference one precision step
below the configuration's: bfloat16 weights and activations for
float32).  Both go through ``run.check`` and ``run.passed``; the control
has to come out not correct.

The limit lies between the largest program reading and the smallest
control reading.  One JSON line per seed, then a summary line.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run


def readings(cell: run.Cell, seed: int, seconds: float) -> dict:
    session = run.build(cell, seed)
    win = run.serve(session, seconds=seconds, traced=False)
    params = session.params
    session.engine = None
    del session
    gc.collect()
    out = {"seed": seed}
    for side in ("program", "control"):
        checks = run.check(cell, params, win, seed,
                           control=side == "control")
        out[side] = checks["served_logit_gap4"]["value"]
        out[f"{side}_correct"] = run.passed(checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.Layout().cell(args.workload)
    run.require_chips(cell.chips)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.enable_cache()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        rows.append(readings(cell, seed, args.seconds))
        gc.collect()
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "program_highest": max(r["program"] for r in rows),
                      "control_lowest": min(r["control"] for r in rows),
                      "limit": cell.checks["served_logit_gap4"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
