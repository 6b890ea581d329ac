#!/usr/bin/env python3
"""Readings for the logit-gap limit: the program's and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a,b,...>

For each seed, one process serves the cell exactly as ``run.py`` does
(``run.run_cell`` with ``control=True``), with a shorter window at the
cell's own load, and reads on the same sampled requests:

* ``program``: the widest gap of a served token under the float32
  reference (what ``run.py`` compares against the limit);
* ``control``: the widest gap, under the float32 reference, of the token
  that the reference computed in float8 (e4m3) puts first, the step below
  the bfloat16 the configuration states.

One JSON line per seed goes to stdout.  The limit is set between the
largest program reading and the smallest control reading (PERF.md gives
both).  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def readings(workload: str, seconds: float, seeds: list) -> list:
    import run

    bench, cell, cfg, mix = run.load_cell(workload)
    out = []
    for seed in seeds:
        res = run.run_cell(bench, cell, cfg, mix, seed, seconds, False,
                           control=True)
        out.append({
            "workload": workload, "seed": seed,
            "program": res["checks"]["logit_gap"]["value"],
            "control": res["control"]["control_gap"],
            "control_mismatch_share": res["control"]["control_mismatch_share"],
            "faults": {k: v["value"] for k, v in res["checks"].items()
                       if k != "logit_gap"},
            "checked_tokens": res["diagnostics"]["checked_tokens"],
        })
        print(json.dumps(out[-1]), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args()
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    readings(args.workload, args.seconds,
             [int(s) for s in args.seeds.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
