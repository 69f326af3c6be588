#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

One process runs the cell once per seed, as ``bench/run.py`` does, with
the control on (``run_cell(..., control=True)``): after the comparison
of the program's statements with the reference, the control (the
reference with float32 sums, ``bench/replay.py``) is compared with the
reference in the same way.  Each seed prints one JSON line: the
program's differing statements (the lower reading) and the control's
(the upper reading).  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import NoChip, run_cell

    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = run_cell(args.workload, seed, args.seconds, False,
                         control=True)
        except NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps({
            "seed": seed,
            "correct": r["correct"],
            "program_differing": r["checks"]["statements_differing"]["value"],
            "control_differing": r["control"]["statements_differing"],
            "compared": r["control"]["statements_compared"],
            "metrics": r["metrics"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
