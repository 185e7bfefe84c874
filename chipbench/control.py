"""The lower-precision control of a cell's check, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13

For each seed, the cell's plain reference is put in the program's place
and computed in the precision below the configuration's (the driver
module's `control`), then compared as a run's check compares.  Each
reading must fail the check's limit.  Prints one JSON line per seed.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = os.path.dirname(HERE)

from chipbench.harness import Refused  # noqa: E402
from chipbench.run import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        for seed in args.seeds:
            _, driver_mod, ctx = load_cell(args.workload, seed)
            reading = driver_mod.control(ctx)
            limits = ctx.traffic["limits"]
            print(json.dumps({"seed": seed, "control": reading, "limits": limits}),
                  flush=True)
    except Refused as e:
        print(f"chipbench control: {e}; nothing was run", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
