#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds <s>]

For each seed it prints one JSON line with the number the cell compares
(``program``) and the same number for the control (``control``): the
cell's reference computed one precision step below what the configuration
states, put in the program's place.  The readings are the runner's
(``control_readings`` in ``chipbench/runners/<runner>.py``): overlay cells
run a short window at the cell's own load and compare the sampled
requests, against the kernels' oracle in bfloat16 (the configuration
states float32); decode cells serve one whole wave per seed through the
timed path and compare the served tokens, against the reference with
every matrix product's operands in float8 (the configuration states
bfloat16), read at the token it puts first.  The benchmark's own runs
never run this.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seeds, seconds, require_chip=True, root=ROOT):
    """The cell's runner's readings of the program and the control."""
    from chipbench import harness
    return harness.runner(cell["traffic_data"]["runner"], root) \
        .control_readings(cell, seeds, seconds, require_chip)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    from chipbench import harness
    harness.enable_compile_cache()
    cell = harness.cell_spec(harness.spec(), args.workload)
    for r in readings(cell, seeds, args.seconds):
        print(json.dumps(dict(workload=args.workload, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
