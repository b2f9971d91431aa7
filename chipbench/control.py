#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds <s>]

For each seed it prints one JSON line with the number the cell compares
(``program``) and the same number for the control (``control``): the
cell's reference computed one precision step below what the configuration
states, put in the program's place.  Overlay cells run a short window at
the cell's own load and compare the sampled requests; the control is the
kernels' oracle in bfloat16 (the configuration states float32).  Decode
cells serve one whole wave per seed through the timed path and compare
the served tokens; the control is the reference with every matrix
product's operands in float8 (the configuration states bfloat16), read at
the token it puts first.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def overlay_readings(cell, seeds, seconds, require_chip=True):
    import ml_dtypes
    from chipbench import harness
    from chipbench.runners import overlay_session
    for seed in seeds:
        run, out = harness.run_traffic(cell, seed, seconds, False,
                                       t_start=time.perf_counter(),
                                       require_chip=require_chip)
        yield dict(seed=seed, program=out["checks"]["worst_rel_err"][0],
                   control=overlay_session.control_err(
                       out["requests"], out["pool"], ml_dtypes.bfloat16),
                   checked=sum("out" in r for r in out["requests"]))


def decode_readings(cell, seeds, require_chip=True):
    from chipbench import harness
    from chipbench.runners import dense_decode
    devices = harness.find_devices(cell["chips"], require_chip)
    run = harness.Run(cell, seeds[0], 0.0, False, devices, T_START)
    dc = dense_decode.Cell(run)
    for seed in seeds:
        dc.set_up(seed)
        prompts = dc.prompts(seed, 0)
        waves = [dict(prompts=prompts, served=dc.wave(prompts)["served"])]
        del dc.step
        g = dc.gaps(dc.sample(waves, seed, run.traffic["check_sequences"]),
                    lowp=True)
        yield dict(seed=seed, program=g["served"], control=g["control"])
        del dc.params


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
    if cell["traffic_data"]["runner"] == "dense_decode":
        readings = decode_readings(cell, seeds)
    else:
        readings = overlay_readings(cell, seeds, args.seconds)
    for r in readings:
        print(json.dumps(dict(workload=args.workload, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
