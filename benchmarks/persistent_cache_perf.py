"""Persistent-cache restart simulation (ISSUE 3 acceptance benchmark).

Simulates a serving-fleet process restart with two SEPARATE python
processes sharing one ``persist_dir``:

  cold_ms — process A boots with an empty disk cache and JIT-compiles the
            tenant kernel set uncapped (full pipeline: template stamp +
            gap fill), write-through persisting every artifact;
  warm_ms — process B "restarts" over the same directory and builds the
            same kernels: every build is a disk hit, deserialized and
            checksum-verified, with NO compiler stage run.

Per-kernel timings are measured inside each child (imports excluded), and
the children report bitstream/program content hashes so the parent can
assert the warm artifacts are bit-for-bit the persisted ones.

Acceptance (ISSUE 3): warm total >= 50x faster than cold total, recorded in
the committed ``BENCH_compile.json`` under the ``persistent`` key.

    PYTHONPATH=src python benchmarks/persistent_cache_perf.py \
        [--smoke] [--gate 50] [--json out.json] [--update BENCH_compile.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Dict, List

KERNELS = ("chebyshev", "mibench", "qspline", "sgfilter")
SMOKE_KERNELS = ("chebyshev", "sgfilter")
# the serving config: wide overlay, 4 pads/perimeter tile (deep stamp bands)
SPEC_KW = dict(width=32, height=8, dsp_per_fu=2, io_per_edge_tile=4)

_CHILD = r"""
import json, sys, time
from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache
from repro.core.jit import jit_compile
from repro.core.overlay import OverlaySpec

cfg = json.loads(sys.argv[1])
spec = OverlaySpec(**cfg["spec"])
cache = JITCache(persist_dir=cfg["dir"])
rows = []
for name in cfg["kernels"]:
    t0 = time.perf_counter()
    ck = jit_compile(BENCHMARKS[name][0], spec, cache=cache)
    ms = (time.perf_counter() - t0) * 1e3
    rows.append(dict(kernel=name, ms=ms, replicas=ck.plan.replicas,
                     pr_path=ck.pr_path, bs=ck.bitstream.sha256(),
                     prog=ck.program.content_hash()))
print(json.dumps(dict(rows=rows, disk_hits=cache.stats.disk_hits,
                      disk_writes=cache.disk.writes)))
"""


def _run_child(persist_dir: str, kernels) -> Dict:
    cfg = json.dumps(dict(dir=persist_dir, kernels=list(kernels),
                          spec=SPEC_KW))
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # the children only build overlay bitstreams: keep them off the chip,
    # which a parent that has touched JAX holds
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _CHILD, cfg], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"child process failed:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench(kernels=KERNELS) -> Dict:
    """Cold process → warm (restarted) process over one shared persist dir."""
    with tempfile.TemporaryDirectory(prefix="ovl-cache-") as d:
        cold = _run_child(d, kernels)
        warm = _run_child(d, kernels)
    rows: List[Dict] = []
    total_cold = total_warm = 0.0
    for c, w in zip(cold["rows"], warm["rows"]):
        match = c["bs"] == w["bs"] and c["prog"] == w["prog"]
        rows.append(dict(
            kernel=c["kernel"], replicas=c["replicas"], pr_path=c["pr_path"],
            cold_ms=round(c["ms"], 3), warm_ms=round(w["ms"], 3),
            speedup=round(c["ms"] / max(w["ms"], 1e-9), 1),
            bit_identical=match))
        total_cold += c["ms"]
        total_warm += w["ms"]
    return dict(
        spec=SPEC_KW, rows=rows,
        total_cold_ms=round(total_cold, 3),
        total_warm_ms=round(total_warm, 3),
        speedup_total=round(total_cold / max(total_warm, 1e-9), 1),
        warm_disk_hits=warm["disk_hits"],
        cold_disk_writes=cold["disk_writes"])


def check_gate(result: Dict, gate: float) -> List[str]:
    """Warm restart must beat cold boot by >= gate overall, every warm build
    must be served from disk, and every artifact must be bit-identical."""
    failures = []
    if result["speedup_total"] < gate:
        failures.append(f"warm restart only {result['speedup_total']}x "
                        f"faster than cold (gate {gate}x)")
    if result["warm_disk_hits"] < len(result["rows"]):
        failures.append(f"only {result['warm_disk_hits']} of "
                        f"{len(result['rows'])} warm builds hit the disk "
                        f"cache")
    for row in result["rows"]:
        if not row["bit_identical"]:
            failures.append(f"{row['kernel']}: warm artifact differs from "
                            f"persisted cold artifact")
    return failures


def run() -> List[Dict]:
    """run.py suite entry point (smoke-sized)."""
    result = bench(SMOKE_KERNELS)
    out = []
    for row in result["rows"]:
        out.append(dict(
            name=f"persistent_cache/{row['kernel']}",
            us_per_call=row["warm_ms"] * 1e3,
            derived=(f"cold={row['cold_ms']:.1f}ms warm={row['warm_ms']:.2f}ms "
                     f"speedup={row['speedup']}x R={row['replicas']} "
                     f"bit_identical={row['bit_identical']}")))
    out.append(dict(
        name="persistent_cache/total",
        us_per_call=result["total_warm_ms"] * 1e3,
        derived=(f"cold={result['total_cold_ms']:.0f}ms "
                 f"warm={result['total_warm_ms']:.1f}ms "
                 f"speedup={result['speedup_total']}x")))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced kernel set for CI")
    ap.add_argument("--gate", type=float, default=None,
                    help="fail unless warm restart >= GATE x faster")
    ap.add_argument("--json", metavar="PATH", default=None)
    ap.add_argument("--update", metavar="PATH", default=None,
                    help="merge the result into an existing benchmark JSON "
                         "under the 'persistent' key")
    args = ap.parse_args()
    result = bench(SMOKE_KERNELS if args.smoke else KERNELS)

    hdr = (f"{'kernel':<10} {'R':>3} {'cold':>9} {'warm':>9} {'speedup':>8} "
           f"{'identical':>9}")
    print(hdr)
    print("-" * len(hdr))
    for r in result["rows"]:
        print(f"{r['kernel']:<10} {r['replicas']:>3} {r['cold_ms']:>7.1f}ms "
              f"{r['warm_ms']:>7.2f}ms {r['speedup']:>7.1f}x "
              f"{str(r['bit_identical']):>9}")
    print(f"{'TOTAL':<10} {'':>3} {result['total_cold_ms']:>7.1f}ms "
          f"{result['total_warm_ms']:>7.2f}ms "
          f"{result['speedup_total']:>7.1f}x")

    failures = check_gate(result, args.gate) if args.gate else []
    result["gate"] = args.gate
    result["gate_failures"] = failures
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {args.json}")
    if args.update:
        with open(args.update) as f:
            doc = json.load(f)
        doc["persistent"] = result
        with open(args.update, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"updated {args.update} [persistent]")
    if failures:
        for msg in failures:
            print(f"GATE FAIL: {msg}")
        raise SystemExit(1)
    if args.gate:
        print(f"gate PASS: warm restart >= {args.gate}x faster than cold")


if __name__ == "__main__":
    main()
