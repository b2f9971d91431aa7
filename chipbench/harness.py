"""The data-driven harness: from a cell's name to its result line.

``BENCHMARK.json`` names each cell's configuration and traffic.  The
configuration is the file the benchmark lists for it; the traffic is
``chipbench/traffic/<traffic>.json``, whose ``runner`` names the module
``chipbench/runners/<runner>.py`` that serves it; a per-layer metric
``<name>`` is read by ``chipbench/metrics/<name>.py``.  Adding a cell, a
traffic mix, a configuration, a runner or a metric is adding files:
nothing here names one.  A configuration or traffic file may hold a
``"cpu"`` object of smaller sizes for the CPU tests; no runner reads it.

A runner's ``run(run)`` sets up, calls ``run.mark_window_start()``, serves
the window, checks what it produced, and returns ``correct``,
``attempted``, ``failed``, ``memory_peak_bytes``, the end-to-end numbers
it measured (``end_to_end``), the numbers compared with their limits
(``checks``: name -> [value, limit]) and what the per-layer readers read
(``readings``).  Where ``run.control`` is set, the numbers compared are
the control's, put in the program's place: that run must come out not
correct.  ``run.mark(phase)`` times the steps of set-up and check.  A
runner's ``control_readings(cell, seeds, seconds, require_chip)`` yields,
for each seed, the number the cell compares for the program and for the
control (``chipbench/control.py``).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import List, Optional, Tuple

from chipbench import common, tracefile

ROOT = Path(__file__).resolve().parent.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path) -> ModuleType:
    """Import a Python file by path (names may hold dots)."""
    name = "chipbench_loaded_" + "".join(
        ch if ch.isalnum() else "_" for ch in str(path.resolve()))
    mod = sys.modules.get(name)
    if mod is None:
        s = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def runner(name: str, root: Path = ROOT) -> ModuleType:
    """The runner ``chipbench/runners/<name>.py`` of the tree at ``root``:
    the package's own module where ``root`` is this checkout, so that what
    a test patches there is what runs; otherwise loaded from its file."""
    if Path(root).resolve() == ROOT:
        return importlib.import_module(f"chipbench.runners.{name}")
    return load_module(Path(root) / "chipbench" / "runners" / f"{name}.py")


def cell_spec(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell's entry with its configuration and traffic loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = dict(cells[workload])
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_data"] = load_json(root / cfg["file"])
    cell["traffic_data"] = load_json(root / "chipbench" / "traffic" /
                                     f"{cell['traffic']}.json")
    return cell


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def find_devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} devices, found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's own persistent compilation cache policy
    (``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``, a fixed path, so that only a
    checkout's first run of a cell compiles.  Set-up time measures that
    policy, so it stays the program's to change."""
    from repro.launch.compile_cache import enable_compile_cache as enable
    return enable()


class Run:
    """What a runner is given: the cell's data, the seed, the window, the
    compile clock and the host spans."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 devices, t_start: float, control: bool = False):
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.chips = cell["chips"]
        self.seed, self.trace, self.devices = seed, trace, devices
        self.control = control
        self.window = common.Window(seconds)
        self.spans = common.Spans()
        self.clock = common.CompileClock()
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.phases: List[Tuple[str, float, int]] = []
        self._t_mark, self._x_mark = t_start, 0

    def mark(self, phase: str) -> None:
        """End the phase ``phase`` of set-up or check: its seconds and the
        executables compiled or loaded in it go to standard error (never
        into a metric), so that a slow or uneven set-up shows its cause."""
        now, x = time.perf_counter(), self.clock.executables
        self.phases.append((phase, now - self._t_mark, x - self._x_mark))
        self._t_mark, self._x_mark = now, x

    def mark_window_start(self) -> None:
        self.mark("window start")
        self.setup_s = self.window.start() - self.t_start
        self._t_mark = self.window.t0

    def phase_line(self) -> str:
        return "chipbench phases: " + ", ".join(
            f"{name} {s:.3f} s ({x} executables)"
            for name, s, x in self.phases)


def _number(x):
    return x if x is not None and math.isfinite(x) else None


def run_traffic(cell: dict, seed: int, seconds: float, trace: bool, *,
                t_start: float, require_chip: bool = True,
                root: Path = ROOT, control: bool = False):
    """Find the devices, then set up, serve and check the cell through its
    runner; returns the run and what the runner returned.  With
    ``control`` the runner checks the control in the program's place."""
    devices = find_devices(cell["chips"], require_chip)
    run = Run(cell, seed, seconds, trace, devices, t_start, control)
    run.mark("start and devices")
    out = runner(cell["traffic_data"]["runner"], root).run(run)
    print(run.phase_line(), file=sys.stderr, flush=True)
    return run, out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_chip: bool = True,
             root: Path = ROOT, cell: Optional[dict] = None,
             control: bool = False) -> dict:
    """Run one cell and return its result object.  ``cell`` replaces the
    cell loaded from ``BENCHMARK.json`` (tests run tiny cells so); with
    ``control`` the numbers compared are the control's, which has to come
    out not correct."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = spec(root)
    cell = cell if cell is not None else cell_spec(bench, workload, root)
    run, out = run_traffic(cell, seed, seconds, trace, t_start=t_start,
                           require_chip=require_chip, root=root,
                           control=control)
    workload = cell["name"]
    metrics = {}
    device = common.device_record(run.devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = dict(correct=bool(out["correct"]), attempted=out["attempted"],
                  failed=out["failed"], metrics=metrics, device=device)
    if not trace:
        values = dict(out["end_to_end"], setup_s=run.setup_s)
        for m in bench["end_to_end"]:
            if applies(m, workload):
                metrics[m["name"]] = dict(value=_number(values[m["name"]]),
                                          unit=m["unit"])
    else:
        readings = dict(out["readings"], config=run.config,
                        traffic=run.traffic, chips=run.chips,
                        peaks=common.chip_peaks(device["kind"])
                        if require_chip else common.PEAKS.get(device["kind"]))
        for m in bench["per_layer"]:
            if applies(m, workload):
                reader = load_module(root / "chipbench" / "metrics" /
                                     f"{m['name']}.py")
                v = reader.read(readings)
                if v is not None:
                    metrics[m["name"]] = dict(value=v, unit=m["unit"])
        events = readings.get("events")
        if events and tracefile.device_planes(events):
            device["busy_s"], device["window_s"] = tracefile.busy_s(events)
            result["breakdown"] = tracefile.breakdown(events)
    result["checks"] = {k: dict(value=_number(v), limit=lim)
                        for k, (v, lim) in out["checks"].items()}
    return result
