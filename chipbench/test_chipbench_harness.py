"""The harness: the contract of ``BENCHMARK.json``, cells found by name
from data files, and the refusal to run without a chip."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parent.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

def tiny_cell(name, size="tiny", root=ROOT):
    """The cell at the CPU sizes of its configuration and traffic files:
    each file's ``cpu.tiny``, then its ``cpu.<size>`` where it has one.
    The traffic's ``window_s`` (0.5 where it gives none) is the window the
    tests run it with, and goes to ``cell["window_s"]``, not to the
    runner.  A file without ``cpu.tiny`` is refused: nothing runs at full
    size on the CPU."""
    bench = harness.spec(root)
    cell = harness.cell_spec(bench, name, root)
    files = {"config_data": {c["name"]: c for c in bench["configs"]}
             [cell["config"]]["file"],
             "traffic_data": f"chipbench/traffic/{cell['traffic']}.json"}
    for part, path in files.items():
        cpu = cell[part].get("cpu", {})
        if "tiny" not in cpu:
            raise KeyError(f"{path} has no \"cpu\": {{\"tiny\": ...}} "
                           f"sizes, so cell {name!r} cannot run on the CPU")
        cell[part].update(cpu["tiny"], **cpu.get(size, {}))
    cell["window_s"] = cell["traffic_data"].pop("window_s", 0.5)
    cell["chips"] = 1
    return cell


def run_tiny(name, trace=False, root=ROOT, seed=2 ** 33 + 5):
    cell = tiny_cell(name, root=root)
    return harness.run_cell(name, seed, cell["window_s"], trace,
                            require_chip=False, root=root, cell=cell)


BENCH = harness.spec()


def test_benchmark_json_keeps_the_contract():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert b["paths"] == ["chipbench"]
    assert 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and PATH.fullmatch(c["file"])
        assert c["file"].startswith("chipbench/")
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
    cells = b["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for k in ("name", "config", "traffic"):
            assert NAME.fullmatch(w[k])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (ROOT / "chipbench" / "traffic" /
                f"{w['traffic']}.json").is_file()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for m in b["end_to_end"]:
        assert m["source"] in {"device_trace", "host_clock"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= {w["name"] for w in cells}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert "\n" not in m["layer"] and m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["workloads"]
        for w in m["workloads"]:
            assert harness.applies(e2e[m["moves"]], w)
    assert len(json.dumps(b)) <= 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if harness.applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert [m for m in BENCH["per_layer"] if harness.applies(m, cell)]


def test_files_under_paths_are_named_from_name_characters():
    for p in (ROOT / "chipbench").rglob("*"):
        if "__pycache__" not in p.parts:
            assert PATH.fullmatch(p.relative_to(ROOT).as_posix()), p


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A cell, a configuration, a traffic mix and a per-layer metric that
    are only new files (and entries in BENCHMARK.json) run unedited."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/paper_suite.json")
                     .read_text())
    cfg.update(name="suite_wide", overlay=dict(width=10, height=10,
                                                dsp_per_fu=2))
    (tmp_path / "chipbench/configs/suite_wide.json").write_text(
        json.dumps(cfg))
    (tmp_path / "chipbench/traffic/one_tenant.json").write_text(json.dumps(
        dict(runner="overlay_session", tenants=1, kernel="cached",
             kernels=["poly1"], zipf_s=None, deck=1, items_log2=[10, 10],
             check_share=1.0)))
    (tmp_path / "chipbench/metrics/launches.py").write_text(
        "def read(r):\n    return r['spans'].count.get('launch')\n")
    bench["configs"].append(dict(name="suite_wide", source="test",
                                 file="chipbench/configs/suite_wide.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="wide_one", config="suite_wide",
                                   traffic="one_tenant", chips=1, why="t"))
    for m in bench["end_to_end"]:
        if m["name"] == "items_per_s":
            m["workloads"].append("wide_one")
    bench["per_layer"].append(dict(
        name="launches", unit="count", better="higher",
        source="host_clock", layer="session", moves="items_per_s",
        workloads=["wide_one"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    e2e = harness.run_cell("wide_one", 3, 0.3, False, require_chip=False,
                           root=tmp_path)
    assert e2e["correct"] and set(e2e["metrics"]) == {"items_per_s",
                                                      "setup_s"}
    traced = harness.run_cell("wide_one", 3, 0.3, True, require_chip=False,
                              root=tmp_path)
    assert traced["metrics"]["launches"]["value"] >= 1


# a runner that exists only as a new file: a closed loop of one jitted
# affine map over a vector drawn from the seed, checked against float64
TOY_RUNNER = '''
import jax
import numpy as np

from chipbench import common


def _x(t, seed):
    return common.rng(seed, 0).random(t["n"], np.float32)


def _err(c, x, y):
    return common.rel_err(y, np.asarray(x, np.float64) * c["scale"] + 1)


def _lowp(c, x):
    return jax.jit(lambda v: v.astype("bfloat16") * c["scale"] + 1)(x)


def run(run):
    c, x = run.config, _x(run.traffic, run.seed)
    f = jax.jit(lambda v: v * c["scale"] + 1)
    y = np.asarray(f(x))
    run.mark_window_start()
    done = 0
    while run.window.open():
        y = np.asarray(f(x))
        done += 1
    err = _err(c, x, _lowp(c, x) if run.control else y)
    limit = c["check"]["rel_err_max"]
    return dict(correct=err <= limit, attempted=done, failed=0,
                memory_peak_bytes=0,
                end_to_end=dict(maps_per_s=done / run.window.seconds),
                checks=dict(rel_err=[err, limit]), readings={})


def control_readings(cell, seeds, seconds, require_chip=True):
    c, t = cell["config_data"], cell["traffic_data"]
    f = jax.jit(lambda v: v * c["scale"] + 1)
    for seed in seeds:
        x = _x(t, seed)
        yield dict(seed=seed, program=_err(c, x, f(x)),
                   control=_err(c, x, _lowp(c, x)))
'''


def _toy_tree(tmp_path):
    """A copy of the benchmark with a new runner, configuration, traffic
    mix and metrics, as new files and BENCHMARK.json entries only."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = {
        "chipbench/runners/toy_loop.py": TOY_RUNNER,
        "chipbench/configs/toy.json": json.dumps(dict(
            name="toy", scale=3.0, check=dict(rel_err_max=1e-4),
            cpu=dict(tiny={}))),
        "chipbench/traffic/toy_map.json": json.dumps(dict(
            runner="toy_loop", n=1 << 16, cpu=dict(tiny=dict(n=512)))),
        "chipbench/metrics/toy_n.py":
            "def read(r):\n    return r['traffic']['n']\n",
    }
    for rel, text in new.items():
        assert not (tmp_path / rel).exists()
        (tmp_path / rel).write_text(text)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="toy", source="test",
                                 file="chipbench/configs/toy.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="toy_cell", config="toy",
                                   traffic="toy_map", chips=1, why="t"))
    bench["end_to_end"].append(dict(
        name="maps_per_s", unit="maps/s", better="higher", bound=0.05,
        source="host_clock", workloads=["toy_cell"]))
    bench["per_layer"].append(dict(
        name="toy_n", unit="items", better="higher",
        source="program_counter", layer="toy", moves="maps_per_s",
        workloads=["toy_cell"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_runner_is_found_by_name(tmp_path):
    """A new runner with its configuration, traffic mix and metrics, added
    as new files and BENCHMARK.json entries, runs through ``tiny_cell``,
    ``run_cell`` and the control's dispatch with no existing file
    edited; it is found only in the tree it was added to."""
    from chipbench import control
    _toy_tree(tmp_path)
    with pytest.raises(ModuleNotFoundError):
        harness.runner("toy_loop")
    e2e = run_tiny("toy_cell", root=tmp_path)
    assert e2e["correct"] and e2e["attempted"] > 0
    assert set(e2e["metrics"]) == {"maps_per_s", "setup_s"}
    traced = run_tiny("toy_cell", trace=True, root=tmp_path)
    assert traced["metrics"] == {"toy_n": dict(value=512, unit="items")}
    cell = tiny_cell("toy_cell", root=tmp_path)
    assert not harness.run_cell("toy_cell", 3, 0.1, False,
                                require_chip=False, root=tmp_path,
                                cell=cell, control=True)["correct"]
    limit = cell["config_data"]["check"]["rel_err_max"]
    for r in control.readings(cell, [1, 2], 0.1, require_chip=False,
                              root=tmp_path):
        assert r["program"] <= limit < r["control"], r
    for p in (ROOT / "chipbench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            copy = tmp_path / p.relative_to(ROOT)
            assert copy.read_bytes() == p.read_bytes(), copy


def test_a_cell_without_cpu_sizes_is_refused(tmp_path):
    _toy_tree(tmp_path)
    traffic = tmp_path / "chipbench/traffic/toy_map.json"
    traffic.write_text(json.dumps(dict(runner="toy_loop", n=1 << 16)))
    with pytest.raises(KeyError, match=r"toy_map\.json has no \"cpu\""):
        tiny_cell("toy_cell", root=tmp_path)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_has_cpu_sizes(cell):
    c = harness.cell_spec(BENCH, cell)
    assert "tiny" in c["config_data"]["cpu"]
    assert "tiny" in c["traffic_data"]["cpu"]


def test_no_runner_reads_the_cpu_sizes():
    """A chip run reads the files as if they had no ``"cpu"`` block."""
    from chipbench.runners import dense_decode
    for path in (ROOT / "chipbench" / "runners").glob("*.py"):
        text = path.read_text()
        assert '"cpu"' not in text and "'cpu'" not in text, path
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        if "arch_id" in cfg:
            bare = {k: v for k, v in cfg.items() if k != "cpu"}
            assert dense_decode.arch_config(cfg) == \
                dense_decode.arch_config(bare)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "suite_bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_tiny_cell_runs_on_cpu(cell):
    r = run_tiny(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if harness.applies(m, cell)}
    assert set(r["metrics"]) == e2e
