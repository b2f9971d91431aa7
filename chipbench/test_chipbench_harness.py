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

# a tiny dense decoder and tiny overlay traffic: the CPU runs the whole
# harness at these sizes in seconds
TINY_LM = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab=256)
TINY_WAVES = dict(batch=4, prompt=8, gen=8, cache_len=16,
                  trace_start_s=0.1, trace_seconds=0.3)


def tiny_cell(name, root=ROOT):
    cell = harness.cell_spec(harness.spec(root), name, root)
    if cell["traffic_data"]["runner"] == "dense_decode":
        cell["config_data"].update(TINY_LM)
        cell["traffic_data"].update(TINY_WAVES)
    else:
        lo, hi = cell["traffic_data"]["items_log2"]
        cell["traffic_data"].update(items_log2=[min(lo, 10), 11], tenants=2)
    cell["chips"] = 1
    return cell


def run_tiny(name, trace=False, root=ROOT, seconds=0.5, seed=2 ** 33 + 5):
    return harness.run_cell(name, seed, seconds, trace, require_chip=False,
                            root=root, cell=tiny_cell(name, root))


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
