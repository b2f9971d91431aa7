"""Persistent on-disk compile cache (ISSUE 3): cold → persist → warm-load
round trip (in-process and cross-process, bit-for-bit), corruption
quarantine, schema-version invalidation, and template-tier persistence."""

import os
import subprocess
import sys
from pathlib import Path


from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import DiskCache, JITCache
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
POLY1 = BENCHMARKS["poly1"][0]


def _entry_files(root: Path):
    return sorted(root.glob("*/*.bin"))


# --------------------------------------------------------------- round trip

def test_disk_round_trip_in_process(tmp_path):
    """cold build → persisted; a FRESH cache over the same dir serves the
    artifact from disk, bit-for-bit equal, with no compiler stage run."""
    cold_cache = JITCache(persist_dir=tmp_path)
    cold = jit_compile(POLY1, SPEC, cache=cold_cache,
                       opts=CompileOptions(max_replicas=4))
    assert cold_cache.disk.writes >= 1

    warm_cache = JITCache(persist_dir=tmp_path)      # simulated restart
    warm = jit_compile(POLY1, SPEC, cache=warm_cache,
                       opts=CompileOptions(max_replicas=4))
    assert warm is not cold                          # distinct object...
    assert warm_cache.stats.disk_hits == 1           # ...from the disk tier
    assert warm.bitstream.data == cold.bitstream.data
    assert warm.bitstream.sha256() == cold.bitstream.sha256()
    assert warm.program.content_hash() == cold.program.content_hash()
    assert warm.placement.fu_pos == cold.placement.fu_pos
    assert warm.latency.delays == cold.latency.delays
    # the promoted entry now hits in memory
    again = jit_compile(POLY1, SPEC, cache=warm_cache,
                        opts=CompileOptions(max_replicas=4))
    assert again is warm
    assert warm_cache.stats.disk_hits == 1


def test_disk_template_tier_survives_restart(tmp_path):
    """A fresh process building at a NEW replica count misses the full key
    but warm-loads the P&R template from disk: no place/route stage runs."""
    cache = JITCache(persist_dir=tmp_path)
    jit_compile(POLY1, SPEC, cache=cache,
                opts=CompileOptions(max_replicas=8, pr_mode="template"))

    fresh = JITCache(persist_dir=tmp_path)
    ck = jit_compile(POLY1, SPEC, cache=fresh,
                     opts=CompileOptions(max_replicas=4, pr_mode="template"))
    assert fresh.stats.disk_template_hits == 1
    assert ck.plan.replicas == 4
    assert ck.stage_times_ms["place"] == 0.0
    assert ck.stage_times_ms["route"] == 0.0
    assert ck.stage_times_ms["stamp"] > 0.0


def test_disk_round_trip_cross_process(tmp_path):
    """True restart: a subprocess warm-loads the persisted artifact and its
    bitstream/program hashes match the parent's cold build exactly."""
    cache = JITCache(persist_dir=tmp_path)
    cold = jit_compile(POLY1, SPEC, cache=cache,
                       opts=CompileOptions(max_replicas=4))
    child = (
        "import json, sys\n"
        "from repro.configs.paper_suite import BENCHMARKS\n"
        "from repro.core.cache import JITCache\n"
        "from repro.core.jit import jit_compile\n"
        "from repro.core.options import CompileOptions\n"
        "from repro.core.overlay import OverlaySpec\n"
        f"cache = JITCache(persist_dir={str(tmp_path)!r})\n"
        "ck = jit_compile(BENCHMARKS['poly1'][0],\n"
        "                 OverlaySpec(width=8, height=8, dsp_per_fu=2),\n"
        "                 opts=CompileOptions(max_replicas=4), cache=cache)\n"
        "print(json.dumps(dict(disk_hits=cache.stats.disk_hits,\n"
        "                      bs=ck.bitstream.sha256(),\n"
        "                      prog=ck.program.content_hash())))\n")
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    import json
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["disk_hits"] == 1
    assert got["bs"] == cold.bitstream.sha256()
    assert got["prog"] == cold.program.content_hash()


# -------------------------------------------------------------- corruption

def test_corrupted_entry_quarantined_and_recompiled(tmp_path):
    cache = JITCache(persist_dir=tmp_path)
    cold = jit_compile(POLY1, SPEC, cache=cache,
                       opts=CompileOptions(max_replicas=4))
    # full-key + template + frontend tiers all persist
    entries = _entry_files(tmp_path)
    assert len(entries) == 3
    for entry in entries:
        blob = bytearray(entry.read_bytes())
        blob[len(blob) // 2] ^= 0xFF                 # flip a payload byte
        entry.write_bytes(bytes(blob))

    fresh = JITCache(persist_dir=tmp_path)
    ck = jit_compile(POLY1, SPEC, cache=fresh,
                     opts=CompileOptions(max_replicas=4))
    assert ck.bitstream.data == cold.bitstream.data  # recompiled, not crashed
    assert fresh.disk.quarantined >= 1
    assert list(tmp_path.glob("*/*.corrupt"))        # evidence kept aside
    # the recompile re-persisted a good entry
    again = JITCache(persist_dir=tmp_path)
    jit_compile(POLY1, SPEC, cache=again, opts=CompileOptions(max_replicas=4))
    assert again.stats.disk_hits == 1


def test_truncated_entry_quarantined(tmp_path):
    cache = JITCache(persist_dir=tmp_path)
    jit_compile(POLY1, SPEC, cache=cache, opts=CompileOptions(max_replicas=4))
    for entry in _entry_files(tmp_path):
        entry.write_bytes(entry.read_bytes()[:20])   # torn write survivor

    fresh = JITCache(persist_dir=tmp_path)
    ck = jit_compile(POLY1, SPEC, cache=fresh,
                     opts=CompileOptions(max_replicas=4))
    assert ck.plan.replicas == 4
    assert fresh.disk.quarantined >= 1


def test_schema_version_invalidation(tmp_path, monkeypatch):
    """Entries written under an older schema are dropped (not quarantined —
    they are stale, not corrupt) and transparently recompiled."""
    cache = JITCache(persist_dir=tmp_path)
    jit_compile(POLY1, SPEC, cache=cache, opts=CompileOptions(max_replicas=4))
    monkeypatch.setattr(DiskCache, "SCHEMA_VERSION", 2)
    fresh = JITCache(persist_dir=tmp_path)
    ck = jit_compile(POLY1, SPEC, cache=fresh,
                     opts=CompileOptions(max_replicas=4))
    assert ck.plan.replicas == 4
    assert fresh.disk.invalidated >= 1
    assert fresh.disk.quarantined == 0
    assert not list(tmp_path.glob("*/*.corrupt"))


# ------------------------------------------------------------------- basics

def test_disk_cache_is_best_effort_on_write_failure(tmp_path):
    """A failing write (e.g. full disk) must not take down the build."""
    dc = DiskCache(tmp_path)
    dc.put("key", lambda: None)                      # unpicklable payload
    assert dc.write_errors == 1
    assert dc.get("key") is None                     # clean miss


def test_memory_eviction_keeps_disk_entry(tmp_path):
    cache = JITCache(capacity=1, persist_dir=tmp_path)
    a = jit_compile(POLY1, SPEC, cache=cache,
                    opts=CompileOptions(max_replicas=2))
    jit_compile(BENCHMARKS["chebyshev"][0], SPEC, cache=cache,
                opts=CompileOptions(max_replicas=2))
    assert cache.stats.evictions >= 1                # a fell out of the LRU
    b = jit_compile(POLY1, SPEC, cache=cache,
                    opts=CompileOptions(max_replicas=2))
    assert cache.stats.disk_hits >= 1                # ...but not off disk
    assert b.bitstream.data == a.bitstream.data
