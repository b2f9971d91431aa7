"""Tracing, metrics & profile-guided re-cutting (repro.obs, ISSUE 10).

The load-bearing claims, each asserted here:
  * spans nest per thread (racing workers never see each other's
    parents) and the disabled path is one shared no-op object;
  * the Chrome-trace export is byte-stable under an injected clock
    (golden file) and splits host wall spans from modelled device spans;
  * ReplayProfiles round-trip through the disk AND remote cache tiers
    (restart warm start, fleet warm start, remote→disk promotion);
  * the re-cutter's never-worse contract: no hot profile → no swap,
    config-dominated profile → no swap (and no compile issued), a split
    that only pays off when each half is priced against the full fabric
    → no swap (an instantiated graph's partitions co-reside), and a
    genuine win (re-fusing a stale per-stage plan under streaming-
    dominated traffic) → swap with BIT-identical outputs, a faster
    modelled engine timeline, and a warm (zero-miss) re-instantiation
    through the adopted plan;
  * Session.stats() emits registered sections in deterministic name
    order and refuses names that would shadow a built-in section;
  * completions past their SLO class's target_p99_us are counted per
    class in stats()["serving"] and in the metrics registry;
  * while the JAX profiler collects, wall spans are host events of its
    trace (nested on their thread), modelled spans never are, and the
    executor launch and Session enqueue book their spans in order.
"""

import glob
import json
import os
import threading

import numpy as np
import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache
from repro.core.graph import partition_graph_grouped
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.recovery import RetryPolicy
from repro.core.remote import RemoteBlobStore, RemoteCache, RemoteEndpoint
from repro.core.runtime import Device
from repro.core.session import Session, SessionError
from repro.obs import (MetricsRegistry, ProfileStore, ReCutter, Tracer,
                       activate, active_tracer, chrome_trace, hot_profiles,
                       modelled, profile_key, span, write_chrome_trace)
from repro.kernels.overlay_exec import ops
from repro.obs.trace import _NULL_SPAN
from repro.serve import InferenceServer, Request
from repro.serve.slo import SLOClass

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
POLY1 = BENCHMARKS["poly1"][0]
OPTS = CompileOptions(max_replicas=4, n_inputs=1)

STICKY = RetryPolicy(breaker_cooldown_s=60.0)


def ticking_clock(step_us=10.0):
    """Deterministic injectable tracer clock: 0, step, 2*step, ..."""
    state = {"t": -step_us}

    def clock():
        state["t"] += step_us
        return state["t"]

    return clock


# ------------------------------------------------------------------ tracer

def test_spans_nest_on_one_thread():
    tr = Tracer(clock=ticking_clock())
    with activate(tr):
        with span("outer", "compile", kernel="k") as sp:
            sp["hit"] = False
            with span("inner", "cache"):
                pass
    outer = next(s for s in tr.spans() if s.name == "outer")
    inner = next(s for s in tr.spans() if s.name == "inner")
    assert outer.parent is None and outer.depth == 0
    assert inner.parent == outer.sid and inner.depth == 1
    assert outer.args == {"kernel": "k", "hit": False}
    # inner closed first but both intervals are positive and nested
    assert inner.ts_us >= outer.ts_us
    assert outer.dur_us > inner.dur_us


def test_span_nesting_across_threads():
    """Racing threads share one tracer but never each other's span
    stacks: every span's parent chain stays within its own thread."""
    tr = Tracer()
    barrier = threading.Barrier(4)

    def worker(tag):
        with activate(tr):
            with span(f"outer:{tag}", "compile"):
                barrier.wait(timeout=30)       # all outers open at once
                with span(f"inner:{tag}", "compile"):
                    barrier.wait(timeout=30)

    threads = [threading.Thread(target=worker, args=(i,), name=f"w{i}")
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = {s.name: s for s in tr.spans()}
    assert len(spans) == 8
    for i in range(4):
        outer, inner = spans[f"outer:{i}"], spans[f"inner:{i}"]
        assert outer.parent is None and inner.parent == outer.sid
        assert outer.track == inner.track == f"w{i}"


def test_disabled_path_is_shared_noop():
    assert active_tracer() is None
    sp = span("anything", "compile", key="v")
    assert sp is _NULL_SPAN                    # one shared object, no alloc
    with sp as h:
        h["outcome"] = "ignored"               # outcome writes are no-ops
    # activation nests and restores, including explicit disabling
    tr = Tracer()
    with activate(tr):
        assert active_tracer() is tr
        with activate(None):
            assert active_tracer() is None
            assert span("x") is _NULL_SPAN
        assert active_tracer() is tr
    assert active_tracer() is None
    assert tr.n_spans == 0


def test_disabled_path_is_shared_noop_with_jax_imported():
    """JAX imported but no profiler trace collected: still the shared
    no-op, and outcome writes on it are dropped."""
    import jax.profiler
    assert not jax.profiler.TraceAnnotation.is_enabled()
    sp = span("launch:stage", "launch")
    assert sp is _NULL_SPAN
    with sp as h:
        h["items"] = 1


def _profiled(body, tmp_path):
    """Run ``body`` under a CPU ``jax.profiler`` trace written to
    ``tmp_path``; returns the host events (plane, line, name, start, end)
    of the profile."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    return [(plane.name, line.name, e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(paths[0]).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events]


def test_spans_reach_the_profiler_nested_on_one_line(tmp_path):
    tr = Tracer()

    def body():
        with span("obs:outer", "compile") as sp:
            sp["hit"] = True                   # dropped by the profiler
            with span("obs:inner", "compile"):
                pass
        with activate(tr):                     # both sinks record
            with span("obs:both", "compile", kernel="k") as sp:
                sp["hit"] = False

    ev = _profiled(body, tmp_path)
    by = {}
    for e in ev:
        by.setdefault(e[2], []).append(e)
    assert {n: len(by.get(n, [])) for n in
            ("obs:outer", "obs:inner", "obs:both")} == \
        {"obs:outer": 1, "obs:inner": 1, "obs:both": 1}
    (outer,), (inner,) = by["obs:outer"], by["obs:inner"]
    assert outer[:2] == inner[:2]               # one plane, one thread line
    assert outer[3] <= inner[3] <= inner[4] <= outer[4]
    assert by["obs:both"][0][3] >= outer[4]
    assert [(s.name, s.args) for s in tr.spans()] == \
        [("obs:both", {"kernel": "k", "hit": False})]
    assert span("obs:after") is _NULL_SPAN      # the trace has stopped


def _poly_program():
    from repro.core.jit import jit_compile
    return jit_compile(POLY1, SPEC, opts=OPTS).program


LAUNCH_SPANS = ["launch:stage", "launch:h2d", "launch:wait", "launch:d2h"]


def test_execute_books_launch_spans_in_order():
    prog = _poly_program()
    x = np.linspace(-1, 1, 300).astype(np.float32)
    tr = Tracer()
    with activate(tr):
        out = ops.execute(prog, [x], interpret=True)
    assert out[0].shape == x.shape
    spans = sorted(tr.spans(), key=lambda s: s.ts_us)
    assert [s.name for s in spans] == LAUNCH_SPANS
    assert {s.cat for s in spans} == {"launch"}
    for a, b in zip(spans, spans[1:]):
        assert a.ts_us + a.dur_us <= b.ts_us
    assert all(s.depth == 0 for s in spans)


def test_session_enqueue_span_wraps_the_launch():
    tr = Tracer()
    x = np.linspace(-1, 1, 300).astype(np.float32)
    with Session([Device("a", SPEC)], use_overlay_executor=True,
                 tracer=tr) as sess:
        fut = sess.compile(POLY1, OPTS, tenant="t")
        ev = sess.enqueue(fut, x)
        with activate(tr):
            y = ev.wait()[0].read()
    assert y.shape == x.shape
    (enq,) = [s for s in tr.spans() if s.name == "session:enqueue"]
    launch = sorted((s for s in tr.spans() if s.name in LAUNCH_SPANS),
                    key=lambda s: s.ts_us)
    assert [s.name for s in launch] == LAUNCH_SPANS
    assert all(s.parent == enq.sid and s.track == enq.track for s in launch)
    assert enq.ts_us <= launch[0].ts_us
    assert launch[-1].ts_us + launch[-1].dur_us <= enq.ts_us + enq.dur_us
    (read,) = [s for s in tr.spans() if s.name == "buffer:read"]
    assert read.parent is None and read.ts_us >= enq.ts_us + enq.dur_us
    # the modelled device slices stay in the tracer
    assert any(s.track.startswith("dev:a/") for s in tr.spans())


def test_modelled_spans_never_reach_the_profiler(tmp_path):
    tr = Tracer()
    x = np.linspace(-1, 1, 64).astype(np.float32)
    with Session([Device("a", SPEC)], tracer=tr) as sess:
        fut = sess.compile(POLY1, OPTS, tenant="t")
        fut.result()

        def body():
            sess.enqueue(fut, x).wait()
            with activate(tr):
                modelled("obs:modelled", "dev:a/t", 0.0, 10.0)

        ev = _profiled(body, tmp_path)
    names = {e[2] for e in ev}
    assert "session:enqueue" in names
    dev = [s for s in tr.spans() if s.track.startswith("dev:")]
    assert {s.name for s in dev} >= {"obs:modelled", "poly1"}
    assert not names & {s.name for s in dev}


def test_span_records_error_and_modelled_spans_are_roots():
    tr = Tracer(clock=ticking_clock())
    with activate(tr):
        with pytest.raises(ValueError):
            with span("boom", "compile"):
                raise ValueError("injected")
    tr.add_modelled("exec:k", "dev:a/t0", 100.0, 50.0, items=64)
    boom = next(s for s in tr.spans() if s.name == "boom")
    assert boom.error == "ValueError: injected"
    dev = next(s for s in tr.spans() if s.name == "exec:k")
    assert dev.parent is None and dev.depth == 0
    assert (dev.ts_us, dev.dur_us, dev.cat) == (100.0, 50.0, "device")
    assert tr.counts_by_cat() == {"compile": 1, "device": 1}


# ----------------------------------------------------------------- metrics

def test_metrics_instruments_and_registry():
    m = MetricsRegistry()
    c = m.counter("a.count")
    assert m.counter("a.count") is c           # get-or-create
    c.inc()
    c.inc(2.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    m.gauge("a.gauge").set(7)
    h = m.histogram("a.hist")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50.0) == 50.0
    assert h.percentile(99.0) == 99.0
    s = h.summary()
    assert s["n"] == 100 and s["max"] == 100.0 and s["mean"] == 50.5
    with pytest.raises(TypeError):
        m.gauge("a.count")                     # kind mismatch is an error
    d = m.as_dict()
    assert d["counters"] == {"a.count": 3.5}
    assert d["gauges"] == {"a.gauge": 7.0}
    assert d["histograms"]["a.hist"]["p99"] == 99.0


def test_histogram_window_bounds_samples_keeps_totals():
    m = MetricsRegistry()
    h = m.histogram("w", window=8)
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["n"] == 100                       # lifetime totals exact
    assert s["p50"] >= 92.0                    # window holds the tail only


def test_metrics_install_lands_in_session_stats():
    with Session([Device("a", SPEC)],
                 metrics=MetricsRegistry()) as sess:
        sess.metrics.counter("builds").inc(3)
        obs = sess.stats()["obs"]
        assert obs["counters"] == {"builds": 3.0}


# ------------------------------------------------------------------ export

def golden_tracer():
    """The deterministic trace behind tests/data/obs_trace_golden.json."""
    tr = Tracer(clock=ticking_clock())
    with activate(tr):
        with span("jit:build", "compile", kernel="poly1"):
            with span("jit:frontend", "compile"):
                pass
            with span("cache:disk", "cache", kind="kernel") as sp:
                sp["hit"] = False
        try:
            with span("jit:route", "compile", kernel="poly1"):
                raise RuntimeError("no feasible route")
        except RuntimeError:
            pass
    tr.add_modelled("wait:k", "dev:a/t0", 0.0, 5.5, cat="queue",
                    gap_us=5.5)
    tr.add_modelled("config:k", "dev:a/t0", 5.5, 40.0, cat="device")
    tr.add_modelled("k", "dev:a/t0", 45.5, 100.0, cat="device",
                    items=4096, replicas=4)
    return tr


def test_chrome_trace_export_matches_golden(tmp_path):
    """Byte-stable export: the golden file IS the format contract."""
    path = write_chrome_trace(golden_tracer(), str(tmp_path / "t.json"))
    got = open(path, encoding="utf-8").read()
    want = open("tests/data/obs_trace_golden.json",
                encoding="utf-8").read()
    assert got == want


def test_chrome_trace_structure():
    doc = chrome_trace(golden_tracer())
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    # wall spans on the host pid, modelled spans on the device pid
    assert {e["pid"] for e in xs if e["name"].startswith("jit:")} == {1}
    assert {e["pid"] for e in xs if e["name"] == "k"} == {2}
    # queue rows ride the device process too (dev: track prefix)
    assert next(e for e in xs if e["name"] == "wait:k")["pid"] == 2
    # nesting and outcome args survive the export
    build = next(e for e in xs if e["name"] == "jit:build")
    disk = next(e for e in xs if e["name"] == "cache:disk")
    assert disk["args"]["parent"] == build["args"]["sid"]
    assert disk["args"]["hit"] is False
    route = next(e for e in xs if e["name"] == "jit:route")
    assert route["args"]["error"] == "RuntimeError: no feasible route"
    names = {(m["name"], m["args"]["name"]) for m in metas}
    assert ("process_name", "host") in names
    assert ("process_name", "overlay (modelled)") in names
    assert ("thread_name", "dev:a/t0") in names


# ------------------------------------------------------------ profile store

def _chain_graph(sess, mults=18, name="g"):
    """Two-stage chain of fused multiply-add ladders.  Each stage is wide
    enough that the per-stage cut leaves two fat co-resident partitions
    alternating configs, while the greedy cut fuses the pair into ONE
    partition that streams the batch in a single pass — the gap the
    profile-guided re-cutter must see (and repair) from measurements."""

    def wide(k):
        def fn(x):
            for _ in range(k):
                x = x * 1.01 + 0.001
            return x
        return fn

    with sess.capture("t", name=name) as g:
        b = g.input("x")
        b = g.call(wide(mults), OPTS.replace(name="s0"), b)
        b = g.call(wide(mults), OPTS.replace(name="s1"), b)
    return g


def test_profile_store_round_trip_disk_and_remote(tmp_path):
    store_blob = RemoteBlobStore()
    rc = RemoteCache([RemoteEndpoint(store_blob, "r0")], retry=STICKY)
    x = np.linspace(0, 1, 50_000).astype(np.float32)
    with Session([Device("a", SPEC)], persist_dir=tmp_path,
                 remote=rc) as sess:
        sess.profiles = ProfileStore(cache=sess.cache)
        g = _chain_graph(sess)
        gx = sess.instantiate(g)
        for _ in range(3):
            sess.launch(gx, x).wait()
        spec = sess.scheduler.partition_spec()
        key = profile_key(g.fingerprint(), spec)
        prof = sess.profiles.get(key)
        assert prof is not None and prof.replays == 3
        assert prof.items_per_replay() == 50_000.0
        assert prof.config_unit_us() > 0          # first replay paid config
        assert sess.profiles.stats_dict()["flushes"] == 3
        assert hot_profiles(sess.profiles) == [prof]
        fp = g.fingerprint()

    # restart warm start: a fresh store over the same disk tier
    disk_only = ProfileStore(cache=JITCache(persist_dir=tmp_path))
    got = disk_only.get(key)
    assert got is not None and got.replays == 3 and got.graph_fp == fp
    assert disk_only.stats_dict()["loads_disk"] == 1
    assert disk_only.get(key) is got              # memory tier after load
    assert disk_only.stats_dict()["loads_memory"] == 1

    # fleet warm start: remote-only host, with remote→disk promotion
    rc2 = RemoteCache([RemoteEndpoint(store_blob, "r1")], retry=STICKY)
    promote_dir = tmp_path / "host2"
    remote_host = ProfileStore(
        cache=JITCache(persist_dir=promote_dir, remote=rc2))
    got = remote_host.get(key)
    assert got is not None and got.replays == 3
    assert remote_host.stats_dict()["loads_remote"] == 1
    # the promotion persisted: a disk-only reload on host2 now works
    assert ProfileStore(
        cache=JITCache(persist_dir=promote_dir)).get(key) is not None

    assert ProfileStore(cache=JITCache()).get("profile:nope") is None


def test_profile_resets_when_the_cut_changes(tmp_path):
    x = np.linspace(0, 1, 10_000).astype(np.float32)
    with Session([Device("a", SPEC)]) as sess:
        sess.profiles = ProfileStore(cache=sess.cache)
        g = _chain_graph(sess)
        gx = sess.instantiate(g)
        for _ in range(2):
            sess.launch(gx, x).wait()
        spec = sess.scheduler.partition_spec()
        prof = sess.profiles.get(profile_key(g.fingerprint(), spec))
        assert prof.replays == 2
        gx.release()
        # re-cut by hand: per-stage partitions under a tight cap (one
        # 18-rung stage needs 18 FUs; the fused pair needs twice that)
        gx2 = sess.instantiate(g, max_partition_fus=20)
        assert gx2.n_partitions == 2
        sess.launch(gx2, x).wait()
        # cut-scoped: stale per-partition rows were dropped, not mixed
        assert prof.replays == 1
        assert prof.cut == tuple(tuple(p.node_ids)
                                 for p in gx2.partitions)


# -------------------------------------------------------------- re-cutting

def test_recut_swap_wins_bit_identical_and_warm():
    """The acceptance loop: the graph serves under a stale adopted
    per-stage cut — two fat partitions co-resident on one fabric,
    alternating configs every replay — and the streaming-dominated
    profile makes the DP re-fuse the chain.  The swap is never-worse by
    the co-resident estimator, faster on the modelled engine timeline,
    BIT-identical on real data, and the adopted plan makes the next
    instantiate a zero-miss warm hit."""
    x = np.linspace(0, 1, 4_000_000).astype(np.float32)
    with Session([Device("a", SPEC)]) as sess:
        sess.profiles = ProfileStore(cache=sess.cache)
        g = _chain_graph(sess)
        spec = sess.scheduler.partition_spec()
        # e.g. adopted from a fleet profile recorded under an older,
        # config-charge-dominated traffic regime
        sess.adopt_graph_plan(g, partition_graph_grouped(
            g, spec, [[0], [1]]))
        gx = sess.instantiate(g)
        assert gx.n_partitions == 2               # the stale cut is live
        for _ in range(2):
            sess.launch(gx, x).wait()
        out_old = sess.launch(gx, x).outputs[0].read()
        ctx = next(iter(sess.contexts.values()))
        mark = ctx.engine_end_us
        sess.launch(gx, x).wait()                 # steady-state replay
        old_replay_us = ctx.engine_end_us - mark
        gx.release()                              # retire before the swap

        rec = ReCutter(sess, sess.profiles)
        res = rec.consider(g)
        assert res.swapped and res.reason == "swapped"
        assert res.old_cut == ((0,), (1,))
        assert res.new_cut == ((0, 1),)           # re-fused single pass
        assert res.new_est_us * rec.min_gain <= res.old_est_us
        assert res.gain > 1.0
        assert rec.stats_dict()["swapped"] == 1

        out_new = sess.launch(res.gexec, x).outputs[0].read()
        np.testing.assert_array_equal(out_old, out_new)   # bit-identical
        # the healing ladder never fired: these are the re-cut kernels
        assert sess.recovery.as_dict()["fallback_nodewise"] == 0
        # the win is real on the modelled engine timeline, not just in
        # the estimator that proposed it
        mark = ctx.engine_end_us
        sess.launch(res.gexec, x).wait()
        assert ctx.engine_end_us - mark < old_replay_us

        res.gexec.release()
        misses_before = sess.cache.stats.misses
        gx2 = sess.instantiate(g)                 # rides the adopted plan
        assert tuple(tuple(p.node_ids)
                     for p in gx2.partitions) == res.new_cut
        sess.launch(gx2, x).wait()
        assert sess.cache.stats.misses == misses_before   # fully warm


def test_recut_refuses_optimistic_split_of_fused_cut():
    """Co-residency honesty: splitting the fused mega-partition looks
    like a win if each half is priced against the full fabric (three
    replicas each), but an instantiated graph's partitions SHARE it —
    the split is measurably slower.  The estimator must price the
    shared budget and keep the fused cut even at streaming-dominated
    batch sizes."""
    x = np.linspace(0, 1, 4_000_000).astype(np.float32)
    with Session([Device("a", SPEC)]) as sess:
        sess.profiles = ProfileStore(cache=sess.cache)
        g = _chain_graph(sess)
        gx = sess.instantiate(g)
        assert gx.n_partitions == 1               # greedy fuses the chain
        for _ in range(3):
            sess.launch(gx, x).wait()
        misses_before = sess.cache.stats.misses
        res = ReCutter(sess, sess.profiles).consider(g)
        assert not res.swapped and res.reason == "kept"
        assert res.new_cut == res.old_cut == ((0, 1),)
        assert sess.cache.stats.misses == misses_before   # no compile


def test_recut_never_worse_guard_config_dominated():
    """Small batches are config-charge-dominated: the DP agrees with the
    greedy cut and the re-cutter must neither swap nor compile."""
    x = np.linspace(0, 1, 10_000).astype(np.float32)
    with Session([Device("a", SPEC)]) as sess:
        sess.profiles = ProfileStore(cache=sess.cache)
        g = _chain_graph(sess)
        gx = sess.instantiate(g)
        for _ in range(3):
            sess.launch(gx, x).wait()
        misses_before = sess.cache.stats.misses
        res = ReCutter(sess, sess.profiles).consider(g)
        assert not res.swapped and res.reason == "kept"
        assert res.gain == 1.0
        assert res.new_est_us >= res.old_est_us / 1.01    # never worse
        assert sess.cache.stats.misses == misses_before   # no compile


def test_recut_requires_a_hot_matching_profile():
    with Session([Device("a", SPEC)]) as sess:
        store = ProfileStore(cache=sess.cache)
        g = _chain_graph(sess)
        g.freeze()
        rec = ReCutter(sess, store)
        res = rec.consider(g)                     # never replayed
        assert not res.swapped and res.reason == "cold"
        assert rec.stats_dict() == dict(attempts=1, swapped=0, kept=0,
                                        cold=1, infeasible=0)


# ----------------------------------------------------------- session stats

def test_stats_sections_deterministic_order_and_collision_guard():
    with Session([Device("a", SPEC)]) as sess:
        sess.register_stats_section("zeta", lambda: {"z": 1})
        sess.register_stats_section("alpha", lambda: {"a": 1})
        keys = list(sess.stats())
        # registered sections come last, in name order
        assert keys.index("alpha") == len(keys) - 2
        assert keys.index("zeta") == len(keys) - 1
        for builtin in ("cache", "devices", "queues", "recovery"):
            assert keys.index(builtin) < keys.index("alpha")
        # shadowing a built-in dashboard is refused
        for name in ("cache", "recovery", "profiles", "devices"):
            with pytest.raises(SessionError):
                sess.register_stats_section(name, dict)


def test_profiles_section_appears_when_attached():
    with Session([Device("a", SPEC)]) as sess:
        assert "profiles" not in sess.stats()
        sess.profiles = ProfileStore(cache=sess.cache)
        blob = sess.stats()["profiles"]
        assert blob["profiles"] == 0 and blob["records"] == 0


# ------------------------------------------------------------- serving SLO

TIGHT = SLOClass("tight", priority=25, target_p99_us=1e-6, max_queue=16)


def test_slo_violations_counted_per_class_and_in_metrics():
    rng = np.random.default_rng(0)
    with Session([Device("a", SPEC), Device("b", SPEC)],
                 metrics=MetricsRegistry()) as sess:
        with InferenceServer(sess, ["mamba2"], max_batch=4) as srv:
            dim = srv.zoo["mamba2"].state_dim
            reqs = [Request("mamba2",
                            rng.standard_normal(dim).astype(np.float32),
                            decode_steps=3,
                            slo=TIGHT if i % 2 == 0 else None)
                    for i in range(4)]
            for r in reqs:
                assert srv.submit(r)
            srv.run()
            serving = sess.stats()["serving"]
            # every "tight" completion blows its 1e-6 µs target; the
            # standard-class requests stay inside their 1 s budget
            assert serving["slo_violations"] == {"tight": 2}
            assert serving["latency_us"]["tight"]["n"] == 2
            counters = sess.stats()["obs"]["counters"]
            assert counters["serving.slo_violations.tight"] == 2.0


# --------------------------------------------------- end-to-end trace cover

def test_serving_trace_covers_all_pipeline_boundaries(tmp_path):
    """One traced serve: the trace must contain compile-stage, cache-tier,
    queue, modelled-device and serving-iteration spans."""
    rng = np.random.default_rng(1)
    tracer = Tracer()
    with Session([Device("a", SPEC)], persist_dir=tmp_path,
                 tracer=tracer) as sess:
        # two families on ONE device: their iterations contend for the
        # engine.  Two waves — the first is compile-gated (cold builds
        # dominate readiness), the second runs warm, where the cross-
        # tenant engine contention shows up as queue-wait slices
        with InferenceServer(sess, ["mamba2", "moe"], max_batch=2) as srv:
            for _ in range(2):
                for fam in ("mamba2", "moe"):
                    dim = srv.zoo[fam].state_dim
                    for _ in range(2):
                        assert srv.submit(Request(
                            fam,
                            rng.standard_normal(dim).astype(np.float32),
                            decode_steps=2))
                srv.run()
    cats = tracer.counts_by_cat()
    for cat in ("compile", "cache", "queue", "device", "serving"):
        assert cats.get(cat, 0) > 0, (cat, cats)
    names = {s.name for s in tracer.spans()}
    assert any(n.startswith("serve:step:") for n in names)
    assert "jit:build" in names and "cache:disk" in names
    # queue rows live on dev:<device>/<tenant> tracks
    assert any(s.track.startswith("dev:a/") for s in tracer.spans())
