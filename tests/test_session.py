"""Async Session API (ISSUE 4 tentpole): futures-based compilation on a
worker pool, single-flight dedup, compile-chained execution events,
CompileOptions as the cache-key tail, queue-aware makespan placement, and
per-tenant shed priorities."""

import dataclasses
import threading

import numpy as np
import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache, make_cache_key
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.runtime import Device, Scheduler, SchedulerError
from repro.core.session import KernelFuture, Session

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
POLY1 = BENCHMARKS["poly1"][0]
CHEB = BENCHMARKS["chebyshev"][0]
X = np.linspace(-2, 2, 512).astype(np.float32)


# ------------------------------------------------------------ CompileOptions

def test_compile_options_frozen_hashable_validated():
    a = CompileOptions(max_replicas=4, seed=1)
    b = CompileOptions(max_replicas=4, seed=1)
    assert a == b and hash(a) == hash(b)
    assert a != CompileOptions(max_replicas=4, seed=2)
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.seed = 3
    with pytest.raises(ValueError):
        CompileOptions(pr_mode="annealed")
    with pytest.raises(ValueError):
        CompileOptions(min_template_fill=0.0)
    assert a.replace(max_replicas=2).max_replicas == 2
    assert a.max_replicas == 4                      # replace didn't mutate


def test_compile_options_is_the_cache_key_tail():
    """``key_tail`` is hashed into every persisted cache key: its bytes are
    pinned, or every disk-cache entry would go cold.  The seed, which it
    carries, changes the key."""
    opts = CompileOptions(max_replicas=4, seed=2, place_effort=0.5,
                          pr_mode="template")
    assert opts.key_tail() == "2:0.5:template:0.95"
    via_opts = make_cache_key(CHEB, SPEC, free_fus=64, free_io=64,
                              opts=opts)
    assert via_opts != make_cache_key(
        CHEB, SPEC, free_fus=64, free_io=64,
        opts=CompileOptions(max_replicas=4, seed=3, place_effort=0.5,
                            pr_mode="template"))


def _jit(**kw):
    return jit_compile(POLY1, SPEC, **kw)


def _build_program(**kw):
    from repro.core.runtime import Context
    return Context(Device("a", SPEC)).build_program(POLY1, **kw)


def _scheduler_build(**kw):
    return Scheduler([Device("a", SPEC)]).build(POLY1, **kw)


def _cache_key(**kw):
    return make_cache_key(POLY1, SPEC, free_fus=64, free_io=64, **kw)


@pytest.mark.parametrize("entry", [_jit, _build_program, _scheduler_build,
                                   _cache_key],
                         ids=["jit_compile", "build_program",
                              "scheduler_build", "make_cache_key"])
def test_build_settings_travel_only_in_opts(entry):
    """Each entry point takes its build settings in ``opts`` alone: a loose
    build keyword is refused, and ``opts`` is accepted."""
    with pytest.raises(TypeError, match="max_replicas"):
        entry(max_replicas=4)
    assert entry(opts=CompileOptions(max_replicas=4)) is not None


# ------------------------------------------------------------- async compile

def test_compile_returns_before_pipeline_runs_and_single_flights():
    """Acceptance: Session.compile returns without running the pipeline
    inline, and two concurrent compiles of the same key run it ONCE."""
    with Session([Device("a", SPEC)], max_workers=1) as sess:
        gate = threading.Event()
        sess._pool.submit(gate.wait, 30)      # occupy the only worker
        opts = CompileOptions(max_replicas=2)
        f1 = sess.compile(POLY1, opts, tenant="t1")
        f2 = sess.compile(POLY1, opts, tenant="t2")
        # both calls returned; the pipeline cannot have started (worker
        # blocked), so nothing ran inline on this thread
        assert not f1.done() and not f2.done()
        assert sess.cache.stats.misses == 0
        gate.set()
        p1, p2 = f1.result(60), f2.result(60)
        assert p1 is p2                        # joined one in-flight build
        assert sess.cache.stats.singleflight_hits == 1
        # the pipeline ran exactly once: one cache miss, one insertion
        assert sess.cache.stats.misses == 1
        assert sess.cache.stats.insertions == 1
        assert sess.ledger_consistent()


def test_different_opts_do_not_single_flight():
    with Session([Device("a", SPEC)], max_workers=2) as sess:
        f1 = sess.compile(POLY1, CompileOptions(max_replicas=1))
        f2 = sess.compile(POLY1, CompileOptions(max_replicas=2))
        p1, p2 = f1.result(60), f2.result(60)
        assert p1 is not p2
        assert p1.compiled.plan.replicas != p2.compiled.plan.replicas
        assert sess.cache.stats.singleflight_hits == 0


def test_build_error_surfaces_on_the_future():
    tiny = OverlaySpec(width=2, height=2)
    with Session([Device("t", tiny)]) as sess:
        fut = sess.compile(BENCHMARKS["mibench"][0])
        assert isinstance(fut, KernelFuture)
        with pytest.raises(SchedulerError):
            fut.result(60)
        assert sess.ledger_consistent()


# ------------------------------------------------------ compile-chained exec

def test_enqueue_chains_execution_onto_compile_event():
    """Fig. 5 semantics: the kernel cannot submit before its JIT build's
    modelled finish time, so serving latency includes compile latency."""
    with Session([Device("a", SPEC)]) as sess:
        fut = sess.compile(POLY1, CompileOptions(max_replicas=4))
        ev = sess.enqueue(fut, X)
        ce = fut.compile_event()
        assert ce.t_end_us > 0.0               # a real (cold) build took time
        assert ev.t_submit_us >= ce.t_end_us
        assert ce in ev.deps
        (out,) = ev.wait()
        np.testing.assert_allclose(out.read(), ((3 * X + 5) * X - 7) * X + 9,
                                   rtol=1e-4, atol=1e-4)
        assert fut.compile_us > 0.0


def test_warm_compile_runs_no_pipeline_stage():
    """A repeat compile at the same fleet state is a cache hit: the future
    resolves to the SAME artifact and no compiler stage runs.  (Wall-clock
    cheapness is asserted on the raw cache path in test_runtime_cache —
    a ratio here would be flaky under CI load.)"""
    cache = JITCache()
    with Session([Device("a", SPEC)], cache=cache) as sess:
        cold = sess.compile(CHEB, CompileOptions(max_replicas=4))
        cold.result(60).release()           # back to the same fleet state
        misses_after_cold = cache.stats.misses
        warm = sess.compile(CHEB, CompileOptions(max_replicas=4))
        assert warm.result(60).compiled is cold.result().compiled
        assert cache.stats.hits >= 1
        assert cache.stats.misses == misses_after_cold   # no stage ran
        assert warm.compile_us >= 0.0


def test_per_tenant_queues_share_one_device_timeline():
    with Session([Device("a", SPEC)]) as sess:
        prog = sess.build(POLY1, CompileOptions(max_replicas=4))
        ea = sess.enqueue(prog, X, tenant="ta")
        eb = sess.enqueue(prog, X, tenant="tb")
        qa = sess.queue_for("ta", "a")
        qb = sess.queue_for("tb", "a")
        assert qa is not qb and qa.tenant == "ta"
        # distinct tenant streams, one engine: busy spans never overlap
        spans = sorted((e.t_submit_us, e.t_end_us) for e in (ea, eb))
        assert spans[1][0] >= spans[0][1] - 1e-9
        assert sess.finish() >= max(ea.t_end_us, eb.t_end_us)


# ------------------------------------------------------ queue-aware placement

def _loaded_fleet(policy):
    sess = Session([Device("a", SPEC), Device("b", SPEC)], policy=policy)
    # static "other logic" on b: free-fabric ranking will always prefer a
    sess.contexts["b"].reserve(fus=8)
    pa = sess.build(POLY1, CompileOptions(max_replicas=2), tenant="t1")
    assert pa.ctx.device.name == "a"
    for _ in range(20):                      # deep modelled backlog on a
        sess.enqueue(pa, X, tenant="t1")
    return sess


def test_makespan_policy_routes_around_queue_backlog():
    with _loaded_fleet("makespan") as sess:
        pb = sess.build(CHEB, CompileOptions(max_replicas=2), tenant="t2")
        assert pb.ctx.device.name == "b"     # less fabric, but idle engine
        report = sess.makespan_report()
        assert (report["a"]["projected_makespan_us"] >
                report["b"]["projected_makespan_us"])


def test_free_fabric_policy_piles_onto_emptiest_device():
    with _loaded_fleet("free_fabric") as sess:
        pb = sess.build(CHEB, CompileOptions(max_replicas=2), tenant="t2")
        assert pb.ctx.device.name == "a"     # most free FUs, ignores queue


def test_inflight_compile_estimates_spread_submissions():
    """The makespan model counts builds already in flight toward a device:
    booking an estimate on the favoured device pushes the NEXT ranking to
    the other one."""
    sched = Scheduler([Device("a", SPEC), Device("b", SPEC)])
    first = sched._ranked()[0]
    token = sched.book_inflight("some-kernel")
    assert token[0] is first and token[1] > 0.0
    assert sched._ranked()[0] is not first   # estimate visible to ranking
    assert sched._ranked(exclude=token)[0] is first   # but not to its own
    sched.release_inflight(token)
    assert first.pending_compile_us == 0.0


def test_build_estimates_converge_to_observed_times():
    """The EWMA must be recorded under the SAME fingerprint namespace the
    Session books in-flight estimates with (kernel_fingerprint), or the
    makespan model would stay pinned at the cold default forever."""
    from repro.core.cache import kernel_fingerprint
    from repro.core.runtime import DEFAULT_BUILD_EST_US
    sched = Scheduler([Device("a", SPEC)])
    fp = kernel_fingerprint(POLY1)
    assert sched.estimate_build_us(fp) == DEFAULT_BUILD_EST_US
    prog = sched.build(POLY1, CompileOptions(max_replicas=2))
    est = sched.estimate_build_us(fp)
    assert est == pytest.approx(prog.build_ms * 1e3)
    # ...and the Session's submit-time booking reads the refined estimate
    with Session([Device("b", SPEC)], cache=sched.cache) as sess:
        fut = sess.compile(POLY1, CompileOptions(max_replicas=2))
        fut.result(60)
        assert sess.scheduler.estimate_build_us(fp) != DEFAULT_BUILD_EST_US


# -------------------------------------------------------- tenant priorities

def test_low_priority_tenant_is_shed_first():
    spec = OverlaySpec(width=4, height=4, dsp_per_fu=2)
    sched = Scheduler([Device("a", spec)])
    sched.set_priority("gold", 10)
    gold = sched.build(POLY1, CompileOptions(max_replicas=3),
                       tenant="gold")
    bronze = sched.build(CHEB, CompileOptions(max_replicas=2),
                         tenant="bronze")
    assert (gold.compiled.plan.replicas, bronze.compiled.plan.replicas) \
        == (3, 2)
    # sgfilter needs 7 FUs/replica; only 4 free -> forces one shed round
    third = sched.build(BENCHMARKS["sgfilter"][0],
                        CompileOptions(max_replicas=1), tenant="new")
    assert third.compiled.plan.replicas == 1
    assert gold.compiled.plan.replicas == 3          # priority kept intact
    assert bronze.compiled.plan.replicas == 1        # bronze paid the bill
    assert sched.ledger_consistent()


# ------------------------------------------------- one core under the Session

def test_legacy_entry_points_share_the_session_core():
    """``Scheduler.build`` and ``Session.compile`` with equal opts share one
    cache entry: the Session runs the scheduler's one build method."""
    cache = JITCache()
    opts = CompileOptions(max_replicas=4)
    sched = Scheduler([Device("a", SPEC), Device("b", SPEC)], cache=cache)
    p0 = sched.build(POLY1, opts)
    assert p0.opts == opts
    assert sched.contexts[p0.ctx.device.name].ledger_consistent()
    misses = cache.stats.misses
    with Session([Device("c", SPEC)], cache=cache) as sess:
        p1 = sess.compile(POLY1, opts).result(60)
        assert p1.compiled is p0.compiled                    # cache hit
    assert cache.stats.misses == misses
