"""Command queues + events + multi-device scheduler (ISSUE 1 tentpole):
in-order serialization, out-of-order dependency/backfill semantics, the
one-time reconfiguration charge, and resource-safe two-device placement."""

import numpy as np
import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache
from repro.core.overlay import OverlaySpec
from repro.core.queue import user_event
from repro.core.runtime import (Buffer, Context, Device, Scheduler,
                                SchedulerError)

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
X = np.linspace(-2, 2, 512).astype(np.float32)


def _ctx():
    return Context(Device("d", SPEC), cache=JITCache())


# ------------------------------------------------------------------- events

def test_in_order_queue_preserves_enqueue_order():
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue(in_order=True)
    events = [q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
              for _ in range(4)]
    for prev, ev in zip(events, events[1:]):
        assert ev.t_submit_us >= prev.t_end_us
        assert ev.deps[-1] is prev            # implicit serialization dep
    # timeline is strictly ordered as enqueued
    assert [e.t_end_us for e in events] == sorted(e.t_end_us for e in events)


def test_out_of_order_queue_respects_event_dependencies():
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue(in_order=False)
    # first enqueue loads the configuration at t=0, so later kernels of the
    # same program are allowed to backfill
    q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    gate = user_event(t_end_us=10_000.0)
    blocked = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)),
                               wait_for=[gate])
    free = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    assert blocked.t_submit_us >= 10_000.0    # waits for its dependency
    assert free.t_end_us < blocked.t_submit_us  # backfills the idle gap


def test_backfill_never_runs_on_unconfigured_overlay():
    """Regression: a kernel may only backfill into a timeline gap if its
    configuration is already active there — otherwise it appends, because a
    mid-history bitstream load would rewrite what later kernels observed."""
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue(in_order=False)
    gate = user_event(t_end_us=10_000.0)
    first = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)),
                             wait_for=[gate])     # config loads at t=10000
    second = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    # before t=10000 the overlay was never configured: no backfill allowed
    assert second.t_submit_us >= first.t_submit_us
    assert second.config_us == 0.0 or second.t_start_us >= first.t_submit_us


def test_late_compile_event_blocks_backfill_into_earlier_gap():
    """Satellite (ISSUE 4): a kernel chained onto a compile event that
    finishes LATE must not backfill an idle gap earlier on the timeline —
    even one where its configuration is already active.  The compile event
    is a dependency like any other: ready time floors the gap search."""
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue(in_order=False)
    first = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    gate = user_event(t_end_us=10_000.0)
    q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)),
                     wait_for=[gate])          # busy [10000, ...]
    # an attractive idle gap exists at [first.t_end_us, 10000) and poly1's
    # config IS active there — but this kernel's JIT build only finishes at
    # t=7000 on the modelled clock (Session.enqueue chains this event)
    compile_done = user_event(t_end_us=7_000.0, name="jit:poly1")
    late = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)),
                            wait_for=[compile_done])
    assert first.t_end_us < 7_000.0            # the early gap was there
    assert late.t_submit_us >= 7_000.0         # ...but compile gates it
    assert late.config_us == 0.0               # config active: no reload
    assert late.t_end_us < 10_000.0            # it DID backfill, post-gate


def test_barrier_blocks_backfill_on_out_of_order_queue():
    """Regression: commands enqueued after a barrier must not start before
    it, even on an out-of-order queue with an idle gap to backfill."""
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue(in_order=False)
    q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))  # config @ 0
    gate = user_event(t_end_us=10_000.0)
    q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)), wait_for=[gate])
    bar = q.enqueue_barrier()
    late = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    assert bar.t_end_us >= 10_000.0
    assert late.t_submit_us >= bar.t_end_us    # no backfill past the fence


def test_reconfiguration_charged_once_per_program():
    ctx = _ctx()
    p1 = ctx.build_program(BENCHMARKS["poly1"][0], max_replicas=4)
    q = ctx.create_queue()
    e1 = q.enqueue_kernel(p1.create_kernel().set_args(Buffer(X)))
    e2 = q.enqueue_kernel(p1.create_kernel().set_args(Buffer(X)))
    assert e1.config_us > 0.0                 # first load pays the config
    assert e2.config_us == 0.0                # overlay already configured
    p2 = ctx.build_program(BENCHMARKS["chebyshev"][0], max_replicas=4)
    e3 = q.enqueue_kernel(p2.create_kernel().set_args(Buffer(X)))
    e4 = q.enqueue_kernel(p1.create_kernel().set_args(Buffer(X)))
    assert e3.config_us > 0.0                 # kernel swap reconfigures
    assert e4.config_us > 0.0                 # and swapping back does too


def test_event_outputs_and_profile():
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue()
    ev = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    (out,) = ev.wait()
    np.testing.assert_allclose(out.read(), ((3 * X + 5) * X - 7) * X + 9,
                               rtol=1e-4, atol=1e-4)
    assert ev.latency_us >= ev.exec_us > 0
    assert q.throughput_kernels_per_sec() > 0
    assert q.profile()[0]["kernel"] == prog.compiled.name


@pytest.mark.parametrize("source", ["host_array", "kernel_output"])
def test_buffer_read_is_a_read_only_view(source):
    """``Buffer.read`` copies nothing, yet nothing written through what it
    returns reaches the Buffer."""
    if source == "host_array":
        buf = Buffer(X.copy())
    else:
        ctx = _ctx()
        prog = ctx.build_program(BENCHMARKS["poly1"][0])
        (buf,) = ctx.create_queue().enqueue_kernel(
            prog.create_kernel().set_args(Buffer(X))).wait()
    before = buf.data.copy()
    got = buf.read()
    assert np.shares_memory(got, buf.data)
    with pytest.raises(ValueError, match="read-only"):
        got[0] = 123.0
    np.testing.assert_array_equal(buf.data, before)
    np.testing.assert_array_equal(buf.read(), before)


def test_barrier_orders_across_out_of_order_queue():
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    q = ctx.create_queue(in_order=False)
    before = [q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
              for _ in range(3)]
    bar = q.enqueue_barrier()
    after = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    assert bar.t_end_us >= max(e.t_end_us for e in before)
    assert after.t_submit_us >= bar.t_end_us


def test_queues_share_one_device_engine():
    """Two queues on one context contend for the same overlay: their busy
    intervals never overlap."""
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["poly1"][0])
    qa = ctx.create_queue()
    qb = ctx.create_queue()
    for _ in range(3):
        qa.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
        qb.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))
    spans = sorted((e.t_submit_us, e.t_end_us)
                   for e in qa.events + qb.events)
    for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
        assert s1 >= e0 - 1e-9, spans


# ---------------------------------------------------------------- scheduler

def test_two_device_scheduler_never_double_books():
    """Acceptance: concurrent kernels across a two-device fleet never
    overcommit any device's FUs/IO, and the ledger stays consistent."""
    sched = Scheduler([Device("a", SPEC), Device("b", SPEC)])
    progs = []
    for name in ("poly1", "chebyshev", "poly2", "sgfilter", "mibench"):
        progs.append(sched.build(BENCHMARKS[name][0]))
        for dev in sched.devices:
            assert 0 <= dev.fu_used <= dev.spec.n_fus
            assert 0 <= dev.io_used <= dev.spec.n_io
        assert sched.ledger_consistent()
    # both devices host work (the fleet actually spreads load)
    assert all(l["programs"] >= 1 for l in sched.ledger().values())
    # resident programs (shedding may have replaced early handles) exactly
    # account for every FU the ledger says is in use
    resident = [p for c in sched.contexts.values() for p in c.programs]
    assert (sum(p.compiled.plan.fus_used for p in resident) ==
            sum(d.fu_used for d in sched.devices))


def test_scheduler_sheds_replicas_on_busy_fleet():
    """When no device has free fabric, the scheduler halves the largest
    resident program instead of failing."""
    sched = Scheduler([Device("a", SPEC)])
    big = sched.build(BENCHMARKS["poly1"][0])       # fills the overlay
    r0 = big.compiled.plan.replicas
    assert sched.devices[0].fu_free < big.compiled.fug.n_fus
    nxt = sched.build(BENCHMARKS["chebyshev"][0])   # forces shedding
    assert nxt.compiled.plan.replicas >= 1
    # the shed program's handle stays valid: the smaller artifact was
    # swapped in place, not released out from under the owner
    assert not big.released
    assert big.compiled.plan.replicas < r0
    big.create_kernel()                              # still usable
    assert sched.ledger_consistent()


def test_failed_enqueue_leaves_timeline_clean():
    """Regression: a kernel rejected at validation (wrong arg count) must
    not leave a phantom busy interval or config switch on the timeline."""
    ctx = _ctx()
    prog = ctx.build_program(BENCHMARKS["sgfilter"][0])   # 2-input kernel
    q = ctx.create_queue()
    with pytest.raises(RuntimeError):
        q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X)))  # 1 buf
    assert ctx._engine_busy == [] and ctx._config_switches == []
    ok = q.enqueue_kernel(prog.create_kernel().set_args(Buffer(X), Buffer(X)))
    assert ok.config_us > 0.0          # first real enqueue pays the config


def test_queue_rejects_program_from_other_device():
    """A program built on one device cannot be enqueued on another device's
    queue — timing and config history would silently be wrong."""
    sched = Scheduler([Device("a", SPEC), Device("b", SPEC)])
    pa = sched.contexts["a"].build_program(BENCHMARKS["poly1"][0],
                                           max_replicas=2)
    qb = sched.contexts["b"].create_queue()
    with pytest.raises(RuntimeError):
        qb.enqueue_kernel(pa.create_kernel().set_args(Buffer(X)))
    assert qb.events == []


def test_scheduler_error_when_nothing_sheddable():
    tiny = OverlaySpec(width=2, height=2)
    sched = Scheduler([Device("t", tiny)])
    with pytest.raises(SchedulerError):
        # mibench needs more FUs than a 2x2 overlay has
        sched.build(BENCHMARKS["mibench"][0])


def test_scheduler_shares_cache_across_devices():
    sched = Scheduler([Device("a", SPEC), Device("b", SPEC)])
    p0 = sched.build(BENCHMARKS["poly1"][0])
    p1 = sched.build(BENCHMARKS["poly1"][0])       # other device, same key
    assert p1.compiled is p0.compiled
    assert sched.cache.stats.hits >= 1
