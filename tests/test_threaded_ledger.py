"""Ledger thread-safety under the async Session runtime (ISSUE 4
satellites): concurrent Program.release() must never double-credit, and
build/release/shed/re-inflate churn across worker threads must leave
``ledger_consistent()`` true."""

import random
import threading

import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.runtime import (Context, Device, Scheduler, SchedulerError)
from repro.core.session import Session

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
POLY1 = BENCHMARKS["poly1"][0]
CHEB = BENCHMARKS["chebyshev"][0]


def test_concurrent_release_never_double_credits():
    """Regression: release() used to check-then-set ``released`` without
    the ledger lock, so two racing threads could both credit the fabric
    back (device usage would go negative / another tenant's booking would
    be un-booked)."""
    ctx = Context(Device("d", SPEC), cache=JITCache())
    for _ in range(10):
        prog = ctx.build_program(POLY1, opts=CompileOptions(max_replicas=4))
        used = ctx.device.fu_used
        assert used > 0
        start = threading.Barrier(9)

        def racer():
            start.wait()
            prog.release()

        threads = [threading.Thread(target=racer) for _ in range(8)]
        for t in threads:
            t.start()
        start.wait()
        for t in threads:
            t.join()
        assert ctx.device.fu_used == 0 and ctx.device.io_used == 0
        assert ctx.ledger_consistent()


def test_release_during_resize_cannot_double_credit():
    """A tenant disconnecting (release) exactly while the scheduler resizes
    its program must not corrupt the ledger — _resize holds the fleet lock
    and release() is atomic under the context lock.  The uncapped first
    build fills the device, the later builds force it to be SHED, and every
    release then fires reinflate() -> _resize churn on worker threads while
    the shed program's own release races it."""
    cache = JITCache()
    rng = random.Random(0)
    for _ in range(3):
        sched = Scheduler([Device("a", SPEC)], cache=cache)
        big = sched.build(POLY1, CompileOptions(), tenant="big")
        others = [sched.build(CHEB, CompileOptions(max_replicas=4),
                              tenant=f"t{i}") for i in range(2)]
        assert big.compiled.plan.replicas < big.planned_replicas  # was shed
        progs = [big] + others
        rng.shuffle(progs)
        start = threading.Barrier(len(progs) + 1)

        def releaser(p):
            start.wait()
            p.release()         # hook fires reinflate -> _resize churn

        threads = [threading.Thread(target=releaser, args=(p,))
                   for p in progs]
        for t in threads:
            t.start()
        start.wait()
        for t in threads:
            t.join()
        assert sched.ledger_consistent()
        assert sched.devices[0].fu_used == 0


def test_concurrent_tenant_enqueues_never_double_book_engine():
    """Per-tenant queues run on independent host threads under a Session;
    the shared engine timeline is booked under the context timeline lock,
    so concurrent enqueues must never claim overlapping busy intervals."""
    import numpy as np
    x = np.linspace(-1, 1, 1024).astype(np.float32)
    with Session([Device("a", SPEC)], max_workers=2) as sess:
        prog = sess.build(POLY1, CompileOptions(max_replicas=4))
        start = threading.Barrier(4)
        errors = []

        def tenant(i):
            try:
                start.wait()
                for _ in range(10):
                    sess.enqueue(prog, x, tenant=f"t{i}")
            except BaseException as e:       # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=tenant, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        busy = sorted(sess.contexts["a"]._engine_busy)
        assert len(busy) == 40                # every threaded enqueue booked
        for (s0, e0), (s1, e1) in zip(busy, busy[1:]):
            assert s1 >= e0 - 1e-9, (s0, e0, s1, e1)


@pytest.mark.parametrize("n_threads,iters", [(4, 6)])
def test_threaded_build_release_stress_ledger_consistent(n_threads, iters):
    """Satellite acceptance: ledger_consistent() under a threaded stress
    loop of async builds + releases (shed + re-inflate firing throughout)."""
    names = ["poly1", "chebyshev", "poly2", "sgfilter"]
    with Session([Device("a", SPEC), Device("b", SPEC)],
                 max_workers=n_threads) as sess:
        errors = []

        def tenant_loop(i):
            rng = random.Random(i)
            held = []
            try:
                for it in range(iters):
                    src = BENCHMARKS[names[(i + it) % len(names)]][0]
                    fut = sess.compile(src, CompileOptions(max_replicas=4),
                                       tenant=f"t{i}")
                    try:
                        prog = fut.result(120)
                    except SchedulerError:
                        continue              # fleet genuinely full: fine
                    held.append(prog)
                    if rng.random() < 0.6 and held:
                        held.pop(rng.randrange(len(held))).release()
                for p in held:
                    p.release()
            except BaseException as e:        # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=tenant_loop, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert sess.ledger_consistent(), sess.ledger()
        # every tenant released everything: the fleet must drain to zero
        # (single-flight may have shared programs across tenants; releases
        # are idempotent so the drain still holds)
        for dev in sess.devices:
            assert dev.fu_used == 0 and dev.io_used == 0
