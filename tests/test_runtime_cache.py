"""JIT compile cache + resource ledger: hit identity, LRU eviction, snapshot
invalidation, and the build-debits-ledger regression (ISSUE 1)."""

import numpy as np
import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import (JITCache, dfg_fingerprint, kernel_fingerprint,
                              make_cache_key)
from repro.core.dfg import trace
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.runtime import Buffer, Context, Device

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
POLY1 = BENCHMARKS["poly1"][0]
CHEB = BENCHMARKS["chebyshev"][0]


# ------------------------------------------------------------- fingerprints

def test_dfg_fingerprint_stable_and_discriminating():
    g = trace(lambda x: x * 3.0 + 5.0, 1, "a")
    h = trace(lambda x: x * 3.0 + 5.0, 1, "b")       # name must not matter
    assert dfg_fingerprint(g) == dfg_fingerprint(h)
    assert dfg_fingerprint(g) == dfg_fingerprint(g.copy())
    different = trace(lambda x: x * 3.0 + 6.0, 1, "a")
    assert dfg_fingerprint(g) != dfg_fingerprint(different)


def test_callable_closure_constants_change_key():
    """Two lambdas with identical code but different closure constants must
    not share a cache entry (constants surface as DFG immediates)."""
    def make(c):
        return lambda x: x * c + 1.0
    fa = kernel_fingerprint(make(2.0), n_inputs=1)
    fb = kernel_fingerprint(make(3.0), n_inputs=1)
    assert fa != fb


def test_key_includes_free_resource_snapshot():
    k0 = make_cache_key(POLY1, SPEC, free_fus=64, free_io=64)
    k1 = make_cache_key(POLY1, SPEC, free_fus=32, free_io=64)
    assert k0 != k1
    assert make_cache_key(POLY1, SPEC, free_fus=64, free_io=64) == k0


def test_key_normalizes_snapshot_to_replica_cap():
    """Distinct snapshots that imply the same replication plan must share
    one entry: the compiler only consumes the snapshot through the plan.
    chebyshev needs 3 FUs/replica, so one busy FU doesn't change the cap
    (64 // 3 == 63 // 3 == 21) — but crossing a replica boundary does."""
    k0 = make_cache_key(CHEB, SPEC, free_fus=64, free_io=64)
    k1 = make_cache_key(CHEB, SPEC, free_fus=63, free_io=64)
    assert k0 == k1
    k2 = make_cache_key(CHEB, SPEC, free_fus=62, free_io=64)   # cap 20
    assert k2 != k0
    # pr_mode / fill knobs are part of the key
    assert make_cache_key(CHEB, SPEC, free_fus=64, free_io=64,
                          opts=CompileOptions(pr_mode="joint")) != k0


def test_busy_fleet_occupancy_jitter_still_hits():
    """Satellite (ISSUE 3): on a busy device whose occupancy moves by less
    than one replica footprint between requests, the second build is a HIT —
    with raw-snapshot keys it was a guaranteed miss."""
    cache = JITCache()
    ctx = Context(Device("d", SPEC), cache=cache)
    ctx.reserve(fus=1)                      # sub-replica occupancy jitter
    p1 = ctx.build_program(CHEB, opts=CompileOptions(max_replicas=4))
    p1.release()
    ctx.release(fus=1)
    ctx.reserve(fus=2)                      # different snapshot, same cap
    p2 = ctx.build_program(CHEB, opts=CompileOptions(max_replicas=4))
    assert p2.compiled is p1.compiled
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert cache.stats.hit_rate == 0.5


# -------------------------------------------------------------------- cache

def test_cache_hit_returns_identical_compiled_kernel():
    cache = JITCache()
    a = jit_compile(POLY1, SPEC, cache=cache)
    b = jit_compile(POLY1, SPEC, cache=cache)
    assert b is a
    assert cache.stats.hits == 1 and cache.stats.misses == 1


def test_cache_warm_build_much_faster_than_cold():
    """Acceptance: warm (hit) build latency >= 10x lower than cold."""
    import time
    cache = JITCache()
    t0 = time.perf_counter()
    jit_compile(CHEB, SPEC, cache=cache)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jit_compile(CHEB, SPEC, cache=cache)
    warm = time.perf_counter() - t0
    assert warm * 10 <= cold, (cold, warm)


def test_cache_lru_eviction_order():
    cache = JITCache(capacity=2)
    ka = make_cache_key(POLY1, SPEC, free_fus=64, free_io=64)
    kb = make_cache_key(CHEB, SPEC, free_fus=64, free_io=64)
    kc = make_cache_key(BENCHMARKS["poly2"][0], SPEC, free_fus=64, free_io=64)
    cache.put(ka, "A")
    cache.put(kb, "B")
    assert cache.get(ka) == "A"           # refresh A: B is now LRU
    cache.put(kc, "C")
    assert kb not in cache                # B evicted, not A
    assert cache.get(ka) == "A" and cache.get(kc) == "C"
    assert cache.stats.evictions == 1


def test_reservation_invalidates_stale_entries():
    """A build made against a full overlay must not be reused once fabric is
    occupied: the free-resource snapshot is part of the key."""
    cache = JITCache()
    ctx = Context(Device("d", SPEC), cache=cache)
    full = ctx.build_program(CHEB)
    r_full = full.compiled.plan.replicas
    full.release()
    ctx.reserve(fus=SPEC.n_fus - 2 * full.compiled.fug.n_fus)
    small = ctx.build_program(CHEB)
    assert small.compiled is not full.compiled         # cache miss, recompiled
    assert small.compiled.plan.replicas < r_full
    assert cache.stats.misses == 2 and cache.stats.hits == 0


# ------------------------------------------------------------------- ledger

def test_build_debits_ledger_and_release_credits():
    """Regression (ISSUE 1 satellite): a second build must see reduced free
    resources — two builds can no longer each claim the full overlay."""
    ctx = Context(Device("d", SPEC))
    free0 = ctx.device.fu_free
    p1 = ctx.build_program(CHEB, opts=CompileOptions(max_replicas=8))
    assert ctx.device.fu_free == free0 - p1.compiled.plan.fus_used
    assert ctx.device.io_free == SPEC.n_io - p1.compiled.plan.io_used
    p2 = ctx.build_program(CHEB)           # compiled against the remainder
    uncontended = Context(Device("e", SPEC)).build_program(CHEB)
    assert p2.compiled.plan.replicas < uncontended.compiled.plan.replicas
    assert ctx.device.fu_used == (p1.compiled.plan.fus_used +
                                  p2.compiled.plan.fus_used)
    assert ctx.device.fu_used <= SPEC.n_fus
    assert ctx.ledger_consistent()
    p1.release()
    p2.release()
    assert ctx.device.fu_used == 0 and ctx.device.io_used == 0
    p1.release()                            # idempotent
    assert ctx.device.fu_used == 0


def test_over_release_of_reservation_rejected():
    """Crediting more than the outstanding reservation would un-book fabric
    owned by resident programs."""
    ctx = Context(Device("d", SPEC))
    prog = ctx.build_program(POLY1, opts=CompileOptions(max_replicas=4))
    ctx.reserve(fus=4)
    with pytest.raises(RuntimeError):
        ctx.release(fus=10)            # > outstanding reservation
    assert ctx.ledger_consistent()
    ctx.release(fus=4)                 # exact release is fine
    assert ctx.ledger_consistent()
    assert ctx.device.fu_used == prog.compiled.plan.fus_used


def test_released_program_cannot_create_kernels():
    ctx = Context(Device("d", SPEC))
    p = ctx.build_program(POLY1)
    p.release()
    with pytest.raises(RuntimeError):
        p.create_kernel()


def test_stale_kernel_of_released_program_rejected():
    """A Kernel handle created before release() must not execute after it —
    the fabric may already belong to another program."""
    ctx = Context(Device("d", SPEC))
    p = ctx.build_program(POLY1)
    x = np.linspace(-1, 1, 32).astype(np.float32)
    k = p.create_kernel().set_args(Buffer(x))
    p.release()
    with pytest.raises(RuntimeError):
        k.enqueue()
    q = ctx.create_queue()
    with pytest.raises(RuntimeError):
        q.enqueue_kernel(k)
    assert q.events == [] and ctx._engine_busy == []   # nothing was booked


def test_program_context_manager_releases():
    ctx = Context(Device("d", SPEC))
    with ctx.build_program(POLY1) as p:
        assert ctx.device.fu_used == p.compiled.plan.fus_used
        x = np.linspace(-1, 1, 64).astype(np.float32)
        (out,) = p.create_kernel().set_args(Buffer(x)).enqueue()
        np.testing.assert_allclose(out.read(), ((3 * x + 5) * x - 7) * x + 9,
                                   rtol=1e-4, atol=1e-4)
    assert ctx.device.fu_used == 0


def test_str_and_dfg_entry_points_share_one_entry():
    """jit_compile lowers source text to a DFG before keying, so the same
    kernel reached as a string or as a DFG hits one cache entry."""
    from repro.core.ir import compile_opencl_to_dfg
    cache = JITCache()
    a = jit_compile(POLY1, SPEC, cache=cache)
    b = jit_compile(compile_opencl_to_dfg(POLY1), SPEC, cache=cache)
    assert b is a
    assert len(cache) == 1 and cache.stats.hits == 1


def test_shared_cache_across_contexts():
    """A fleet-wide cache: the second device's build of the same kernel at
    the same free snapshot is a hit."""
    cache = JITCache()
    c0 = Context(Device("d0", SPEC), cache=cache)
    c1 = Context(Device("d1", SPEC), cache=cache)
    a = c0.build_program(POLY1)
    b = c1.build_program(POLY1)
    assert b.compiled is a.compiled
    # ...but each device's ledger is debited independently
    assert c0.device.fu_used == c1.device.fu_used == a.compiled.plan.fus_used
