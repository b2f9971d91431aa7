"""Template-stamped P&R (ISSUE 2/3): template-vs-joint parity, four-edge
stamp legality, gap fill to the resource plan, replica-count changes running
no place/route stage, and scheduler re-inflation through the cached
template."""

import numpy as np
import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache, make_template_key
from repro.core.fuse import to_fu_graph
from repro.core.ir import compile_opencl_to_dfg
from repro.core.jit import jit_compile
from repro.core.latency import balance
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec, RoutingGraph
from repro.core.runtime import Device, Scheduler
from repro.core.template import (build_template, estimate_capacity, gap_fill,
                                 stamp)

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
# 4 pads per perimeter tile: deep stamp bands become legal, so stamped
# replicas must route their I/O through vertical trunks across other bands
TRUNK_SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2, io_per_edge_tile=4)
# tall fabric: the long perimeters are east/west, so side slots dominate and
# band-1 side slots must route through *horizontal* trunks
TALL_SPEC = OverlaySpec(width=8, height=32, dsp_per_fu=2, io_per_edge_tile=4)


def _routing_overuse(routing, spec):
    """Recount tree-edge usage (once per source net) against capacity."""
    rg = RoutingGraph(spec)
    usage = {}
    seen = set()
    for net in routing.nets:
        for e in zip(net.path, net.path[1:]):
            key = (net.skind, net.src, e)
            if key in seen:
                continue
            seen.add(key)
            usage[e] = usage.get(e, 0) + 1
    return [(e, u, rg.capacity.get(e)) for e, u in usage.items()
            if e not in rg.capacity or u > rg.capacity[e]]


def _channel_overuse(ck, spec):
    return _routing_overuse(ck.routing, spec)


# ------------------------------------------------------------------ parity

@pytest.mark.parametrize("name", ["chebyshev", "mibench", "qspline",
                                  "sgfilter"])
def test_template_vs_joint_parity(name):
    """Same replica budget through both P&R paths: identical FU/IO usage,
    both legal (no channel overuse), both latency-balanced."""
    src = BENCHMARKS[name][0]
    ck_t = jit_compile(src, SPEC,
                       opts=CompileOptions(max_replicas=4, pr_mode="template"))
    ck_j = jit_compile(src, SPEC,
                       opts=CompileOptions(max_replicas=4, pr_mode="joint"))
    assert ck_t.pr_path == "template" and ck_j.pr_path == "joint"
    assert ck_t.plan.replicas == ck_j.plan.replicas == 4
    assert ck_t.plan.fus_used == ck_j.plan.fus_used
    assert ck_t.plan.io_used == ck_j.plan.io_used
    assert _channel_overuse(ck_t, SPEC) == []
    assert _channel_overuse(ck_j, SPEC) == []
    x = np.linspace(-1, 1, 128).astype(np.float32)
    xs = [x] * len(ck_t.dfg.inputs)
    np.testing.assert_allclose(ck_t.run_reference(*xs),
                               ck_j.run_reference(*xs), rtol=1e-5)


def test_stamped_latency_equals_recomputed_stage():
    """The stamped LatencyAssignment must equal re-running the latency stage
    on the stamped routing — stamping skips the stage losslessly (this is
    the 'identical latency-balance depth' parity claim, exactly)."""
    for spec, r in ((SPEC, 8), (TRUNK_SPEC, 20)):
        ck = jit_compile(BENCHMARKS["poly1"][0], spec,
                         opts=CompileOptions(max_replicas=r,
                                             pr_mode="template"))
        assert ck.plan.replicas == r
        lat = balance(ck.fug, spec, ck.routing)
        assert lat.delays == ck.latency.delays
        assert lat.ready == ck.latency.ready
        assert lat.out_ready == ck.latency.out_ready
        assert lat.pipeline_depth == ck.latency.pipeline_depth


def test_template_deterministic_by_seed():
    a = jit_compile(BENCHMARKS["chebyshev"][0], SPEC,
                    opts=CompileOptions(max_replicas=8, pr_mode="template",
                                        seed=3))
    b = jit_compile(BENCHMARKS["chebyshev"][0], SPEC,
                    opts=CompileOptions(max_replicas=8, pr_mode="template",
                                        seed=3))
    assert a.bitstream.data == b.bitstream.data
    assert a.placement.fu_pos == b.placement.fu_pos


def test_trunk_bands_route_and_evaluate():
    """Deep stamp bands (vertical IO trunks across shallower bands) stay
    within channel capacity and compute the right values."""
    ck = jit_compile(BENCHMARKS["poly1"][0], TRUNK_SPEC,
                     opts=CompileOptions(pr_mode="template"))
    assert ck.plan.replicas > 16          # more than the perimeter-only rows
    assert _channel_overuse(ck, TRUNK_SPEC) == []
    x = np.linspace(-2, 2, 256).astype(np.float32)
    np.testing.assert_allclose(ck.run_reference(x),
                               ((3 * x + 5) * x - 7) * x + 9,
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- stamp legality

def test_stamped_regions_never_overlap():
    """Property: across every kernel, no tile hosts two FUs and no pad
    coordinate exceeds its physical multiplicity."""
    from repro.core.template import TemplateError
    checked = 0
    for name in sorted(BENCHMARKS):
        fug = to_fu_graph(compile_opencl_to_dfg(BENCHMARKS[name][0]),
                          dsp_per_fu=SPEC.dsp_per_fu)
        try:
            tmpl = build_template(fug, SPEC)
        except TemplateError:
            continue
        checked += 1
        placement, routing, _lat = stamp(tmpl, SPEC, tmpl.capacity)
        tiles = list(placement.fu_pos.values())
        assert len(tiles) == len(set(tiles)), f"{name}: FU overlap"
        for (x, y) in tiles:
            assert 0 <= x < SPEC.width and 0 <= y < SPEC.height
        pads = list(placement.in_pos.values()) + \
            list(placement.out_pos.values())
        for (x, y) in pads:
            assert x in (-1, SPEC.width) or y in (-1, SPEC.height)
        from collections import Counter
        for coord, n in Counter(pads).items():
            assert n <= SPEC.io_per_edge_tile, \
                f"{name}: pad {coord} over multiplicity"
    assert checked >= 4, "property test ran vacuously"


def test_stamped_property_random_kernels():
    """Hypothesis sweep: random polynomial kernels never produce overlapping
    stamps, off-grid tiles, or over-multiplicity pads at any replica count."""
    st = pytest.importorskip("hypothesis.strategies")
    hypothesis = pytest.importorskip("hypothesis")
    from repro.core.dfg import optimize, trace
    from repro.core.ir import _lower_consts

    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 50), width=st.integers(6, 16),
                      r=st.integers(1, 6))
    def check(seed, width, r):
        rng = np.random.default_rng(seed)
        c = rng.uniform(-2, 2, 4).round(2)

        def kern(x):
            return ((c[0] * x + c[1]) * x + c[2]) * x + c[3]

        spec = OverlaySpec(width=width, height=8)
        g = optimize(_lower_consts(trace(kern, 1, f"rand{seed}")))
        fug = to_fu_graph(g, dsp_per_fu=spec.dsp_per_fu)
        tmpl = build_template(fug, spec, seed=seed)
        n = min(r, tmpl.capacity)
        placement, routing, lat = stamp(tmpl, spec, n)
        tiles = list(placement.fu_pos.values())
        assert len(tiles) == len(set(tiles))
        assert all(0 <= x < spec.width and 0 <= y < spec.height
                   for x, y in tiles)
        pads = list(placement.in_pos.values()) + \
            list(placement.out_pos.values())
        assert all(x in (-1, spec.width) or y in (-1, spec.height)
                   for x, y in pads)
        # every net path is 4-connected and starts/ends at its endpoints
        for net in routing.nets:
            for (ax, ay), (bx, by) in zip(net.path, net.path[1:]):
                assert abs(ax - bx) + abs(ay - by) == 1

    check()


# -------------------------------------------------- stage-time assertions

def test_replica_change_on_cached_template_runs_no_par_stage():
    """Acceptance: with the template cached, changing the replica count runs
    no place/route/latency stage — only stamping."""
    cache = JITCache()
    src = BENCHMARKS["chebyshev"][0]
    cold = jit_compile(src, SPEC, cache=cache,
                       opts=CompileOptions(max_replicas=8, pr_mode="template"))
    assert cold.pr_path == "template"
    assert cold.stage_times_ms["place"] > 0          # template was built
    assert cache.stats.template_misses == 1

    warm = jit_compile(src, SPEC, cache=cache,
                       opts=CompileOptions(max_replicas=4, pr_mode="template"))
    assert warm.plan.replicas == 4                   # genuinely rebuilt
    assert warm is not cold
    assert cache.stats.template_hits == 1
    assert warm.stage_times_ms["place"] == 0.0
    assert warm.stage_times_ms["route"] == 0.0
    assert warm.stage_times_ms["latency"] == 0.0
    assert warm.stage_times_ms["stamp"] > 0.0


def test_template_key_independent_of_free_snapshot():
    g = compile_opencl_to_dfg(BENCHMARKS["poly1"][0])
    assert make_template_key(g, SPEC) == make_template_key(g, SPEC)
    assert make_template_key(g, SPEC) != make_template_key(g, SPEC, seed=1)
    assert make_template_key(g, SPEC) != \
        make_template_key(g, TRUNK_SPEC)


def test_auto_mode_never_degrades_replication():
    """auto keeps resource-aware maximal replication ON the template fast
    path: four-edge stamping + gap fill reach the full resource plan, so an
    uncapped poly1 build (which used to need the joint annealer for its
    four-perimeter fill) never runs a joint stage."""
    ck = jit_compile(BENCHMARKS["poly1"][0], SPEC)
    assert ck.pr_path == "template"
    assert "template_probe" not in ck.stage_times_ms    # joint never probed
    uncapped_joint = jit_compile(BENCHMARKS["poly1"][0], SPEC,
                                 opts=CompileOptions(pr_mode="joint"))
    assert ck.plan.replicas >= uncapped_joint.plan.replicas
    # ...and uses the pure stamp when the request is the binding constraint
    capped = jit_compile(BENCHMARKS["poly1"][0], SPEC,
                         opts=CompileOptions(max_replicas=8))
    assert capped.pr_path == "template"
    assert capped.plan.replicas == 8
    assert "infill" not in capped.stage_times_ms        # stamp grid sufficed


# --------------------------------------------------------------- four edges

def test_four_edge_slots_used_and_legal():
    """Uncapped builds use all four perimeter edges: the verified slot list
    contains W/E slots and the full-capacity stamp stays legal."""
    fug = to_fu_graph(compile_opencl_to_dfg(BENCHMARKS["poly1"][0]),
                      dsp_per_fu=SPEC.dsp_per_fu)
    tmpl = build_template(fug, SPEC)
    assert {"N", "S", "W", "E"} <= {s.edge for s in tmpl.slots}
    placement, routing, _lat = stamp(tmpl, SPEC, tmpl.capacity)
    tiles = list(placement.fu_pos.values())
    assert len(tiles) == len(set(tiles))
    assert _routing_overuse(routing, SPEC) == []


def test_side_trunk_bands_route_and_balance():
    """On a tall fabric the long perimeters are east/west: band-1 side slots
    splice *horizontal* trunks, and the closed-form latency composition must
    still equal re-running the latency stage."""
    fug = to_fu_graph(compile_opencl_to_dfg(BENCHMARKS["poly1"][0]),
                      dsp_per_fu=TALL_SPEC.dsp_per_fu)
    tmpl = build_template(fug, TALL_SPEC)
    assert any(s.edge in ("W", "E") and s.band >= 1 for s in tmpl.slots)
    placement, routing, lat = stamp(tmpl, TALL_SPEC, tmpl.capacity)
    assert _routing_overuse(routing, TALL_SPEC) == []
    tiles = list(placement.fu_pos.values())
    assert len(tiles) == len(set(tiles))
    relat = balance(fug, TALL_SPEC, routing)
    assert relat.delays == lat.delays
    assert relat.ready == lat.ready
    assert relat.out_ready == lat.out_ready


def test_vectorized_edge_counting_matches_reference():
    """The numpy slot verifier counts exactly what the python reference
    multiset counts, per slot, on every edge/band combination."""
    import numpy as np
    from repro.core.template import (_chain_edges, _encode_edges,
                                     _net_edge_arrays, _slot_edge_multiset,
                                     _tx_interior)
    for spec in (SPEC, TALL_SPEC):
        fug = to_fu_graph(compile_opencl_to_dfg(BENCHMARKS["poly1"][0]),
                          dsp_per_fu=spec.dsp_per_fu)
        tmpl = build_template(fug, spec)
        interior, in_cols, out_cols = _net_edge_arrays(tmpl.nets)
        for slot in tmpl.slots:
            ref = _slot_edge_multiset(tmpl.nets, slot, spec, tmpl.h)
            ref_codes = {}
            for (a, b), n in ref.items():
                e = np.asarray([[a[0], a[1], b[0], b[1]]], np.int64)
                ref_codes[int(_encode_edges(e, spec)[0])] = n
            e = np.concatenate([
                _tx_interior(interior, slot, spec, tmpl.h),
                _chain_edges(in_cols, slot, spec, tmpl.h, outbound=False),
                _chain_edges(out_cols, slot, spec, tmpl.h, outbound=True)])
            codes, counts = np.unique(_encode_edges(e, spec),
                                      return_counts=True)
            assert dict(zip(codes.tolist(), counts.tolist())) == ref_codes, \
                f"vectorized/reference mismatch at {slot}"


# ----------------------------------------------------------------- gap fill

def test_gap_fill_reaches_full_plan():
    """An uncapped build past the stamp-grid capacity gap-fills remnant
    replicas up to the full resource plan, stays legal, and computes the
    right values."""
    from repro.core.replicate import plan_replication
    spec = OverlaySpec(width=32, height=8, dsp_per_fu=2)
    ck = jit_compile(BENCHMARKS["chebyshev"][0], spec)
    plan = plan_replication(ck.fug, spec)
    assert ck.pr_path == "template"
    assert ck.stage_times_ms.get("infill", 0.0) > 0.0
    assert ck.plan.replicas == plan.replicas
    assert _channel_overuse(ck, spec) == []
    tiles = list(ck.placement.fu_pos.values())
    assert len(tiles) == len(set(tiles))
    relat = balance(ck.fug, spec, ck.routing)
    assert relat.delays == ck.latency.delays
    assert relat.out_ready == ck.latency.out_ready
    x = np.linspace(-1, 1, 128).astype(np.float32)
    ref = jit_compile(BENCHMARKS["chebyshev"][0], spec,
                      opts=CompileOptions(max_replicas=1))
    np.testing.assert_allclose(ck.run_reference(x), ref.run_reference(x),
                               rtol=1e-5)


def test_gap_fill_deterministic_by_seed():
    spec = OverlaySpec(width=32, height=8, dsp_per_fu=2)
    a = jit_compile(BENCHMARKS["chebyshev"][0], spec,
                    opts=CompileOptions(seed=5))
    b = jit_compile(BENCHMARKS["chebyshev"][0], spec,
                    opts=CompileOptions(seed=5))
    assert a.stage_times_ms.get("infill", 0.0) > 0.0
    assert a.bitstream.data == b.bitstream.data
    assert a.placement.fu_pos == b.placement.fu_pos


def test_gap_fill_partial_progress_is_kept():
    """gap_fill returns what it achieved when the target exceeds the fabric:
    every added replica is legal, none are torn down."""
    fug = to_fu_graph(compile_opencl_to_dfg(BENCHMARKS["poly1"][0]),
                      dsp_per_fu=SPEC.dsp_per_fu)
    tmpl = build_template(fug, SPEC)
    placement, routing, lat = stamp(tmpl, SPEC, tmpl.capacity)
    placement, routing, lat, got = gap_fill(
        fug, SPEC, placement, routing, lat, target=10_000)
    assert tmpl.capacity <= got < 10_000
    assert _routing_overuse(routing, SPEC) == []
    tiles = list(placement.fu_pos.values())
    assert len(tiles) == len(set(tiles))


def test_estimate_capacity_bounds_template():
    for name in sorted(BENCHMARKS):
        fug = to_fu_graph(compile_opencl_to_dfg(BENCHMARKS[name][0]),
                          dsp_per_fu=SPEC.dsp_per_fu)
        est = estimate_capacity(fug, SPEC)
        if est == 0:
            continue
        tmpl = build_template(fug, SPEC)
        assert 1 <= tmpl.capacity <= est


# ------------------------------------------------------------ re-inflation

def test_scheduler_reinflates_on_release():
    """ROADMAP open item: when fabric frees up, shed programs grow back to
    their planned replica count — without any P&R stage rerunning."""
    sched = Scheduler([Device("a", SPEC)])
    a = sched.build(BENCHMARKS["poly1"][0],
                    opts=CompileOptions(max_replicas=16))      # 32 FUs
    first = a.compiled
    c = sched.build(BENCHMARKS["chebyshev"][0],
                    opts=CompileOptions(max_replicas=10))  # 30 FUs
    assert a.compiled.plan.replicas == 16 and a.planned_replicas == 16
    b = sched.build(BENCHMARKS["sgfilter"][0])    # nothing free: sheds a
    assert a.compiled.plan.replicas < 16
    assert b.compiled.plan.replicas >= 1
    assert sched.ledger_consistent()

    shrunk = a.compiled.plan.replicas
    # the shed rebuild itself was a re-stamp of the cached template: its
    # full key missed (new replica cap) but no place/route stage ran
    assert a.compiled.pr_path == "template"
    assert a.compiled.stage_times_ms["place"] == 0.0
    assert a.compiled.stage_times_ms["route"] == 0.0
    assert a.compiled.stage_times_ms["stamp"] > 0.0

    c.release()                                    # frees 30 FUs → reinflate
    assert a.compiled.plan.replicas == 16 > shrunk
    assert not a.released
    a.create_kernel()                              # owner handle still valid
    assert sched.ledger_consistent()
    # the growth was served straight from the compile cache: the rebuild's
    # normalized key (effective replica cap 16, 'request'-limited) matches
    # the original build's even though the raw free-FU count differs, so
    # the scheduler got the original artifact back — zero compiler stages
    assert a.compiled is first


def test_reinflation_restores_victim_when_no_growth_possible():
    """Releasing fabric that does NOT make growth possible must leave every
    shed program resident and the ledger intact."""
    sched = Scheduler([Device("a", SPEC)])
    a = sched.build(BENCHMARKS["poly1"][0],
                    opts=CompileOptions(max_replicas=16))
    c = sched.build(BENCHMARKS["chebyshev"][0],
                    opts=CompileOptions(max_replicas=10))
    sched.build(BENCHMARKS["sgfilter"][0])         # sheds a → 8 replicas
    shrunk = a.compiled.plan.replicas
    ctx = sched.contexts["a"]
    ctx.reserve(fus=ctx.device.fu_free)            # pin all remaining fabric
    c.release()                                    # reinflate can't grow a
    assert a.compiled.plan.replicas >= shrunk      # never shrinks
    assert not a.released and a in ctx.programs
    assert sched.ledger_consistent()


# -------------------------------------------------- frontend double-compile

def test_cache_miss_does_not_reoptimize_frontend(monkeypatch):
    """Regression: jit_compile lowers (and optimizes) the kernel for cache
    keying; the frontend stage must not run optimize() on it again."""
    import repro.core.jit as jit_mod
    calls = {"n": 0}
    real = jit_mod.optimize

    def counting(g):
        calls["n"] += 1
        return real(g)

    monkeypatch.setattr(jit_mod, "optimize", counting)
    cache = JITCache()
    ck = jit_compile(BENCHMARKS["poly1"][0], SPEC, cache=cache)
    assert cache.stats.misses == 1 and ck.plan.replicas >= 1
    assert calls["n"] == 0, "frontend re-optimized an already-optimized DFG"

    # python-callable path: lowering for the cache key optimizes exactly
    # once; the frontend stage must not run the pass pipeline again
    calls["n"] = 0
    ck2 = jit_compile(lambda x: x * 2.0 + 1.0, SPEC, cache=cache,
                      opts=CompileOptions(n_inputs=1, name="fn"))
    assert cache.stats.misses == 2 and ck2.plan.replicas >= 1
    assert calls["n"] == 1, "callable cache miss paid the frontend twice"
