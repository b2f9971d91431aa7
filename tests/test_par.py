"""Place & route & latency-balance & bitstream: structural invariants."""

import pytest

from repro.core.bitstream import parse_header
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec, RoutingGraph
from repro.core.place import PlacementError
from repro.configs.paper_suite import BENCHMARKS

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)


@pytest.fixture(scope="module")
def cheb():
    return jit_compile(BENCHMARKS["chebyshev"][0], SPEC)


def test_placement_is_injective(cheb):
    positions = list(cheb.placement.fu_pos.values())
    assert len(positions) == len(set(positions)), "two FUs on one tile"


def test_placement_within_grid(cheb):
    for (x, y) in cheb.placement.fu_pos.values():
        assert 0 <= x < SPEC.width and 0 <= y < SPEC.height


def test_io_on_perimeter(cheb):
    for (x, y) in list(cheb.placement.in_pos.values()) + \
            list(cheb.placement.out_pos.values()):
        assert x in (-1, SPEC.width) or y in (-1, SPEC.height)


def test_routing_respects_capacity(cheb):
    rg = RoutingGraph(SPEC)
    usage = {}
    seen = set()
    # recount tree edges once per net (nets sharing a source share a tree)
    for net in cheb.routing.nets:
        for e in zip(net.path, net.path[1:]):
            key = (net.skind, net.src, e)
            if key in seen:
                continue
            seen.add(key)
            usage[e] = usage.get(e, 0) + 1
    for e, u in usage.items():
        assert u <= rg.capacity[e], f"overused bundle {e}"


def test_routes_connect_endpoints(cheb):
    pl = cheb.placement
    for net in cheb.routing.nets:
        src = (pl.fu_pos[net.src] if net.skind == "fu"
               else pl.in_pos[net.src])
        dst = (pl.fu_pos[net.dst] if net.dkind == "fu"
               else pl.out_pos[net.dst])
        assert net.path[0] == src and net.path[-1] == dst
        # 4-connected steps only
        for (ax, ay), (bx, by) in zip(net.path, net.path[1:]):
            assert abs(ax - bx) + abs(ay - by) == 1


def test_latency_balanced(cheb):
    """All inputs of every FU arrive in the same cycle after delays."""
    lat, routing, fug = cheb.latency, cheb.routing, cheb.fug
    depth_of = {s.sid: len(s.members) * SPEC.fu_latency for s in fug.supers}
    for net in routing.nets:
        if net.dkind != "fu":
            continue
        src_ready = 0 if net.skind == "in" else lat.ready[net.src]
        arrival = src_ready + net.hops + \
            lat.delays.get((net.dst[0], net.dst[1], net.port), 0)
        expected = lat.ready[net.dst] - depth_of[net.dst[1]]
        assert arrival == expected, f"unbalanced input at {net.dst}"


def test_latency_within_capacity(cheb):
    assert cheb.latency.max_delay_used <= SPEC.max_delay


def test_bitstream_header_roundtrip(cheb):
    h = parse_header(cheb.bitstream)
    assert h["width"] == 8 and h["height"] == 8
    assert h["replicas"] == cheb.plan.replicas
    assert h["tiles_used"] == len(cheb.placement.fu_pos)


def test_bitstream_size_order_of_magnitude(cheb):
    # paper: 1061 bytes for an 8x8 overlay config
    assert 200 < cheb.bitstream.n_bytes < 20_000


def test_kernel_too_big_raises():
    tiny = OverlaySpec(width=2, height=2, dsp_per_fu=1)
    big_src = BENCHMARKS["sgfilter"][0]
    with pytest.raises(PlacementError):
        jit_compile(big_src, tiny)


def test_deterministic_given_seed():
    a = jit_compile(BENCHMARKS["poly1"][0], SPEC, opts=CompileOptions(seed=7))
    b = jit_compile(BENCHMARKS["poly1"][0], SPEC, opts=CompileOptions(seed=7))
    assert a.bitstream.data == b.bitstream.data
    assert a.placement.fu_pos == b.placement.fu_pos
