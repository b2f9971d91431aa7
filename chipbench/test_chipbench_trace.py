"""The reduction from a profiler trace to busy time, idle share, kernel
time, collective time and the breakdown, on hand-built traces, and the
loader on a real (CPU) profile."""

import pytest

from chipbench import tracefile
from chipbench.tracefile import Event

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = tracefile.OPS_LINE, tracefile.MODULES_LINE


def _trace():
    ms = 1e6
    return [
        Event("/host:CPU", "python", tracefile.WINDOW, 0, 100 * ms),
        Event("/host:CPU", "python", "chipbench.launch", 5 * ms, 35 * ms),
        Event("/host:CPU", "python", "chipbench.jit", 60 * ms, 90 * ms),
        # device 0: two overlapping ops, one op past the window's end
        Event(D0, MODS, "jit_overlay_execute(1)", 10 * ms, 30 * ms),
        Event(D0, OPS, "custom-call.1", 10 * ms, 25 * ms),
        Event(D0, OPS, "fusion.2", 20 * ms, 30 * ms),
        Event(D0, OPS, "all-reduce.3", 50 * ms, 55 * ms),
        Event(D0, OPS, "fusion.2", 95 * ms, 120 * ms),
        # device 1: one op
        Event(D1, OPS, "all-gather.4", 0, 10 * ms),
    ]


def test_busy_union_and_idle_share():
    ev = _trace()
    # device 0: [10, 30] + [50, 55] + [95, 100] = 30 ms; device 1: 10 ms
    busy, window = tracefile.busy_s(ev)
    assert window == pytest.approx(0.1)
    assert busy == pytest.approx((0.030 + 0.010) / 2)
    assert tracefile.busy_ns(ev, D0, 0, 100e6) == pytest.approx(30e6)


def test_merge_is_a_union():
    assert tracefile.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                                 [5, 9]]


def test_kernel_and_collective_time():
    ev = _trace()
    k = tracefile.matching(ev, D0, MODS, "overlay_execute")
    assert tracefile.total_ns(k) == pytest.approx(20e6)
    assert tracefile.collective_ns(ev, D0) == pytest.approx(5e6)
    assert tracefile.collective_ns(ev, D1) == pytest.approx(10e6)
    assert tracefile.device_planes(ev) == [D0, D1]


def test_breakdown_names_ops_and_gaps():
    b = tracefile.breakdown(_trace())
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "custom-call.1"
    assert dict(b["device_ops"])["fusion.2"] == pytest.approx(0.015)
    gaps = b["idle_gaps"]
    # gaps on device 0: [0,10] launch, [30,50] none, [55,95] jit
    assert [g[0] for g in gaps] == ["chipbench.jit", "host:other",
                                    "chipbench.launch"]
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.020, 0.010])


def test_leaves_and_op_names():
    loop = Event(D0, OPS, "%while.2 = (s32[]) while(...)", 0, 100)
    body = [Event(D0, OPS, "%fusion.1 = bf16[8] fusion(...)", 10, 20),
            Event(D0, OPS, "%fusion.1 = bf16[8] fusion(...)", 30, 40)]
    after = Event(D0, OPS, "copy.3", 100, 110)
    assert tracefile.leaves([after, loop] + body) == body + [after]
    assert tracefile.op_name(body[0].name) == "fusion.1"
    assert tracefile.op_name("copy.3") == "copy.3"
    b = tracefile.breakdown([Event("/host:CPU", "python", tracefile.WINDOW,
                                   0, 200), loop, after] + body)
    assert dict(b["device_ops"]) == pytest.approx({"fusion.1": 20e-9,
                                                   "copy.3": 10e-9})


def test_scope_is_optional_and_left_out_of_the_reductions():
    plain = Event(D0, OPS, "fusion.2", 0, 10)
    scoped = Event(D0, OPS, "fusion.7", 10, 30, "jit(step)/mlp/dot_general:")
    assert plain.scope == "" and scoped.scope.split("/")[1] == "mlp"
    ev = [Event("/host:CPU", "python", tracefile.WINDOW, 0, 40), plain,
          scoped]
    assert tracefile.busy_ns(ev, D0, 0, 40) == pytest.approx(30)
    assert tracefile.total_ns(e for e in ev if "/mlp/" in e.scope) == 20
    assert dict(tracefile.breakdown(ev)["device_ops"]) == pytest.approx(
        {"fusion.7": 20e-9, "fusion.2": 10e-9})


def test_op_scopes_read_the_metadata_stat(tmp_path):
    """A device operation's scope is its metadata's ``tf_op`` stat, held
    as a string or as a reference to a stat name; host planes and other
    stats are left out."""
    space = tracefile._xspace()()
    for pname in (D0, "/host:CPU"):
        plane = space.planes.add(name=pname.encode())
        for key, name in [(1, b"tf_op"), (2, b"hlo_op"),
                          (3, b"jit(step)/attn/dot_general:")]:
            plane.stat_metadata.add(key=key).value.name = name
        op = plane.event_metadata.add(key=7).value
        op.name, op.display_name = b"%fusion.7 = bf16[8] fusion(...)", \
            b"fusion.7"
        op.stats.add(metadata_id=2, str_value=b"fusion.7")
        op.stats.add(metadata_id=1, str_value=b"jit(step)/mlp/mul:")
        ref = plane.event_metadata.add(key=8).value
        ref.name = b"copy.3"
        ref.stats.add(metadata_id=1, ref_value=3)
        plane.event_metadata.add(key=9).value.name = b"convert.1"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert tracefile.op_scopes(str(path)) == {
        (D0, "%fusion.7 = bf16[8] fusion(...)"): "jit(step)/mlp/mul:",
        (D0, "fusion.7"): "jit(step)/mlp/mul:",
        (D0, "copy.3"): "jit(step)/attn/dot_general:"}


def test_window_must_be_marked_once():
    with pytest.raises(RuntimeError):
        tracefile.window([e for e in _trace() if e.name != tracefile.WINDOW])


def test_capture_reads_a_real_profile():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    cap = tracefile.Capture()
    cap.start()
    with tracefile.annotate("launch", True):
        f(x).block_until_ready()
    ev = cap.stop()
    lo, hi = tracefile.window(ev)
    marks = [e for e in ev if e.name == "chipbench.launch"]
    assert len(marks) == 1 and lo <= marks[0].start_ns < hi
