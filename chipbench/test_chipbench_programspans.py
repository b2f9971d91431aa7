"""The reductions of the program's own host spans in a profiler trace, and
the per-layer metrics that read them, on hand-built traces."""

from pathlib import Path

import pytest

from chipbench import harness, programspans, tracefile
from chipbench.tracefile import Event

H, D0 = "/host:CPU", "/device:TPU:0"
MS = 1e6


def _ev(line, name, start_ms, end_ms, plane=H):
    return Event(plane, line, name, start_ms * MS, end_ms * MS)


def _metric(name):
    return harness.load_module(Path(harness.ROOT) / "chipbench" /
                               "metrics" / f"{name}.py")


def _launch(line, t0):
    """One launch on ``line`` from ``t0`` ms: enqueue [t0, t0+10] holding
    stage 1, h2d 1, wait 4, d2h 1 ms (3 ms of bookkeeping), then a 2 ms
    read."""
    return [_ev(line, "session:enqueue", t0, t0 + 10),
            _ev(line, "launch:stage", t0 + 1, t0 + 2),
            _ev(line, "launch:h2d", t0 + 2, t0 + 3),
            _ev(line, "launch:wait", t0 + 3, t0 + 7),
            _ev(line, "launch:d2h", t0 + 7, t0 + 8),
            _ev(line, "buffer:read", t0 + 10, t0 + 12)]


def _trace(*extra):
    return [_ev("python", tracefile.WINDOW, 0, 100), *extra]


def test_totals_are_clipped_to_the_window():
    ev = _trace(_ev("python", "launch:wait", -5, 5),
                _ev("python", "launch:wait", 20, 30),
                _ev("python", "launch:wait", 95, 110),
                _ev("python", "launch:wait", 120, 130),
                _ev("TPU", "launch:wait", 40, 50, plane=D0))
    assert programspans.total_ns(ev, ["launch:wait"]) == \
        pytest.approx(20 * MS)
    assert programspans.count(ev, "launch:wait") == 3


def test_self_time_subtracts_named_children_per_line():
    ev = _trace(*_launch("a", 10), _ev("a", "launch:stage", 50, 51))
    # 10 ms of enqueue, less 7 ms of children inside it; the stray stage
    # at 50 ms lies in no enqueue and is not subtracted
    assert programspans.self_ns(ev, "session:enqueue",
                                programspans.LAUNCH) == pytest.approx(3 * MS)


def test_self_time_of_two_overlapping_threads():
    # two threads, 4 ms apart, so each enqueue overlaps the other's
    # children in time
    two_lines = _trace(*_launch("t1", 10), *_launch("t2", 14))
    assert programspans.self_ns(two_lines, "session:enqueue",
                                programspans.LAUNCH) == pytest.approx(6 * MS)
    # the profiler may give both threads one line name: a child is still
    # subtracted once, and the other thread's read is not a child
    one_line = _trace(*_launch("python", 10), *_launch("python", 14))
    assert programspans.self_ns(one_line, "session:enqueue",
                                programspans.LAUNCH) == pytest.approx(6 * MS)
    assert programspans.total_ns(one_line, ["launch:wait"]) == \
        pytest.approx(8 * MS)


def test_self_time_clipped_at_the_window_edge():
    ev = _trace(*_launch("a", 95))
    # enqueue [95, 100] in the window, stage [96, 97], h2d [97, 98],
    # wait [98, 100]: 1 ms of self time left inside
    assert programspans.self_ns(ev, "session:enqueue",
                                programspans.LAUNCH) == pytest.approx(1 * MS)


def test_idle_while_in_launch_host_spans():
    ev = _trace(*_launch("t1", 10), *_launch("t2", 14),
                _ev(tracefile.OPS_LINE, "custom-call.1", 11.5, 16.5,
                    plane=D0))
    host = ("launch:stage", "launch:h2d", "launch:d2h", "buffer:read")
    # host copies and transfers: t1 [11, 13] + [17, 18] + [20, 22],
    # t2 [15, 17] + [21, 22] + [24, 26]; union [11, 13] + [15, 18] +
    # [20, 22] + [24, 26] = 9 ms, of which [11.5, 13] + [15, 16.5] = 3 ms
    # with the device busy
    assert programspans.idle_while_ns(ev, host) == pytest.approx(6 * MS)
    assert _metric("device_idle_launch_host.overlay").read(
        dict(events=ev)) == pytest.approx(6.0)


def test_launch_metrics_per_million_items():
    ev = _trace(*_launch("t1", 10), *_launch("t2", 14),
                _ev(tracefile.OPS_LINE, "custom-call.1", 11.5, 16.5,
                    plane=D0))
    r = dict(events=ev, items=2_000_000)
    # per launch: copies 1 + 2, transfers 1 + 1, wait 4, self 3 ms
    want = dict(launch_host_copy_ms_per_mitem=3.0,
                launch_transfer_ms_per_mitem=2.0,
                launch_wait_ms_per_mitem=4.0,
                enqueue_self_ms_per_mitem=3.0)
    for name, v in want.items():
        assert _metric(name).read(r) == pytest.approx(v), name


def test_jit_metrics_count_builds():
    ev = _trace(_ev("w0", "jit:build", 10, 20),
                _ev("w0", "jit:template_build", 11, 17),
                _ev("w1", "jit:build", 12, 16),
                _ev("w1", "jit:stamp", 13, 14))
    r = dict(events=ev)
    assert _metric("jit_build_ms").read(r) == pytest.approx(7.0)
    # one template built for two builds: 6 ms over 2
    assert _metric("jit_template_ms").read(r) == pytest.approx(3.0)
    # builds with no template built (every template cached) cost none
    hits = _trace(_ev("w0", "jit:build", 10, 12))
    assert _metric("jit_template_ms").read(dict(events=hits)) == 0.0


NEW_METRICS = ["launch_host_copy_ms_per_mitem", "launch_transfer_ms_per_mitem",
               "launch_wait_ms_per_mitem", "enqueue_self_ms_per_mitem",
               "device_idle_launch_host.overlay", "jit_build_ms",
               "jit_template_ms"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_null_where_the_program_wrote_no_spans(name):
    # a trace of a program without these spans: the harness's own
    # annotations and the device's operations only
    ev = _trace(_ev("python", "chipbench.launch", 10, 20),
                _ev("python", "chipbench.jit", 30, 40),
                _ev(tracefile.OPS_LINE, "custom-call.1", 12, 14, plane=D0))
    assert _metric(name).read(dict(events=ev, items=1 << 20)) is None
    assert _metric(name).read(dict(events=None, items=1 << 20)) is None
    assert _metric(name).read(dict(items=1 << 20)) is None


def test_traced_tiny_cells_read_the_program_spans(monkeypatch):
    """The program's spans reach a real (CPU) profile through the harness:
    a tiny bulk cell on the executor (interpreted) and a tiny churn cell."""
    from chipbench.test_chipbench_harness import run_tiny
    import repro.core.runtime
    import repro.kernels.overlay_exec.kernel  # noqa: F401 — binds interpret
    monkeypatch.setattr(repro.core.runtime, "interpret_mode",
                        lambda interpret=None: False)
    m = {k: v["value"] for k, v in
         run_tiny("suite_bulk", trace=True)["metrics"].items()}
    parts = ["launch_host_copy_ms_per_mitem", "launch_transfer_ms_per_mitem",
             "launch_wait_ms_per_mitem", "enqueue_self_ms_per_mitem"]
    assert all(m[k] > 0 for k in parts), m
    # the spans lie inside the harness's span around the same launches
    assert 0.5 * m["launch_ms_per_mitem"] <= sum(m[k] for k in parts) \
        <= 1.01 * m["launch_ms_per_mitem"]
    m = {k: v["value"] for k, v in
         run_tiny("suite_jit_churn", trace=True)["metrics"].items()}
    assert 0 < m["jit_build_ms"] <= 1.01 * m["jit_ms"]
    assert m["jit_template_ms"] is not None
