"""Overlay cells: tenants in closed loops submit the paper's OpenCL kernels
to one ``Session`` and read every result back.

The traffic file says how many tenants there are, which kernel a request
picks (Zipf over a fixed order, or uniform, dealt from shuffled decks),
how many work-items it covers (log-uniform over the powers of two in a
range, dealt likewise), and whether a request
runs a kernel the host compiled once in set-up (``"kernel": "cached"``) or
submits a new kernel that must be compiled first (``"kernel": "new"``, a
template with fresh constants).  Inputs are float32 slices of one pool,
uniform in [-1, 1], drawn from the seed in set-up.

Set-up compiles the cached kernels and runs every executor shape the
traffic can reach once, so that nothing compiles in the window.  A request
is timed from its submission (the compile, for a new kernel) to the output
``Buffer`` being read.  After the window, a share of the requests drawn
from the seed is compared with the kernels' NumPy oracles in float64;
with ``run.control`` the oracle in bfloat16 is compared in the program's
place.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, List

import numpy as np

from chipbench import common, counts, suite, tracefile


def build_session(c: dict):
    from repro.core.overlay import OverlaySpec
    from repro.core.runtime import Device
    from repro.core.session import Session
    spec = OverlaySpec(**c["overlay"])
    return Session([Device(f"ovl{i}", spec) for i in range(c["devices"])],
                   persist_dir=c["persist_dir"])


def compile_options(c: dict):
    from repro.core.options import CompileOptions
    return CompileOptions(max_replicas=c["max_replicas"])


def launch(sess, handle, inputs, tenant: str) -> np.ndarray:
    """The executor path: enqueue on the tenant's queue, read the output."""
    return sess.enqueue(handle, *inputs, tenant=tenant).wait()[0].read()


def deck_counts(p, deck: int) -> List[int]:
    """Largest-remainder counts of each kernel in a deck of ``deck``
    requests drawn with probabilities ``p``."""
    raw = np.asarray(p) * deck
    n = np.floor(raw).astype(int)
    for i in np.argsort(n - raw)[:deck - n.sum()]:
        n[i] += 1
    return n.tolist()


class Traffic:
    """One tenant's requests, from its stream of the seed.  Kernels come in
    decks of ``deck`` requests holding each kernel in its share (Zipf over
    the listed order, or uniform), and sizes in decks holding each size
    once; every deck is shuffled by the seed.  So every seed sends the
    same mix of kernels and sizes over any stretch of a few decks, in
    another order."""

    def __init__(self, t: dict, kernels: List[str], seed: int, tenant: int):
        self.t = t
        self.rng = common.rng(seed, 2, tenant)
        if t.get("zipf_s") is not None:
            w = 1.0 / np.arange(1, len(kernels) + 1) ** t["zipf_s"]
        else:
            w = np.ones(len(kernels))
        self.kernel_deck = [k for k, n in zip(kernels, deck_counts(
            w / w.sum(), t["deck"])) for _ in range(n)]
        lo, hi = t["items_log2"]
        self.size_deck = [1 << k for k in range(lo, hi + 1)]
        self.kernels: List[str] = []
        self.sizes: List[int] = []

    def _draw(self, pending: list, deck: list):
        if not pending:
            pending.extend(self.rng.permutation(len(deck)).tolist())
        return deck[pending.pop()]

    def next(self, pool_len: int) -> dict:
        r = self.rng
        name = self._draw(self.kernels, self.kernel_deck)
        items = self._draw(self.sizes, self.size_deck)
        consts = suite.draw_constants(name, r) if self.t["kernel"] == "new" \
            else suite.TEMPLATES[name].defaults
        return dict(kernel=name, items=items, consts=consts,
                    offset=int(r.integers(0, pool_len - items + 1)),
                    check=bool(r.random() < self.t["check_share"]))


def _inputs(pool, req):
    n_in = suite.TEMPLATES[req["kernel"]].n_in
    s = slice(req["offset"], req["offset"] + req["items"])
    return [pool[j, s] for j in range(n_in)]


class Cell:
    """One overlay cell: set-up, the window, and the check."""

    def __init__(self, run):
        self.run = run
        self.c, self.t = run.config, run.traffic
        self.kernels = self.t["kernels"]
        lo, hi = self.t["items_log2"]
        self.pool_len = (1 << hi) + (1 << max(lo, hi - 4))
        n_in = max(suite.TEMPLATES[k].n_in for k in self.kernels)
        g = common.rng(run.seed, 0)
        self.pool = g.random((n_in, self.pool_len), np.float32) * 2 - 1
        self.sess = build_session(self.c)
        self.opts = compile_options(self.c)
        self.handles: Dict[str, object] = {}

    # -------------------------------------------------------------- set-up
    def _warm(self, handle, name: str, tenant: str) -> None:
        lo, hi = self.t["items_log2"]
        for k in range(lo, hi + 1):
            req = dict(kernel=name, offset=0, items=1 << k)
            launch(self.sess, handle, _inputs(self.pool, req), tenant)

    def warm_up(self) -> None:
        sess, g = self.sess, common.rng(self.run.seed, 1)
        if self.t["kernel"] == "cached":
            # the host builds each kernel once, as an OpenCL host calls
            # clBuildProgram once; its tenants' threads share the programs
            futs = {n: sess.compile(suite.source(n, suite.TEMPLATES[n]
                                                 .defaults), self.opts,
                                    tenant="host")
                    for n in self.kernels}
            for f in futs.values():
                f.result()
            self.run.mark("overlay JIT")
            for n in self.kernels:
                self._warm(futs[n], n, "warm")
            self.handles = futs
        else:
            # a new kernel runs at its own executor signature or at that of
            # a resident program it may share (ops.shared_signature): warm
            # each template alone and beside each other one
            for a in self.kernels:
                pa = sess.compile(suite.source(a, suite.draw_constants(a, g)),
                                  self.opts, tenant="warm").result()
                self._warm(pa, a, "warm")
                for b in self.kernels:
                    if b != a:
                        pb = sess.compile(suite.source(
                            b, suite.draw_constants(b, g)), self.opts,
                            tenant="warm").result()
                        self._warm(pb, b, "warm")
                        pb.release()
                pa.release()
        self._drain("warm")
        self.run.mark("warm-up launches")

    def _drain(self, tenant: str) -> None:
        for d in self.sess.devices:
            self.sess.queue_for(tenant, d.name).drain()

    # -------------------------------------------------------------- window
    def request(self, req: dict, tenant: str, traced: bool) -> None:
        sess = self.sess
        xs = _inputs(self.pool, req)
        t0 = time.perf_counter()
        if self.t["kernel"] == "new":
            with tracefile.annotate("jit", traced):
                fut = sess.compile(suite.source(req["kernel"], req["consts"]),
                                   self.opts, tenant=tenant)
                prog = fut.result()
            t1 = time.perf_counter()
            self.run.spans.add("jit", t1 - t0)
            handle = fut
        else:
            prog, handle, t1 = None, self.handles[req["kernel"]], t0
        try:
            with tracefile.annotate("launch", traced):
                out = launch(sess, handle, xs, tenant)
            t2 = time.perf_counter()
            self.run.spans.add("launch", t2 - t1, items=req["items"])
        finally:
            if prog is not None:
                prog.release()
        req["t_done"] = t2
        req["latency"] = t2 - t0
        if req["check"]:
            req["out"] = out

    def tenant_loop(self, i: int, win, log: list, traced: bool) -> None:
        tenant = f"tenant-{i}"
        traffic = Traffic(self.t, self.kernels, self.run.seed, i)
        while win.open():
            req = traffic.next(self.pool_len)
            log.append(req)
            try:
                self.request(req, tenant, traced)
            except Exception as e:    # a failed request counts as missing
                req["error"] = repr(e)
            self._drain(tenant)

    def window(self) -> dict:
        run, win = self.run, self.run.window
        logs: List[list] = [[] for _ in range(self.t["tenants"])]
        traced = run.trace
        cap = tracefile.Capture() if traced else None
        if cap:
            cap.start()
        x0 = run.clock.executables
        run.mark_window_start()
        threads = [threading.Thread(target=self.tenant_loop,
                                    args=(i, win, logs[i], traced))
                   for i in range(self.t["tenants"])]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        xla = run.clock.executables - x0
        events = cap.stop() if cap else None
        reqs = [r for log in logs for r in log]
        return dict(reqs=reqs, xla_compiles=xla, events=events)

    # --------------------------------------------------------------- check
    def check(self, reqs: List[dict]) -> float:
        """Widest relative error of the sampled outputs, float64 oracle."""
        return max([oracle_err(r, self.pool) for r in reqs if "out" in r],
                   default=0.0)


CHUNK = 1 << 16


def oracle_err(req: dict, pool) -> float:
    """``common.rel_err`` of a request's output against the float64
    oracle, the same number taken over chunks of work-items that stay in
    the host's cache, which is a few times sooner at 2^24 items."""
    got = np.asarray(req["out"])
    if got.shape != (req["items"],) or not np.all(np.isfinite(got)):
        return math.inf
    xs = _inputs(pool, req)
    diff = scale = 0.0
    for i in range(0, req["items"], CHUNK):
        want = suite.oracle(req["kernel"], req["consts"],
                            [x[i:i + CHUNK] for x in xs])
        diff = max(diff, float(np.max(np.abs(got[i:i + CHUNK] - want))))
        scale = max(scale, float(np.max(np.abs(want))))
    return diff / max(scale, 1e-30)


def control_err(reqs: List[dict], pool, dtype, limit: int = 8,
                items: int = 1 << 20) -> float:
    """The oracle computed in ``dtype``, in the program's place, on the
    first ``limit`` sampled requests (their first ``items`` work-items):
    its widest relative error against float64."""
    worst = 0.0
    for r in [r for r in reqs if "out" in r][:limit]:
        xs = [x[:items] for x in _inputs(pool, r)]
        got = suite.oracle(r["kernel"], r["consts"], xs, dtype)
        worst = max(worst, common.rel_err(
            got, suite.oracle(r["kernel"], r["consts"], xs)))
    return worst


def run(run) -> dict:
    cell = Cell(run)
    run.mark("input pool and session")
    cell.warm_up()
    w = cell.window()
    run.mark("window and its last requests")
    reqs, win = w["reqs"], run.window
    done = [r for r in reqs if "error" not in r]
    in_window = [r for r in done if r["t_done"] <= win.t_end]
    items = sum(r["items"] for r in in_window)
    latencies = [r.get("latency", float("inf")) for r in reqs]
    peak = common.memory_peak_bytes(run.devices)
    cell.sess.close()
    if run.control:
        import ml_dtypes
        worst = control_err(reqs, cell.pool, ml_dtypes.bfloat16)
    else:
        worst = cell.check(reqs)
    run.mark("check")
    limit = run.config["check"]["rel_err_max"]
    failed = len(reqs) - len(done)
    n_checked = sum("out" in r for r in reqs)
    return dict(
        correct=failed == 0 and n_checked > 0 and worst <= limit,
        attempted=len(reqs), failed=failed, memory_peak_bytes=peak,
        end_to_end=dict(items_per_s=items / win.seconds,
                        request_p95_ms=1e3 * common.nearest_rank(latencies,
                                                                 95)),
        checks=dict(worst_rel_err=[worst, limit],
                    failed_requests=[failed, 0]),
        readings=dict(
            events=w["events"], spans=run.spans,
            xla_compiles=w["xla_compiles"],
            items=sum(r["items"] for r in done),
            overlay_bytes=sum(counts.overlay_bytes(
                suite.TEMPLATES[r["kernel"]].n_in,
                suite.TEMPLATES[r["kernel"]].n_out, r["items"])
                for r in done)),
        requests=reqs, pool=cell.pool)


def control_readings(cell, seeds, seconds, require_chip=True):
    """Per seed, a window of ``seconds`` at the cell's own load: the
    sampled requests' widest relative error (``program``) and that of
    the oracle in bfloat16 on them (``control``)."""
    import ml_dtypes
    from chipbench import harness
    for seed in seeds:
        _, out = harness.run_traffic(cell, seed, seconds, False,
                                     t_start=time.perf_counter(),
                                     require_chip=require_chip)
        yield dict(seed=seed, program=out["checks"]["worst_rel_err"][0],
                   control=control_err(out["requests"], out["pool"],
                                       ml_dtypes.bfloat16),
                   checked=sum("out" in r for r in out["requests"]))
