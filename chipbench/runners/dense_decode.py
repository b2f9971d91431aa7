"""Decode cells: lockstep waves of greedy decoding through the program's
serve step on a (data=1, model=chips) mesh.

A wave is ``batch`` sequences with prompts of ``prompt`` tokens drawn from
the seed, fed through the serve step one token at a time (as the program
prefills today), then ``gen`` greedy tokens, each read back to the host
as a streaming server must.  The window starts at the start of a wave.

The timed path is the program's: ``make_serve_step`` over
``build_model(cfg)``, with the cache from ``model.init_cache``, both laid
out by ``param_specs()``/``cache_specs()`` on ``make_host_mesh`` and
compiled ahead of the window.  The weights are the benchmark's own, drawn
on the device from the seed in one jitted call, in the program's tree.
After the window a sample of finished sequences drawn from the seed is run
through the plain reference, and every served token's reference logit is
compared with the reference's best at its position; with
``run.control`` the same gap is read for the token that the float8
control puts first, in the program's place.
"""

from __future__ import annotations

import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import common, counts, reference, tracefile

ARCH_KEYS = ("arch_id", "family", "n_layers", "d_model", "n_heads",
             "n_kv_heads", "d_ff", "vocab", "head_dim", "activation",
             "rope_theta", "norm_eps")


def arch_config(c: dict):
    from repro.models.common import ArchConfig
    if c["dtype"] != "bfloat16":
        raise ValueError(f"unsupported dtype {c['dtype']!r}")
    return ArchConfig(**{k: c[k] for k in ARCH_KEYS}, dtype=jnp.bfloat16)


def shape_config(c: dict) -> dict:
    """The configuration with the padded vocabulary the weights have."""
    return dict(c, vocab_padded=(c["vocab"] + 255) // 256 * 256)


def _is_norm(path) -> bool:
    k = str(getattr(path[-1], "key", path[-1]))
    return k.startswith("ln") or k.endswith("norm")


def draw_params(model, key, shardings):
    """Weights from ``key`` in the tree and dtypes the program serves
    with: matrices normal with scale fan_in^-0.5 (the second-to-last axis),
    norm weights 1 + 0.1 * normal.  One jitted call, each device drawing
    its own shards."""
    shapes = jax.eval_shape(model.init, key)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, s) in zip(keys, paths):
            z = jax.random.normal(k, s.shape, "float32")
            if _is_norm(path):
                out.append((1.0 + 0.1 * z).astype(s.dtype))
            else:
                out.append((z * s.shape[-2] ** -0.5).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init, out_shardings=shardings)(key)


def build_step(model, params, cache, c_sh, rep):
    """The program's serve step, compiled ahead for these shapes."""
    from repro.train.step import make_serve_step
    tok = jax.device_put(np.zeros((cache["k"].shape[1], 1), np.int32), rep)
    return jax.jit(make_serve_step(model), donate_argnums=(1,),
                   out_shardings=(rep, c_sh)).lower(
        params, cache, tok, jax.device_put(np.int32(0), rep)).compile()


def build_pick(logits, rep):
    """Greedy sampling on the device: (B, V) logits -> (B, 1) int32."""
    return jax.jit(lambda lg: jnp.argmax(lg, -1).astype(jnp.int32)[:, None],
                   out_shardings=rep).lower(logits).compile()


class Cell:
    def __init__(self, run):
        from repro.launch.mesh import make_host_mesh
        from repro.models.registry import build_model

        self.run = run
        self.c = shape_config(run.config)
        t = run.traffic
        self.B, self.P, self.G = t["batch"], t["prompt"], t["gen"]
        self.S = t["cache_len"]
        if self.P + self.G > self.S + 1:
            raise ValueError("a wave does not fit the cache")
        self.mesh = make_host_mesh(run.chips, devices=run.devices)
        if dict(self.mesh.shape) != {"data": 1, "model": run.chips}:
            raise RuntimeError(f"unexpected mesh {dict(self.mesh.shape)}")
        self.model = build_model(arch_config(self.c))

        def named(tree):
            return jax.tree.map(lambda s: NamedSharding(self.mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))

        self.p_sh = named(self.model.param_specs())
        self.c_sh = named(self.model.cache_specs(model_axis=run.chips))
        self.rep = NamedSharding(self.mesh, P())
        self.new_cache = jax.jit(
            functools.partial(self.model.init_cache, self.B, self.S),
            out_shardings=self.c_sh).lower().compile()
        run.mark("model and cache program")

    def set_up(self, seed: int) -> None:
        self.params = draw_params(self.model, common.jax_key(seed),
                                  self.p_sh)
        jax.block_until_ready(self.params)
        self.run.mark("weights")
        cache = self.new_cache()
        self.step = build_step(self.model, self.params, cache, self.c_sh,
                               self.rep)
        self.run.mark("serve step program")
        tok = jax.device_put(np.zeros((self.B, 1), np.int32), self.rep)
        logits, cache = self.step(self.params, cache, tok,
                                  jax.device_put(np.int32(0), self.rep))
        self.pick = build_pick(logits, self.rep)
        np.asarray(self.pick(logits))
        del cache
        self.run.mark("warm-up step")

    def prompts(self, seed: int, wave: int) -> np.ndarray:
        return common.rng(seed, 1, wave).integers(
            0, self.c["vocab"], (self.B, self.P), dtype=np.int32)

    def wave(self, prompts, win=None, trace=None) -> dict:
        """Serve one wave; stop early where ``win`` closes.  Returns the
        served tokens, the host time each step's tokens arrived, and
        whether the wave finished."""
        put = functools.partial(jax.device_put, device=self.rep)
        cache = self.new_cache()
        served = np.zeros((self.B, self.G), np.int32)
        times: List[float] = []
        traced = trace is not None
        tok = None
        for p in range(self.P + self.G - 1):
            if win is not None and not win.open():
                jax.block_until_ready(cache)
                return dict(served=served, times=times, done=False)
            if trace is not None:
                trace.at_step(p, cache)
            if p < self.P:
                tok = put(prompts[:, p:p + 1])
            with tracefile.annotate("step", traced):
                logits, cache = self.step(self.params, cache, tok,
                                          put(np.int32(p)))
            if p >= self.P - 1:
                with tracefile.annotate("readback", traced):
                    tok = self.pick(logits)
                    host = np.asarray(tok)
                times.append(time.perf_counter())
                served[:, p - self.P + 1] = host[:, 0]
        return dict(served=served, times=times, done=True)

    def sample(self, waves: List[dict], seed: int, n: int):
        """``n`` finished sequences drawn from the seed: tokens (n, P+G)."""
        rows = [(w, b) for w in range(len(waves)) for b in range(self.B)]
        pick = common.rng(seed, 3).choice(len(rows), n, replace=False)
        return np.stack([np.concatenate([waves[rows[i][0]]["prompts"]
                                         [rows[i][1]],
                                         waves[rows[i][0]]["served"]
                                         [rows[i][1]]]) for i in pick])

    def gaps(self, tokens: np.ndarray, lowp: bool = False) -> dict:
        """Served-token gaps against the float32 reference, and with
        ``lowp`` the gaps of the float8 control's choices."""
        toks = jax.device_put(tokens, self.rep)
        ref = reference.logits(self.c, self.params, toks)
        out = dict(served=float(np.max(np.asarray(
            reference.served_gaps(ref, toks, self.P - 1)))))
        if lowp:
            low = reference.logits(self.c, self.params, toks, lowp=True)
            out["control"] = float(np.max(np.asarray(
                reference.lowp_gaps(ref, low, self.P - 1))))
        return out


class StepTrace:
    """Traces the steps from ``start_s`` to ``start_s + seconds`` into the
    window, starting and stopping at step boundaries with the device
    drained, and records each traced step's cache occupancy."""

    def __init__(self, win, start_s: float, seconds: float):
        self.win = win
        self.t_on = start_s
        self.t_off = start_s + seconds
        self.cap = None
        self.kv_lens: List[int] = []
        self.events = None

    def at_step(self, p: int, pending) -> None:
        el = time.perf_counter() - self.win.t0
        if self.cap is None and self.events is None and el >= self.t_on:
            jax.block_until_ready(pending)
            self.cap = tracefile.Capture()
            self.cap.start()
        elif self.cap is not None and el >= self.t_off:
            jax.block_until_ready(pending)
            self.finish()
        if self.cap is not None:
            self.kv_lens.append(p + 1)

    def finish(self) -> None:
        """Stop the trace (the device is drained by then)."""
        if self.cap is not None:
            self.events = self.cap.stop()
            self.cap = None


def run(run) -> dict:
    cell = Cell(run)
    cell.set_up(run.seed)
    t = run.traffic
    win = run.window
    trace = None
    if run.trace:
        trace = StepTrace(win, t["trace_start_s"], t["trace_seconds"])
    waves, gaps, tokens = [], [], 0
    run.mark_window_start()
    w = 0
    while win.open():
        prompts = cell.prompts(run.seed, w)
        out = cell.wave(prompts, win, trace)
        inside = [x for x in out["times"] if x <= win.t_end]
        tokens += cell.B * len(inside)
        gaps += [b - a for a, b in zip(inside, inside[1:])]
        if out["done"]:
            waves.append(dict(prompts=prompts, served=out["served"]))
        w += 1
    if trace is not None:
        trace.finish()
    run.mark("window and its last step")
    peak = common.memory_peak_bytes(run.devices)
    if not waves:
        # the window ended inside the first wave: finish one, untimed, so
        # that there are served sequences to compare
        prompts = cell.prompts(run.seed, w)
        waves.append(dict(prompts=prompts,
                          served=cell.wave(prompts)["served"]))
    del cell.step
    sample = cell.sample(waves, run.seed, t["check_sequences"])
    g = cell.gaps(sample, lowp=run.control)
    gap = g["control"] if run.control else g["served"]
    run.mark("check")
    limit = run.config["check"]["served_logit_gap_max"]
    return dict(
        correct=bool(gap <= limit), attempted=cell.B * w, failed=0,
        memory_peak_bytes=peak,
        end_to_end=dict(
            tokens_per_s=tokens / win.seconds,
            token_gap_p95_ms=1e3 * common.nearest_rank(gaps, 95)
            if gaps else None),
        checks=dict(served_logit_gap=[gap, limit]),
        readings=dict(
            events=trace.events if trace else None,
            kv_lens=trace.kv_lens if trace else [],
            step_counts=[counts.dense_decode_step(cell.c, cell.B, n,
                                                  run.chips)
                         for n in (trace.kv_lens if trace else [])]))


def control_readings(cell, seeds, seconds, require_chip=True):
    """Per seed, one whole wave through the timed path: the sampled
    sequences' widest served-token gap (``program``) and that of the
    float8 control's choices (``control``).  There is no window, so
    ``seconds`` is not used."""
    from chipbench import harness
    devices = harness.find_devices(cell["chips"], require_chip)
    run = harness.Run(cell, seeds[0], 0.0, False, devices,
                      time.perf_counter())
    dc = Cell(run)
    for seed in seeds:
        dc.set_up(seed)
        prompts = dc.prompts(seed, 0)
        waves = [dict(prompts=prompts, served=dc.wave(prompts)["served"])]
        del dc.step
        g = dc.gaps(dc.sample(waves, seed, run.traffic["check_sequences"]),
                    lowp=True)
        yield dict(seed=seed, program=g["served"], control=g["control"])
        del dc.params
