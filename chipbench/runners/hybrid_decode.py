"""Hybrid decode cells (zamba2): the lockstep waves of ``dense_decode``
through the program's serve step, on a model whose cache holds two kinds
of state side by side: each Mamba layer's conv and SSM state and each
hybrid site's keys and values.

A wave is ``batch`` sequences with prompts drawn from the seed, fed one
token per step (as the program prefills today), then ``gen`` greedy
tokens, each read back to the host.  The timed path is the program's:
``make_serve_step`` over ``build_model(cfg)``, with the cache from
``model.init_cache``, both laid out by ``param_specs()``/``cache_specs()``
and compiled ahead of the window.  The configuration file holds the
published keys (``hidden_size``, ``mamba_ngroups``, ``hybrid_layer_ids``,
...); :func:`arch_config` maps them onto the program's ``ArchConfig`` and
refuses what the program does not compute.  The weights are the
benchmark's own, drawn on the device from the seed.  After the window a
sample of finished sequences drawn from the seed goes through
``reference_zamba2``, and every served token's reference logit is
compared with the reference's best at its position; with ``run.control``
the same gap is read for the token that the float8 control puts first.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import (common, counts_hybrid, reference, reference_zamba2,
                       scopes)
from chipbench.runners import dense_decode
from chipbench.runners.dense_decode import StepTrace, build_pick

# the published switches whose other value the program does not compute
FIXED = dict(hidden_act="gelu", use_mem_rope=True, use_long_context=False,
             use_shared_mlp_adapter=True, use_shared_attention_adapter=False,
             add_bias_linear=False, time_step_limit=None)


def arch_config(c: dict):
    """The program's ``ArchConfig`` of a configuration file."""
    from repro.models.common import ArchConfig
    if c["dtype"] != "bfloat16":
        raise ValueError(f"unsupported dtype {c['dtype']!r}")
    for k, want in FIXED.items():
        if c[k] != want:
            raise ValueError(f"{k} = {c[k]!r}: only {want!r} is computed")
    d, ids = c["hidden_size"], list(c["hybrid_layer_ids"])
    if c["layers_block_type"] != ["hybrid" if i in ids else "mamba"
                                  for i in range(c["num_hidden_layers"])]:
        raise ValueError("layers_block_type disagrees with hybrid_layer_ids")
    if c["attention_hidden_size"] != 2 * d or \
            c["num_attention_heads"] * c["attention_head_dim"] != 2 * d:
        raise ValueError("attention must span concat(x, embeddings)")
    if c["n_mamba_heads"] * c["mamba_headdim"] != c["mamba_expand"] * d:
        raise ValueError("n_mamba_heads * mamba_headdim != d_inner")
    if c["ffn_hidden_size"] != c["intermediate_size"]:
        raise ValueError("ffn_hidden_size != intermediate_size")
    return ArchConfig(
        arch_id=c["name"], family="hybrid",
        n_layers=c["num_hidden_layers"], d_model=d,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c["attention_head_dim"],
        activation=c["hidden_act"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], ssm_state=c["mamba_d_state"],
        ssm_head_dim=c["mamba_headdim"], ssm_expand=c["mamba_expand"],
        ssm_chunk=c["chunk_size"], conv_width=c["mamba_d_conv"],
        ssm_groups=c["mamba_ngroups"], conv_bias=c["use_conv_bias"],
        hybrid_layer_ids=tuple(ids), n_shared_blocks=c["num_mem_blocks"],
        adapter_rank=c["adapter_rank"],
        attn_scale=(c["attention_head_dim"] / 2) ** -0.5,
        dtype=jnp.bfloat16)


def draw_params(model, c: dict, key, shardings):
    """Weights from ``key`` in the tree and dtypes the program serves
    with.  As for yi-6b: matrices normal with scale fan_in^-0.5 (the
    second-to-last axis), norm weights 1 + 0.1 normal; the tied embedding
    at d^-0.5, its fan-in as the head.  The SSM's own vectors as
    ``Zamba2PreTrainedModel._init_weights`` draws them: dt_bias the
    inverse softplus of a dt log-uniform in [time_step_min,
    time_step_max] (floored at time_step_floor), A_log = log(1..H), D = 1;
    the conv bias normal with the conv weight's scale, K^-0.5."""
    shapes = jax.eval_shape(model.init, key)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])

    def one(k, path, s):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "A_log":
            return jnp.log(jnp.arange(1, s.shape[0] + 1, dtype=s.dtype))
        if name == "D":
            return jnp.ones(s.shape, s.dtype)
        if name == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, s.shape) *
                                     (hi - lo) + lo), c["time_step_floor"])
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(s.dtype)
        z = jax.random.normal(k, s.shape, "float32")
        if name == "conv_b":
            return (z * c["mamba_d_conv"] ** -0.5).astype(s.dtype)
        if name == "embed":
            return (z * s.shape[-1] ** -0.5).astype(s.dtype)
        if name.startswith("ln") or name.endswith("norm"):
            return (1.0 + 0.1 * z).astype(s.dtype)
        return (z * s.shape[-2] ** -0.5).astype(s.dtype)

    def init(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [one(k, p, s) for k, (p, s) in zip(keys, paths)])

    return jax.jit(init, out_shardings=shardings)(key)


def build_step(model, params, cache, c_sh, rep):
    """The program's serve step, compiled ahead for these shapes, with
    the cache donated."""
    from repro.train.step import make_serve_step
    tok = jax.device_put(np.zeros((cache["conv"][0].shape[0], 1), np.int32),
                         rep)
    return jax.jit(make_serve_step(model), donate_argnums=(1,),
                   out_shardings=(rep, c_sh)).lower(
        params, cache, tok, jax.device_put(np.int32(0), rep)).compile()


class Cell(dense_decode.Cell):
    """A decode cell of the hybrid model: ``dense_decode``'s waves and
    sample, with this model's weights, serve step and reference."""

    def __init__(self, run):
        from repro.launch.mesh import make_host_mesh
        from repro.models.registry import build_model

        self.run = run
        self.c = run.config
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
        self.params = draw_params(self.model, self.c, common.jax_key(seed),
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
            0, self.c["vocab_size"], (self.B, self.P), dtype=np.int32)

    def gaps(self, tokens: np.ndarray, lowp: bool = False) -> dict:
        """Served-token gaps against the float32 reference, and with
        ``lowp`` the gaps of the float8 control's choices."""
        toks = jax.device_put(tokens, self.rep)
        ref = reference_zamba2.logits(self.c, self.params, toks)
        out = dict(served=float(np.max(np.asarray(
            reference.served_gaps(ref, toks, self.P - 1)))))
        if lowp:
            low = reference_zamba2.logits(self.c, self.params, toks,
                                          lowp=True)
            out["control"] = float(np.max(np.asarray(
                reference.lowp_gaps(ref, low, self.P - 1))))
        return out


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
    inherit = None
    if trace is not None:
        trace.finish()
        # the scopes of the serve step's operations that XLA added
        inherit = scopes.inherited(cell.step.as_text())
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
    kv_lens = trace.kv_lens if trace else []
    return dict(
        correct=bool(gap <= limit), attempted=cell.B * w, failed=0,
        memory_peak_bytes=peak,
        end_to_end=dict(
            tokens_per_s=tokens / win.seconds,
            token_gap_p95_ms=1e3 * common.nearest_rank(gaps, 95)
            if gaps else None),
        checks=dict(served_logit_gap=[gap, limit]),
        readings=dict(
            events=trace.events if trace else None, kv_lens=kv_lens,
            inherited_scopes=inherit,
            step_counts=[counts_hybrid.decode_step(cell.c, cell.B, n)
                         for n in kv_lens]))


def control_readings(cell, seeds, seconds, require_chip=True):
    """Per seed, one whole wave through the timed path: the sampled
    sequences' widest served-token gap (``program``) and that of the
    float8 control's choices (``control``).  There is no window, so
    ``seconds`` is not used."""
    from chipbench import harness
    devices = harness.find_devices(cell["chips"], require_chip)
    run = harness.Run(cell, seeds[0], 0.0, False, devices,
                      time.perf_counter())
    hc = Cell(run)
    for seed in seeds:
        hc.set_up(seed)
        prompts = hc.prompts(seed, 0)
        waves = [dict(prompts=prompts, served=hc.wave(prompts)["served"])]
        del hc.step
        g = hc.gaps(hc.sample(waves, seed, run.traffic["check_sequences"]),
                    lowp=True)
        yield dict(seed=seed, program=g["served"], control=g["control"])
        del hc.params
