"""Serving driver — continuous-batching inference over the overlay JIT.

The default path drives :mod:`repro.serve`: the requested arch's family
is mapped onto its overlay serving pipeline
(:data:`repro.serve.models.FAMILY_PIPELINE`), an
:class:`~repro.serve.server.InferenceServer` is stood up on a modelled
two-device Session, and a synthetic request trace is served with
continuous batching — printing admission/completion counters, batch
occupancy and per-SLO-class modelled latency from
``Session.stats()["serving"]``.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --requests 24 --gen 8

The older raw-JAX driver (token-recurrent prefill + argmax/categorical
decode through ``make_serve_step``, never touching the Session) is kept
behind ``--legacy`` with a DeprecationWarning, parity-tested in
``tests/test_launch_serve.py``.  Its loop, :func:`decode_loop`, is what
``chip_smoke.py`` drives at a zoo model's published width.
"""

from __future__ import annotations

import argparse
import functools
import time
import warnings
from typing import Any, Dict

import numpy as np

from repro.configs.registry import ALL_ARCHS, get_arch, reduced_config
from repro.launch.compile_cache import enable_compile_cache


def decode_loop(cfg, mesh, *, batch: int, prompt_len: int, gen: int,
                temperature: float = 0.0) -> Dict[str, Any]:
    """The raw-JAX serving loop: token-recurrent prefill and argmax (or
    categorical) decode through ``make_serve_step``, on ``mesh``.

    Parameters are drawn by one jitted init with the mesh's output
    shardings, so every device draws only its own shard and the f32
    normals fuse into the bf16 cast.  Init and step are compiled ahead of
    time, so the returned run times exclude compilation.  Returns the
    model, its parameters, the prompt, the logits after the last prompt
    token, the generated tokens and the seconds of each stage."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.registry import build_model
    from repro.train.step import make_serve_step

    def _named(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda x: isinstance(x, P))

    model = build_model(cfg)
    p_sh = _named(model.param_specs())
    c_sh = _named(model.cache_specs(model_axis=mesh.shape["model"]))
    rep = NamedSharding(mesh, P())
    max_len = prompt_len + gen

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    init = jax.jit(model.init, out_shardings=p_sh).lower(key).compile()
    new_cache = jax.jit(functools.partial(model.init_cache, batch, max_len),
                        out_shardings=c_sh).lower().compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, cache = init(key), new_cache()
    jax.block_until_ready((params, cache))
    t_init = time.perf_counter() - t0

    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab, (batch, prompt_len), np.int32)

    def put(x):
        return jax.device_put(x, rep)

    t0 = time.perf_counter()
    serve_step = jax.jit(make_serve_step(model), donate_argnums=(1,),
                         out_shardings=(rep, c_sh)).lower(
        params, cache, put(prompt[:, :1]), put(np.int32(0))).compile()
    t_compile += time.perf_counter() - t0

    # prefill: feed prompt tokens one step at a time through the decode
    # path (token-recurrent prefill; blockwise prefill is the prefill_*
    # shape)
    t0 = time.perf_counter()
    logits = None
    for i in range(prompt_len):
        logits, cache = serve_step(params, cache, put(prompt[:, i:i + 1]),
                                   put(np.int32(i)))
    prompt_logits = np.asarray(logits, np.float32)
    t_prefill = time.perf_counter() - t0

    def pick(logits, key):
        key, sub = jax.random.split(key)
        if temperature > 0:
            nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32)[:, None], key

    key_s = put(key)
    t0 = time.perf_counter()
    pick = jax.jit(pick, out_shardings=(rep, rep)).lower(logits,
                                                         key_s).compile()
    t_compile += time.perf_counter() - t0

    out_tokens = []
    t0 = time.perf_counter()
    for i in range(gen):
        nxt, key_s = pick(logits, key_s)
        out_tokens.append(nxt)
        logits, cache = serve_step(params, cache, nxt,
                                   put(np.int32(prompt_len + i)))
    jax.block_until_ready(logits)
    t_gen = time.perf_counter() - t0

    return dict(model=model, params=params, prompt=prompt,
                prompt_logits=prompt_logits,
                tokens=np.concatenate(out_tokens, axis=1),
                init_s=t_init, compile_s=t_compile, prefill_s=t_prefill,
                decode_s=t_gen)


def _legacy_main(args) -> None:
    """The raw-JAX serving loop this driver used before repro.serve."""
    warnings.warn(
        "--legacy drives the raw-JAX serve loop, which bypasses the "
        "Session runtime (no JIT cache, no queues, no SLO classes); it "
        "will be removed once the overlay path covers sampling. Use the "
        "default repro.serve path instead.",
        DeprecationWarning, stacklevel=2)
    from repro.launch.mesh import make_host_mesh

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    out = decode_loop(cfg, make_host_mesh(args.model_shards),
                      batch=args.batch, prompt_len=args.prompt_len,
                      gen=args.gen, temperature=args.temperature)
    t_prefill, t_gen = out["prefill_s"], out["decode_s"]
    print(f"arch={args.arch} batch={args.batch} "
          f"prefill {args.prompt_len} tok in {t_prefill:.2f}s | "
          f"decode {args.gen} tok in {t_gen:.2f}s "
          f"({args.batch * args.gen / t_gen:.1f} tok/s)")
    print("sample:", out["tokens"][0, :16].tolist())


def serve_overlay(arch: str, n_requests: int, gen: int, slo: str,
                  max_batch: int, devices: int = 2,
                  seed: int = 0) -> dict:
    """Serve a synthetic trace for ``arch`` through repro.serve; returns
    the ``stats()["serving"]`` blob (drives both main() and the parity
    test)."""
    from repro.core.runtime import Device, OverlaySpec
    from repro.core.session import Session
    from repro.serve import InferenceServer, Request
    from repro.serve.models import FAMILY_PIPELINE, PIPELINES

    cfg = get_arch(arch)
    family = FAMILY_PIPELINE[cfg.family]
    dim = PIPELINES[family].state_dim
    spec = OverlaySpec(width=8, height=8, dsp_per_fu=2)
    rng = np.random.default_rng(seed)
    with Session([Device(f"ovl{i}", spec) for i in range(devices)]) as s:
        srv = InferenceServer(s, {family: slo}, max_batch=max_batch)
        reqs = [Request(family, rng.standard_normal(dim), decode_steps=gen,
                        t_arrival_us=float(i) * 25.0)
                for i in range(n_requests)]
        for r in reqs:
            srv.submit(r)
        makespan = srv.run()
        stats = s.stats()["serving"]
        stats["makespan_us"] = makespan
        stats["family"] = family
        srv.close()
    return stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALL_ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="legacy: JAX batch size; default: max batch")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--legacy", action="store_true",
                    help="deprecated raw-JAX loop (bypasses the Session)")
    ap.add_argument("--requests", type=int, default=16,
                    help="overlay path: synthetic trace length")
    ap.add_argument("--slo", choices=("realtime", "standard", "batch"),
                    default="standard")
    args = ap.parse_args()
    enable_compile_cache()

    if args.legacy:
        _legacy_main(args)
        return

    stats = serve_overlay(args.arch, args.requests, args.gen, args.slo,
                          max_batch=args.batch)
    fam = stats["family"]
    m = stats["models"][fam]
    print(f"arch={args.arch} -> pipeline={fam} slo={args.slo} "
          f"max_batch={args.batch}")
    print(f"admitted={stats['admitted']} completed={stats['completed']} "
          f"rejected={stats['rejected']} "
          f"degraded_steps={stats['degraded_steps']}")
    print(f"iterations={m['iterations']} "
          f"occupancy_ewma={m['occupancy_ewma']:.2f} "
          f"makespan={stats['makespan_us']:.0f}us")
    for cls, lat in stats["latency_us"].items():
        print(f"  {cls}: n={lat['n']} p50={lat['p50']:.0f}us "
              f"p99={lat['p99']:.0f}us")


if __name__ == "__main__":
    main()
