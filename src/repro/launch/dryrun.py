import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch × shape × mesh) combination lowers,
SPMD-partitions, and compiles on the production mesh, and extract the
roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k [--multi-pod] [--remat dots] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The XLA_FLAGS line above MUST precede any jax import: jax locks the device
count at first init, and the production mesh needs 512 host placeholders.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.registry import ALL_ARCHS, SHAPES, get_arch, \
    shape_applicable  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.registry import build_model, input_shardings, \
    input_specs  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.step import (init_state, make_prefill_step,  # noqa: E402
                              make_serve_step, make_train_step, state_specs)


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def _lower_cell(cfg, model, seq, batch, kind, multi_pod, mesh,
                grad_accum: int = 1):
    """Lower one (cfg × shape × mesh) step; returns the Lowered object."""
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if kind == "train":
        step_fn = make_train_step(model, AdamWConfig(),
                                  grad_accum=grad_accum)
        state_shape = jax.eval_shape(
            lambda k: init_state(model, k), key_spec)
        batch_shapes = input_specs(cfg, seq, batch, kind, multi_pod)
        st_sh = _named(mesh, state_specs(model, multi_pod))
        b_sh = _named(mesh, input_shardings(cfg, kind, multi_pod,
                                            batch_size=batch))
        lowered = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None),
                          donate_argnums=(0,)).lower(
            state_shape, batch_shapes)
    elif kind == "prefill":
        step_fn = make_prefill_step(model)
        params_shape = jax.eval_shape(model.init, key_spec)
        batch_shapes = input_specs(cfg, seq, batch, kind, multi_pod)
        p_sh = _named(mesh, model.param_specs(multi_pod))
        b_sh = _named(mesh, input_shardings(cfg, kind, multi_pod,
                                            batch_size=batch))
        lowered = jax.jit(step_fn, in_shardings=(p_sh, b_sh),
                          out_shardings=None).lower(
            params_shape, batch_shapes)
    else:  # decode
        step_fn = make_serve_step(model)
        params_shape = jax.eval_shape(model.init, key_spec)
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(batch, seq))
        toks = input_specs(cfg, seq, batch, kind, multi_pod)
        p_sh = _named(mesh, model.param_specs(multi_pod))
        seq_sharded = (batch == 1)          # long_500k: shard the KV seq
        c_sh = _named(mesh, model.cache_specs(multi_pod,
                                              seq_sharded=seq_sharded,
                                              model_axis=16))
        t_sh = _named(mesh, input_shardings(cfg, kind, multi_pod,
                                            batch_size=batch))
        lowered = jax.jit(
            step_fn,
            in_shardings=(p_sh, c_sh, t_sh["tokens"], t_sh["cur_pos"]),
            out_shardings=(None, c_sh),
            donate_argnums=(1,)).lower(
            params_shape, cache_shape,
            toks["tokens"], toks["cur_pos"])
    return lowered


def _costs(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = rl.collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _lin(c1, c2, k1, k2, L):
    """cost(L) = fixed + L·body, solved from two layer counts.

    cost_analysis counts a lax.scan body ONCE regardless of trip count, so
    the full-depth compile underreports per-layer work.  Compiling the same
    step at depths k1 < k2 isolates the body; the formula is exact whether
    XLA keeps the loop or unrolls it.
    """
    def one(a, b):
        body = max(0.0, (b - a) / (k2 - k1))
        fixed = max(0.0, a - k1 * body)
        return fixed + L * body
    f = one(c1[0], c2[0])
    by = one(c1[1], c2[1])
    keys = set(c1[2]) | set(c2[2])
    coll = {k: one(c1[2].get(k, 0.0), c2[2].get(k, 0.0)) for k in keys}
    return f, by, coll


def _bilin_scalar(cc, k1, k2, g1, g2, L, G):
    """Solve cost = α + β·L + γ·G + δ·L·G from 4 (layers, accum) points and
    extrapolate to (L, G); negative components clamp to 0."""
    c11, c21 = cc[(k1, g1)], cc[(k2, g1)]
    c12, c22 = cc[(k1, g2)], cc[(k2, g2)]
    dk, dg = (k2 - k1), (g2 - g1)
    d = max(0.0, (c22 - c21 - c12 + c11) / (dk * dg))
    b = max(0.0, (c21 - c11) / dk - d * g1)
    g_ = max(0.0, (c12 - c11) / dg - d * k1)
    a = max(0.0, c11 - b * k1 - g_ * g1 - d * k1 * g1)
    return a + b * L + g_ * G + d * L * G


def _small_cfgs(cfg):
    """Two reduced-depth clones for the linear cost model.  A hybrid keeps
    its first layers and the sites among them: the clones end at the
    second and fourth site, so they differ by two sites and the layers
    between them, the whole model's ratio of sites to layers."""
    import dataclasses as dc
    ids = cfg.hybrid_layer_ids
    k1, k2 = (ids[1], ids[3]) if ids else (1, 2)
    kw1, kw2 = {"n_layers": k1}, {"n_layers": k2}
    if ids:
        kw1["hybrid_layer_ids"] = tuple(i for i in ids if i < k1)
        kw2["hybrid_layer_ids"] = tuple(i for i in ids if i < k2)
    if cfg.enc_layers:
        kw1["enc_layers"] = k1
        kw2["enc_layers"] = k2
    return dc.replace(cfg, **kw1), dc.replace(cfg, **kw2), k1, k2


def dryrun_cell(arch: str, shape: str, multi_pod: bool = False,
                remat: str = "full", attn_impl: str = "ref",
                verbose: bool = True, correct_scan_costs: bool = True,
                ssd_dtype: str = "f32", moe_grouped: bool = False,
                parallel_block: bool = False, ssm_chunk: int = 0,
                grad_accum: int = 1, seq_shard_prefill: bool = False
                ) -> Optional[Dict[str, Any]]:
    cfg = get_arch(arch)
    if ssm_chunk:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, ssm_chunk=ssm_chunk)
    seq, batch, kind = SHAPES[shape]
    skip = shape_applicable(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape, "skipped": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    opts = dict(remat_policy=remat, attn_impl=attn_impl,
                ssd_dtype=ssd_dtype, moe_grouped=moe_grouped,
                parallel_block=parallel_block)
    model = build_model(cfg, **opts)
    if seq_shard_prefill and kind == "prefill" and hasattr(model,
                                                           "act_sharding"):
        batch_ax = ("pod", "data") if multi_pod else "data"
        model.act_sharding = NamedSharding(mesh, P(batch_ax, "model", None))

    t0 = time.perf_counter()
    lowered = _lower_cell(cfg, model, seq, batch, kind, multi_pod, mesh,
                          grad_accum=grad_accum)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    roof = rl.analyze(compiled, cfg, seq, batch, kind, n_dev, remat=remat)

    if correct_scan_costs:
        cfg1, cfg2, k1, k2 = _small_cfgs(cfg)
        if grad_accum > 1 and kind == "train":
            # two scan axes (layers × microbatches): bilinear cost model
            # cost = α + β·L + γ·G + δ·L·G solved from 4 reduced compiles
            g1, g2 = 2, 4
            cc = {}
            for cfg_s, kk in ((cfg1, k1), (cfg2, k2)):
                ms = build_model(cfg_s, **opts)
                for gg in (g1, g2):
                    cc[(kk, gg)] = _costs(_lower_cell(
                        cfg_s, ms, seq, batch, kind, multi_pod, mesh,
                        grad_accum=gg).compile())
            L, G = cfg.n_layers, grad_accum
            f = _bilin_scalar({kg: cc[kg][0] for kg in cc},
                              k1, k2, g1, g2, L, G)
            by = _bilin_scalar({kg: cc[kg][1] for kg in cc},
                               k1, k2, g1, g2, L, G)
            keys = set().union(*(cc[kg][2] for kg in cc))
            coll = {k: _bilin_scalar(
                {kg: cc[kg][2].get(k, 0.0) for kg in cc},
                k1, k2, g1, g2, L, G) for k in keys}
        else:
            m1 = build_model(cfg1, **opts)
            m2 = build_model(cfg2, **opts)
            c1 = _costs(_lower_cell(cfg1, m1, seq, batch, kind, multi_pod,
                                    mesh, grad_accum=grad_accum).compile())
            c2 = _costs(_lower_cell(cfg2, m2, seq, batch, kind, multi_pod,
                                    mesh, grad_accum=grad_accum).compile())
            f, by, coll = _lin(c1, c2, k1, k2, cfg.n_layers)
        roof = rl.Roofline(
            flops_per_device=max(f, roof.flops_per_device),
            bytes_per_device=max(by, roof.bytes_per_device),
            coll_bytes_per_device=max(sum(coll.values()),
                                      roof.coll_bytes_per_device),
            coll_breakdown=coll,
            model_flops_global=roof.model_flops_global,
            n_devices=n_dev)

    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind, "seq": seq, "batch": batch,
        "opts": {"remat": remat, "ssd_dtype": ssd_dtype,
                 "moe_grouped": moe_grouped,
                 "parallel_block": parallel_block,
                 "ssm_chunk": ssm_chunk or cfg.ssm_chunk,
                 "grad_accum": grad_accum},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "bytes_per_device": getattr(mem, "temp_size_in_bytes", 0) +
        getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "roofline": roof.to_dict(),
    }
    if verbose:
        print(f"[{arch} × {shape} × {result['mesh']}] "
              f"compile={t_compile:.1f}s "
              f"mem/dev={result['bytes_per_device']/2**30:.2f}GiB "
              f"compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"coll={roof.collective_s*1e3:.2f}ms "
              f"dominant={roof.dominant} mfu={roof.mfu:.3f}")
        print("  memory_analysis:", mem)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALL_ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "dots",
                                                        "none"])
    ap.add_argument("--attn-impl", default="ref")
    ap.add_argument("--ssd-dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--moe-grouped", action="store_true")
    ap.add_argument("--parallel-block", action="store_true",
                    help="beyond-paper PaLM-style block (dense/vlm)")
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seq-shard-prefill", action="store_true",
                    help="context-parallel prefill: activations seq-sharded "
                         "over the model axis (§Perf B3)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ALL_ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    results = []
    for a, s in cells:
        try:
            r = dryrun_cell(a, s, multi_pod=args.multi_pod,
                            remat=args.remat, attn_impl=args.attn_impl,
                            ssd_dtype=args.ssd_dtype,
                            moe_grouped=args.moe_grouped,
                            parallel_block=args.parallel_block,
                            ssm_chunk=args.ssm_chunk,
                            grad_accum=args.grad_accum,
                            seq_shard_prefill=args.seq_shard_prefill)
        except Exception as e:  # noqa: BLE001 — report and continue
            r = {"arch": a, "shape": s, "error": f"{type(e).__name__}: {e}"}
            print(f"[{a} × {s}] FAILED: {r['error']}")
        results.append(r)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    ok = sum(1 for r in results if "roofline" in r)
    sk = sum(1 for r in results if "skipped" in r)
    err = sum(1 for r in results if "error" in r)
    print(f"\ndry-run: {ok} compiled, {sk} skipped (documented), "
          f"{err} failed")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
