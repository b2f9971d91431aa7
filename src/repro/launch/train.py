"""End-to-end training driver.

On real hardware this runs the production mesh; on this CPU container it
drives reduced configs (``--reduced``) through the *identical* code path:
pjit'd train_step, sharded state, checkpoint/restart, straggler watchdog.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt /tmp/ckpt
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ALL_ARCHS, get_arch, reduced_config
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.registry import build_model, input_shardings
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainLoop, TrainLoopConfig
from repro.train.step import init_state, make_train_step, state_specs


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALL_ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg, remat_policy=args.remat)

    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.model_shards))
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(1, args.steps // 10),
                          total_steps=args.steps)

    with jax.default_device(jax.devices()[0]):
        state = init_state(model, jax.random.PRNGKey(0))
    st_sh = _named(mesh, state_specs(model))
    state = jax.device_put(state, st_sh)
    b_sh = _named(mesh, input_shardings(cfg, "train"))

    step_fn = jax.jit(make_train_step(model, opt_cfg),
                      in_shardings=(st_sh, b_sh),
                      out_shardings=(st_sh, None),
                      donate_argnums=(0,))

    ds = SyntheticTokens(cfg.vocab, args.seq, args.batch)
    extra: Dict[str, Any] = {}
    if cfg.frontend == "vision":
        extra["input_embeds"] = np.zeros(
            (args.batch, max(1, args.seq // 8), cfg.d_model), np.float32)
    if cfg.frontend == "audio":
        extra["input_embeds"] = np.zeros(
            (args.batch, args.seq, cfg.d_model), np.float32)

        class AudioDS(SyntheticTokens):
            def batch_at(self, step):
                b = super().batch_at(step)
                n = max(8, args.seq // 4)
                return {"tokens": b["tokens"][:, :n],
                        "labels": b["labels"][:, :n]}
        ds = AudioDS(cfg.vocab, args.seq, args.batch)

    loop = TrainLoop(step_fn, state, ds,
                     TrainLoopConfig(total_steps=args.steps,
                                     checkpoint_dir=args.ckpt,
                                     checkpoint_every=max(10,
                                                          args.steps // 4)),
                     extra_batch=extra or None)
    resumed = loop.try_restore()
    print(f"arch={args.arch} reduced={args.reduced} mesh={dict(mesh.shape)} "
          f"params={cfg.param_count():,} resumed={resumed} "
          f"start={loop.start_step}")
    out = loop.run()
    for m in out["metrics"]:
        print(f"  step {m['step']:5d} loss {m['loss']:.4f} "
              f"gnorm {m['grad_norm']:.3f} {m['dt_s']*1e3:.0f}ms")
    if out["stragglers"]:
        print(f"  straggler events: {len(out['stragglers'])}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
